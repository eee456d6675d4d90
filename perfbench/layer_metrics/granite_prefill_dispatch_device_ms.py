"""Model step: the device time of one prefill dispatch (a rung of prompt rows
of a bucket, at most 4096 token places), the median over the traced runs
that hold the chunked Mamba-2 kernel (``ssd_chunk_prefill``)."""

from perfbench import metric_lib_granite as lib


def read(records):
    return lib.prefill_dispatch_ms(records)
