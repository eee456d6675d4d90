"""Model step: the device time of one prefill dispatch (a rung of prompt rows
of a bucket, at most 8192 token places), the median over the traced runs
that hold the chunked delta-rule kernel (``delta_rule_chunk_prefill``)."""

from perfbench import metric_lib_solar as lib


def read(records):
    return lib.prefill_dispatch_ms(records)
