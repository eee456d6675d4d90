"""Model step: device time of one prefill dispatch (several prompts of one
length bucket): the median length of the compiled-program runs in the trace
that hold the prefill scan kernel (``ssm_prefill_scan``)."""

from perfbench import metric_lib_jamba as lib


def read(records):
    return lib.prefill_dispatch_ms(records)
