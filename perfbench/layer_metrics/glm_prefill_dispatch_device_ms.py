"""Model step: device time of one prefill dispatch (several prompts of one
length bucket): the median length of the compiled-program runs in the trace
that hold the flash forward kernel."""

from perfbench import metric_lib_glm as lib


def read(records):
    return lib.module_ms(records, lib.PREFILL_KERNEL)
