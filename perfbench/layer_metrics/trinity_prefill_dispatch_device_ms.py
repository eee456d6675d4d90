"""Model step: device time of one prefill dispatch (up to 8192 tokens of one
length bucket): the median length of the compiled-program runs in the
trace that hold the flash forward kernel."""

from perfbench import metric_lib_trinity as lib


def read(records):
    return lib.prefill_dispatch_ms(records)
