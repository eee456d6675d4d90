"""Model step: the device time of one prefill dispatch (one prompt in its
bucket of 1024 to 16384 token places):
the median length of the compiled-program runs that hold the prefill's
masked attention kernel."""

from perfbench import metric_lib_glm52 as lib


def read(records):
    return lib.prefill_dispatch_ms(records)
