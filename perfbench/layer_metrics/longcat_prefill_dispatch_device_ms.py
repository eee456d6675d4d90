"""Model step: the device time of one prefill dispatch (up to 4096 token
places in a bucket of 512 to 4096): the median length of the
compiled-program runs that hold the flash forward."""

from perfbench import metric_lib_longcat as lib


def read(records):
    return lib.prefill_dispatch_ms(records)
