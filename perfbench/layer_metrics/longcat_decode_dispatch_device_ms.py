"""Model step: the device time of one decode dispatch (4 token steps of
every live slot through 4 layers of two attention blocks): the median
length of the compiled-program runs that hold the absorbed latent decode
kernel."""

from perfbench import metric_lib_longcat as lib


def read(records):
    return lib.decode_dispatch_ms(records)
