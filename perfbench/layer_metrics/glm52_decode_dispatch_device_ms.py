"""Model step: the device time of one decode dispatch (4 token steps of
every live slot): the median length of the compiled-program runs that
hold the sparse latent decode kernel."""

from perfbench import metric_lib_glm52 as lib


def read(records):
    return lib.decode_dispatch_ms(records)
