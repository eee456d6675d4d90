"""Model step: device time of one decode dispatch of the latent-attention
decoder (``tokens_per_dispatch`` tokens for every slot): the median length
of the compiled-program runs in the trace that hold the latent decode
kernel."""

from perfbench import metric_lib_glm as lib


def read(records):
    return lib.module_ms(records, lib.DECODE_KERNEL)
