"""Model step: a decode dispatch's share of its memory roofline: the bytes
it must read (every parameter but the embedding table once a token step,
the live latent rows once; ``kernel_costs_glm.decode_step_bytes``) at the
peak bandwidth, over the device time of the runs that hold the decode
kernel."""

from perfbench import metric_lib_glm as lib


def read(records):
    return lib.decode_hbm_roofline(records)
