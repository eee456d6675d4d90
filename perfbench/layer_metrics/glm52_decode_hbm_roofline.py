"""Model step: a decode dispatch's share of its memory roofline: the bytes a
token step must move (the weights of the held experts that got a token,
the other weights but the embedding table, the SELECTED latent rows, every
resident narrow key; ``kernel_costs_glm52.decode_step_bytes``) at the peak
bandwidth, over the device time of the runs that hold the sparse decode
kernel."""

from perfbench import metric_lib_glm52 as lib


def read(records):
    return lib.decode_hbm_roofline(records)
