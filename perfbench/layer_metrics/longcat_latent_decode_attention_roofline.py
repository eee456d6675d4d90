"""Kernels: absorbed-form latent decode attention at 64 heads over a
512-wide latent part, eight calls a token step (two pools a layer), each
resident row read once for all heads, against the kernel's own device
time."""

from perfbench import metric_lib_longcat as lib


def read(records):
    return lib.latent_decode_attention_roofline(records)
