"""Kernels: the latent paged decode attention kernel's share of its
roofline over the traced dispatches: each live slot's cached rows read
once for all heads (``kernel_costs_glm.latent_decode_attention``); memory
bound."""

from perfbench import metric_lib_glm as lib


def read(records):
    return lib.latent_decode_attention_roofline(records)
