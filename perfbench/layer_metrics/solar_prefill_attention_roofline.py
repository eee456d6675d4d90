"""Kernels: the attention layer's prefill (the flash forward kernel, causal,
no positions, grouped-query) share of its roofline over the traced prefill
dispatches: the lower triangle's products at the prompts' real lengths
(``kernel_costs_solar.prefill_attention``)."""

from perfbench import metric_lib_solar as lib


def read(records):
    return lib.prefill_attention_roofline(records)
