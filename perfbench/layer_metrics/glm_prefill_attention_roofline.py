"""Kernels: the flash forward kernel's share of its roofline in the
prefill dispatches (causal, head width 256, bfloat16): the lower
triangle of each prompt's OWN length
(``kernel_costs_glm.prefill_attention``); padding to the bucket is not
work."""

from perfbench import metric_lib_glm as lib


def read(records):
    return lib.prefill_attention_roofline(records)
