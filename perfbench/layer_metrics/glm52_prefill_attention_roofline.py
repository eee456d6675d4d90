"""Kernels: the prefill's masked attention
(``sparse_latent_prefill_attention``): the products of the positions each
row attends (``min(t + 1, index_topk)``) against the kernel's own device
time; the kernel walks the whole lower triangle, so a long prompt reads
low."""

from perfbench import metric_lib_glm52 as lib


def read(records):
    return lib.prefill_attention_roofline(records)
