"""Kernels: the paged decode self-attention kernel's share of its roofline
over the traced dispatches. Needed: each live slot's own keys and values
read once (``kernel_costs.decode_attention``, float32 pages); memory
bound."""

from perfbench import metric_lib as lib


def read(records):
    return lib.attention_roofline(records, lib.DECODE_KERNEL, 'self')
