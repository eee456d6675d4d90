"""Kernels: the full layer's decode kernel's
(``gqa_paged_decode_attention``) share of its roofline: every live
position's K row and V row once for all 32 query heads
(``kernel_costs_trinity.decode_attention``) over the kernel's OWN time in
the trace (the window layers' kernel carries another name)."""

from perfbench import metric_lib_trinity as lib


def read(records):
    return lib.full_decode_attention_roofline(records)
