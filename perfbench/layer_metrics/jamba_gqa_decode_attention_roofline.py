"""Kernels: the grouped-query paged decode kernel's
(``gqa_paged_decode_attention``) share of its roofline: what its grid must
touch a call (each live position's K row and V row once for all 20 query
heads, ``kernel_costs_jamba.gqa_decode_attention``) over the kernel's OWN
time in the trace."""

from perfbench import metric_lib_jamba as lib


def read(records):
    return lib.gqa_decode_attention_roofline(records)
