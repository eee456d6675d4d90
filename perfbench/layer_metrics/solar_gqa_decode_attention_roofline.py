"""Kernels: the grouped-query decode attention's
(``gqa_paged_decode_attention``, 64 query heads on 8 key/value heads) share
of its roofline over the traced decode dispatches: each visible K row and
V row read once for its group of query heads
(``kernel_costs_solar.gqa_decode_attention``)."""

from perfbench import metric_lib_solar as lib


def read(records):
    return lib.gqa_decode_attention_roofline(records)
