"""Kernels: the window decode kernel's (``gqa_window_decode_attention``)
share of its roofline: the rows its queries can see (at most
``sliding_window`` a slot; NOT the whole pages its ring holds, which the
kernel copies) once for all 32 query heads
(``kernel_costs_trinity.decode_attention``) over the kernel's OWN time in
the trace."""

from perfbench import metric_lib_trinity as lib


def read(records):
    return lib.window_decode_attention_roofline(records)
