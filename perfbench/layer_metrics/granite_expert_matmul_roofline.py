"""Kernels: the routed experts' grouped products' (``gmm``) share of their
roofline over the traced dispatches: the (token, expert) pairs that fell on
the 36 held experts of width 768, each expert that got a token read once a
call (``kernel_costs_granite.expert_matmuls``)."""

from perfbench import metric_lib_granite as lib


def read(records):
    return lib.expert_matmul_roofline(records)
