"""Kernels: the routed experts' grouped matrix products' share of their
roofline over the traced dispatches, decode and prefill
(``kernel_costs_glm.expert_matmuls``: every expert that can have got a row
read once a call; memory bound in decode, compute bound in prefill)."""

from perfbench import metric_lib_glm as lib


def read(records):
    return lib.expert_matmul_roofline(records)
