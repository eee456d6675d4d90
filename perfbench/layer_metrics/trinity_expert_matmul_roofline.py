"""Kernels: the grouped expert products' (``gmm``) share of their roofline:
every expert that can have got a row read once a call, the (token,
expert) rows in and out once (``kernel_costs_trinity.expert_matmuls``),
decode steps and prefill dispatches alike, over the kernel's own time."""

from perfbench import metric_lib_trinity as lib


def read(records):
    return lib.expert_matmul_roofline(records)
