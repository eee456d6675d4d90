"""Kernels: the grouped products of the held real experts (``gmm``) over the
pairs that fell on them, the weights of the experts that got a token read
once a call, against the kernel's own device time; a choice that fell on
an identity is no product."""

from perfbench import metric_lib_longcat as lib


def read(records):
    return lib.expert_matmul_roofline(records)
