"""Kernels: the grouped products of the held experts (``gmm``) over the
pairs that fell on them, the weights of the experts that got a token read
once a call, against the kernel's own device time."""

from perfbench import metric_lib_glm52 as lib


def read(records):
    return lib.expert_matmul_roofline(records)
