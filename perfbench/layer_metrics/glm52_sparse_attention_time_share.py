"""Kernels: indexer scores, selection, gather and attention over the chosen
positions, decode and prefill, as a share of the device's busy time: the
three kernels by name, the composed paths (top-k sort, row gather, the
prefill's scores and bisection) by their results' shapes."""

from perfbench import metric_lib_glm52 as lib


def read(records):
    return lib.sparse_attention_time_share(records)
