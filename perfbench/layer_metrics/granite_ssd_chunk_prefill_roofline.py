"""Kernels: the chunked Mamba-2 prefill's (``ssd_chunk_prefill``) share of its
roofline over the traced prefill dispatches: the greater of its time at the
matrix unit's peak and at the memory's at the prompts' REAL tokens, the
products under the diagonal only (``kernel_costs_granite.chunk_prefill``),
over the kernel's time."""

from perfbench import metric_lib_granite as lib


def read(records):
    return lib.chunk_prefill_roofline(records)
