"""Kernels: the chunked delta-rule prefill's (``delta_rule_chunk_prefill``)
share of its roofline over the traced prefill dispatches: the greater of
its time at the matrix unit's peak and at the memory's at the prompts'
REAL tokens (``kernel_costs_solar.chunk_prefill``), over the kernel's
time."""

from perfbench import metric_lib_solar as lib


def read(records):
    return lib.chunk_prefill_roofline(records)
