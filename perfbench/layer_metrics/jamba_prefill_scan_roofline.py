"""Kernels: the prefill scan's (``ssm_prefill_scan``) share of
``max(operations / peak, bytes / bandwidth)`` over the traced prefill
dispatches, with each prompt's REAL tokens in the numerator
(``kernel_costs_jamba.prefill_scan``): padding to the bucket is not work.
The scan runs on the vector and transcendental units, whose peaks
``peaks.json`` does not publish, so the share is of the memory roofline
and reads low by construction."""

from perfbench import metric_lib_jamba as lib


def read(records):
    return lib.prefill_scan_roofline(records)
