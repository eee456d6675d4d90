"""Kernels: the state-space kernels' share of the device's busy time: both
convolutions, the prefill scan and the one-token state update
(``kernels/selective_scan.py``), found by name; the mixer's products and
norms are fusions like any other layer's and are not in it
(``metric_lib_jamba.ssm_time_share``)."""

from perfbench import metric_lib_jamba as lib


def read(records):
    return lib.ssm_time_share(records)
