"""Kernels: the three attention kernels' share of the device's busy time:
the window and the full decode kernels and the prefill's flash kernel;
projections, norms, RoPE and the gate are fusions like any other layer's
and are not in it."""

from perfbench import metric_lib_trinity as lib


def read(records):
    return lib.attention_time_share(records)
