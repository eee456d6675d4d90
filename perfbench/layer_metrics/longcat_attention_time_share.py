"""Kernels: both attention kernels (the absorbed latent decode kernel and the
flash forward of the prefill) as a share of the device's busy time."""

from perfbench import metric_lib_longcat as lib


def read(records):
    return lib.attention_time_share(records)
