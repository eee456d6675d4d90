"""Kernels: the Mamba-2 mechanism's share of the device's busy time: both
convolutions, the chunked prefill and the one-token state update, by their
kernels' names (``metric_lib_granite.ssm_time_share`` says what a device
event cannot tell from the block's other fusions)."""

from perfbench import metric_lib_granite as lib


def read(records):
    return lib.ssm_time_share(records)
