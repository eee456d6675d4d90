"""Kernels: the linear-attention mechanism's share of the device's busy time:
both convolutions, the chunked prefill and the one-token state update, by
their kernels' names (``metric_lib_solar.linear_time_share`` says what a
device event cannot tell from the block's other fusions)."""

from perfbench import metric_lib_solar as lib


def read(records):
    return lib.linear_time_share(records)
