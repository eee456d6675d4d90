"""Kernels: the routed experts' share of the device's busy time: the grouped
products with their metadata kernel and the sorts of routing and
dispatch."""

from perfbench import metric_lib_longcat as lib


def read(records):
    return lib.expert_time_share(records)
