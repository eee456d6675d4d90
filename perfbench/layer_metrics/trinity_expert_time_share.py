"""Kernels: the routed experts' share of the device's busy time: the grouped
products with their metadata kernel and the two sorts
(``metric_lib_glm.expert_time_share``'s events)."""

from perfbench import metric_lib_trinity as lib


def read(records):
    return lib.expert_time_share(records)
