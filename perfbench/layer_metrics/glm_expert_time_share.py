"""Kernels: the routed experts' share of the device's busy time: the
grouped products and the sorts of routing and dispatch (what a device
event's name tells of the expert op; its elementwise fusions are
microseconds beside them)."""

from perfbench import metric_lib_glm as lib


def read(records):
    return lib.expert_time_share(records)
