"""Kernels: the routed experts' share of the device's busy time: the grouped
products and the sorts of routing and dispatch, less the selection's
sorts."""

from perfbench import metric_lib_glm52 as lib


def read(records):
    return lib.expert_time_share(records)
