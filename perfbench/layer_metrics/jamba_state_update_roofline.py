"""Kernels: the one-token state update's (``ssm_state_update``) share of
its roofline over the traced decode dispatches: the live slots' state read
once and written once a token a layer
(``kernel_costs_jamba.state_update``); memory bound."""

from perfbench import metric_lib_jamba as lib


def read(records):
    return lib.state_update_roofline(records)
