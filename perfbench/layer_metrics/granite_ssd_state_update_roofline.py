"""Kernels: the one-token Mamba-2 update's (``ssd_state_update``) share of its
roofline over the traced decode dispatches: the live slots' matrix state
read once and written once a token a layer
(``kernel_costs_granite.state_update``); memory bound."""

from perfbench import metric_lib_granite as lib


def read(records):
    return lib.state_update_roofline(records)
