"""Kernels: the one-token delta-rule update's (``delta_rule_state_update``)
share of its roofline over the traced decode dispatches: the live slots'
matrix state read once and written once a token a layer
(``kernel_costs_solar.state_update``); memory bound."""

from perfbench import metric_lib_solar as lib


def read(records):
    return lib.state_update_roofline(records)
