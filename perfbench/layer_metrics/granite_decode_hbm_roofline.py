"""Model step: a decode dispatch's share of its memory roofline: the bytes a
token step must move (the weights of the held experts that got a token, the
other weights with the tied table, the live slots' matrix state and window
read and written, the visible K/V rows;
``kernel_costs_granite.decode_step_bytes``) at the peak bandwidth, over the
device time of the runs that hold the state update kernel."""

from perfbench import metric_lib_granite as lib


def read(records):
    return lib.decode_hbm_roofline(records)
