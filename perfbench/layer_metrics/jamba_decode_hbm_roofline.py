"""Model step: a decode dispatch's share of its memory roofline: the bytes
a token step must move (every parameter once, the LIVE slots' recurrent
state and window read and written once, the live K/V rows once;
``kernel_costs_jamba.decode_step_bytes``) at the peak bandwidth, over the
device time of the runs that hold the state update kernel."""

from perfbench import metric_lib_jamba as lib


def read(records):
    return lib.decode_hbm_roofline(records)
