"""Model step: a decode dispatch's share of its memory roofline: the bytes
a token step must read (every parameter but the embedding once, the live
rows of the full layer and the VISIBLE rows of the four window layers
once; ``kernel_costs_trinity.decode_step_bytes``) at the peak bandwidth,
over the device time of the runs that hold the window decode kernel."""

from perfbench import metric_lib_trinity as lib


def read(records):
    return lib.decode_hbm_roofline(records)
