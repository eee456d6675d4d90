"""Model step: a decode dispatch's share of its memory roofline: the bytes a
token step must move (the weights outside the routed experts, the held
experts HIT by the round's counters, the resident rows of all eight latent
pools, the head; ``kernel_costs_longcat.decode_step_bytes``) at the peak
bandwidth, over the device time of the runs that hold the decode kernel."""

from perfbench import metric_lib_longcat as lib


def read(records):
    return lib.decode_hbm_roofline(records)
