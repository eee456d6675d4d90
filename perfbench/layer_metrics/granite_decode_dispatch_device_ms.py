"""Model step: the device time of one decode dispatch (4 token steps of every
slot), the median over the traced runs that hold the one-token Mamba-2 state
update kernel (``ssd_state_update``)."""

from perfbench import metric_lib_granite as lib


def read(records):
    return lib.decode_dispatch_ms(records)
