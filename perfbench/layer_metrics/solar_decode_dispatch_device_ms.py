"""Model step: the device time of one decode dispatch (4 token steps of every
slot), the median over the traced runs that hold the one-token state
update kernel (``delta_rule_state_update``)."""

from perfbench import metric_lib_solar as lib


def read(records):
    return lib.decode_dispatch_ms(records)
