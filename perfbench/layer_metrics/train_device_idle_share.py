"""Device: share of the traced window in which no operation ran on the
device (1 - busy/window, from the profiler's trace, mean over chips)."""

from perfbench import metric_lib as lib


def read(records):
    return lib.idle_share(records)
