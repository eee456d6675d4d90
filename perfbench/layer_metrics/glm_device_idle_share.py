"""Device: share of the traced window in which no operation ran on the
device (1 - busy/window, from the profiler's trace)."""

from perfbench import metric_lib


def read(records):
    return metric_lib.idle_share(records)
