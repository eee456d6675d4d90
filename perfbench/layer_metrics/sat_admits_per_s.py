"""Serving host plane: requests admitted a second over the window (the
benchmark's span around ``admit_pending``)."""

from perfbench import metric_lib as lib


def read(records):
    return lib.admits_per_s(records)
