"""Serving host plane: median host time between the end of one decode
dispatch and the start of the next (the benchmark's span around
``step``): admissions, cancels and event hand-off happen there."""

from perfbench import metric_lib as lib


def read(records):
    return lib.dispatch_gap_p50_ms(records)
