"""Serving host plane: median length of one admission (the ``prefill``
span of the program's request traces: slot and pages taken, one encoder
dispatch, the slot registered)."""

from perfbench import metric_lib as lib


def read(records):
    return lib.trace_stat_p50_ms(records, 'prefill_s')
