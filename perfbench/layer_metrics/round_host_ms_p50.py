"""Serving host plane: median of a dispatching round's host time: the
``round`` span less its ``step.dispatch`` and ``wait`` children."""

from perfbench import program_records as pr


def read(records):
    return pr.read_rounds(records, pr.round_host_ms_p50, log=True)
