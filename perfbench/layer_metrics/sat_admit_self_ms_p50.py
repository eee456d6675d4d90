"""Serving host plane, above the knee: median self time of an admission
(``admit`` less its ``admit.dispatch`` children): the session's own
Python per request."""

from perfbench import program_records as pr


def read(records):
    return pr.read_rounds(records, pr.span_ms_p50, 'admit', True)
