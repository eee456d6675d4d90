"""Serving host plane, above the knee: median length of one executor call
of an admission (``admit.dispatch``: the encoder forward)."""

from perfbench import program_records as pr


def read(records):
    return pr.read_rounds(records, pr.span_ms_p50, 'admit.dispatch')
