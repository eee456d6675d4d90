"""Serving host plane, above the knee: median length of one cancel (the
``cancel`` span: the table rewrite and the slot's release)."""

from perfbench import program_records as pr


def read(records):
    return pr.read_rounds(records, pr.span_ms_p50, 'cancel')
