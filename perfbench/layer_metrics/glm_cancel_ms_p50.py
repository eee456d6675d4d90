"""Serving host plane: median length of one cancel (the ``cancel`` span):
bookkeeping here, the page table and the live mask are fed with the next
decode dispatch, so a client that ends its stream costs no dispatch."""

from perfbench import metric_lib_glm as lib
from perfbench import program_records as pr


def read(records):
    return lib.read_rounds(records, pr.span_ms_p50, "cancel")
