"""Serving host plane: median self time of an ``admit_pending`` call that
admitted (``admit`` less its ``prefill`` children): the session's own
Python for the round's admissions, slots and pages taken and the feeds
laid out."""

from perfbench import metric_lib_glm as lib
from perfbench import program_records as pr


def read(records):
    return lib.read_rounds(records, pr.span_ms_p50, "admit", True)
