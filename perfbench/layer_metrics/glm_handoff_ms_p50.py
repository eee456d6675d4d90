"""Serving host plane: median time a dispatching round spends handing
events to the streams' queues (its ``handoff`` spans together)."""

from perfbench import metric_lib_glm as lib
from perfbench import program_records as pr


def read(records):
    return lib.read_rounds(records, pr.per_round_ms_p50, "handoff")
