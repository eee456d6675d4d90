"""Serving host plane, above the knee: median time a dispatching round
spends handing events to the streams' queues (its ``handoff`` spans
together)."""

from perfbench import program_records as pr


def read(records):
    return pr.read_rounds(records, pr.per_round_ms_p50, 'handoff')
