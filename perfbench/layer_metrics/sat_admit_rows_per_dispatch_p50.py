"""Serving host plane, above the knee: sources one admission dispatch
encodes, the median over the window's rounds that admitted (the round's
``admit_rows`` over its ``admit_dispatches``). 1 by construction while an
admission is an executor call of its own; None on a program whose rounds
carry no such counts."""

from perfbench import program_records as pr
from perfbench.metric_lib import median


def rows_per_dispatch_p50(rounds):
    roots = [r["spans"][0] for r in rounds]
    return median([root["admit_rows"] / float(root["admit_dispatches"])
                   for root in roots if root.get("admit_dispatches")])


def read(records):
    return pr.read_rounds(records, rows_per_dispatch_p50)
