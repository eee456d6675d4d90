"""Serving host plane, above the knee: slots one table dispatch of a
cancel repoints, the median over the window's rounds that cancelled with
a dispatch (the round's ``cancel_rows`` over its ``cancel_dispatches``).
1 by construction while a cancel is an executor call of its own; None on
a program whose rounds carry no such counts."""

from perfbench import program_records as pr
from perfbench.metric_lib import median


def rows_per_dispatch_p50(rounds):
    roots = [r["spans"][0] for r in rounds]
    return median([root["cancel_rows"] / float(root["cancel_dispatches"])
                   for root in roots if root.get("cancel_dispatches")])


def read(records):
    return pr.read_rounds(records, rows_per_dispatch_p50)
