"""``sat_cancel_rows_per_dispatch_p50``: the reader of the round counts the
batched release writes (``cancel_rows`` / ``cancel_dispatches``)."""

import importlib
import os

from perfbench import harness


def _round(rows=None, dispatches=None):
    root = {"name": "round", "t0": 0.0, "t1": 1.0, "cpu": None,
            "parent": None}
    if rows is not None:
        root.update(cancel_rows=rows)
    if dispatches is not None:
        root.update(cancel_dispatches=dispatches, cancel_pad_rows=0)
    return {"id": 0, "spans": [root]}


def _metric():
    return importlib.import_module(
        "perfbench.layer_metrics.sat_cancel_rows_per_dispatch_p50")


def test_the_median_is_over_the_rounds_that_cancelled_with_a_dispatch():
    stat = _metric().rows_per_dispatch_p50
    rounds = [_round(24, 1), _round(), _round(40, 2), _round(31, 1),
              _round()]
    assert stat(rounds) == 24.0       # of 24, 20 and 31
    # one cancel an executor call: 1 by construction
    assert stat([_round(3, 3), _round(1, 1)]) == 1.0
    # a failed repoint leaves rows without a dispatch: not a reading
    assert stat([_round(5, 0), _round(6, 1)]) == 6.0


def test_a_program_without_the_counts_reads_nothing():
    metric = _metric()
    assert metric.rows_per_dispatch_p50([_round(), _round()]) is None
    assert metric.rows_per_dispatch_p50([]) is None
    # a decoder-only session's rounds count rows and never a dispatch
    assert metric.rows_per_dispatch_p50([_round(4), _round(2)]) is None
    # and without a device trace the reader gives no number at all
    assert metric.read({"trace": None}) is None


ENTRY = {
    "name": "sat_cancel_rows_per_dispatch_p50", "unit": "count",
    "better": "higher", "source": "program_span",
    "layer": "serving host plane", "moves": "serve_tokens_per_s",
    "workloads": ["serve_base_saturated"]}


def check_declared(bench, root):
    """PR 34's entry is found by its NAME, wherever later additions to the
    list's end leave it."""
    (entry,) = [m for m in bench["per_layer"] if m["name"] == ENTRY["name"]]
    assert entry == ENTRY


def test_the_metric_is_declared_for_the_saturated_cell_alone():
    check_declared(harness.load_json(
        os.path.join(harness.ROOT, "BENCHMARK.json")), harness.ROOT)
