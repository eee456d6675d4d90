"""``sat_admit_rows_per_dispatch_p50``: the reader of the round counts the
batched admission writes (``admit_rows`` / ``admit_dispatches``)."""

import importlib
import os

from perfbench import harness


def _round(rows=None, dispatches=None):
    root = {"name": "round", "t0": 0.0, "t1": 1.0, "cpu": None,
            "parent": None}
    if dispatches is not None:
        root.update(admit_rows=rows, admit_dispatches=dispatches,
                    admit_pad_rows=0)
    return {"id": 0, "spans": [root]}


def _metric():
    return importlib.import_module(
        "perfbench.layer_metrics.sat_admit_rows_per_dispatch_p50")


def test_the_median_is_over_the_rounds_that_admitted():
    stat = _metric().rows_per_dispatch_p50
    rounds = [_round(20, 1), _round(), _round(34, 2), _round(28, 1),
              _round()]
    assert stat(rounds) == 20.0       # of 20, 17 and 28
    # one admission an executor call: 1 by construction
    assert stat([_round(3, 3), _round(1, 1)]) == 1.0


def test_a_program_without_the_counts_reads_nothing():
    metric = _metric()
    assert metric.rows_per_dispatch_p50([_round(), _round()]) is None
    assert metric.rows_per_dispatch_p50([]) is None
    # and without a device trace the reader gives no number at all
    assert metric.read({"trace": None}) is None


ENTRY = {
    "name": "sat_admit_rows_per_dispatch_p50", "unit": "count",
    "better": "higher", "source": "program_span",
    "layer": "serving host plane", "moves": "serve_tokens_per_s",
    "workloads": ["serve_base_saturated"]}


def check_declared(bench, root):
    """PR 32's entry is found by its NAME, wherever later additions to the
    list's end leave it."""
    (entry,) = [m for m in bench["per_layer"] if m["name"] == ENTRY["name"]]
    assert entry == ENTRY


def test_the_metric_is_declared_for_the_saturated_cell_alone():
    check_declared(harness.load_json(
        os.path.join(harness.ROOT, "BENCHMARK.json")), harness.ROOT)
