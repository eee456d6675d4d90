"""The host ledger (``perfbench/host_ledger.py``) and its six ``host_``
readers: on fabricated rounds and dispatch records with known answers (the
cells' shapes: executor calls under ``cancel`` and ``admit``, or under
``admit`` > ``prefill`` with a caller that fetches for itself; executor
calls made with tracing off; a parent's records without the account), the
split of the ``single`` calls by the ``handoff`` before them, what an entry
of BENCHMARK.json has to say of a reader, and the CPU rehearsals of the
four saturated cells printing the ledger from the program's own rings.
Orderings and signs, never durations."""

import importlib
import os

import pytest

import _perfbench_tiny as tiny
import _perfbench_glm_tiny
import _perfbench_jamba_tiny
import _perfbench_trinity_tiny

from perfbench import harness, host_ledger
from perfbench import program_records as pr

CELLS = ["serve_base_saturated", "serve_glm_saturated",
         "serve_jamba_saturated", "serve_trinity_longctx"]
# (name, unit, better, source, layer): what BENCHMARK.json says of each
METRICS = [
    ("host_worker_lockwait_share", "%", "lower", "program_span",
     "serving host plane"),
    ("host_handler_cpu_share", "%", "lower", "program_counter",
     "serving host plane"),
    ("host_exec_call_cpu_ms", "ms", "lower", "program_counter",
     "model step"),
    ("host_exec_call_blocked_ms", "ms", "lower", "program_counter",
     "model step"),
    ("host_handler_wakeups_per_s", "1/s", "lower", "program_counter",
     "serving host plane"),
    ("host_watcher_verdicts_per_cancel_row", "count", "higher",
     "program_counter", "serving host plane")]
OPENING = 99.5   # time.time() at the fabricated window's opening


# -- fabricated records -------------------------------------------------------

def _span(name, t0, t1, parent, cpu, **counts):
    return dict(name=name, t0=t0, t1=t1, cpu=cpu, parent=parent, **counts)


def _round(t, shape, k):
    """One round of 1 s from ``t``: it waits 0.1 s, makes two ``single``
    calls and one decode call, and hands off until 2 ms before its end.
    ``shape``: ``base`` (the calls under ``cancel`` and ``admit``, each
    ``.dispatch`` span just its executor call) or ``decoder`` (both under
    ``admit`` > ``prefill``, each ``.dispatch`` span 9 and 10 ms longer
    than its call: a caller that fetches for itself, as the benchmark's
    tap does, waits for the chip there)."""
    spans = [
        _span("round", t, t + 1.0, None, 0.2, live=4, backlog=2,
              tokens=16, handler_cpu=1.0 + 0.4 * k,
              handler_chunks=100 + 200 * k,
              handler_wakeups=500 + 300 * k, watcher_cancel=40 + 26 * k,
              cancel_rows=13 * (k + 1)),
        _span("wait", t, t + 0.1, 0, 0.0)]
    if shape == "base":
        spans += [
            _span("cancel", t + 0.1, t + 0.2, 0, 0.02),
            _span("cancel.dispatch", t + 0.101, t + 0.181, 2, 0.015),
            _span("admit", t + 0.2, t + 0.4, 0, 0.05),
            _span("admit.dispatch", t + 0.25, t + 0.35, 4, 0.03)]
    else:
        spans += [
            _span("admit", t + 0.1, t + 0.4, 0, 0.07),
            _span("prefill", t + 0.1, t + 0.2, 2, 0.02),
            _span("prefill.dispatch", t + 0.101, t + 0.19, 3, 0.015),
            _span("prefill", t + 0.2, t + 0.4, 2, 0.05),
            _span("prefill.dispatch", t + 0.25, t + 0.36, 5, 0.03)]
    n = len(spans)
    spans += [
        _span("step", t + 0.4, t + 0.8, 0, 0.05),
        _span("step.dispatch", t + 0.42, t + 0.78, n, 0.03),
        _span("handoff", t + 0.8, t + 0.998, 0, 0.08)]
    return {"id": k + 1, "spans": spans}


def _call(origin, t1, phases, cpu):
    return {"origin": origin, "t1": t1, "wall_s": sum(phases.values()),
            "phases": phases, "cpu": cpu}


def _calls(t):
    """The round's three dispatch records: a ``single`` call of 0.08 s
    that begins 1 ms into the round's work, one of 0.1 s that waits 0.03 s
    for the chip, and the decode call, 0.3 s of its 0.36 the chip's."""
    return [
        _call("single", t + 0.181,
              {"feed": 0.01, "dispatch": 0.05, "fetch": 0.01, "host": 0.01},
              {"feed": 0.005, "dispatch": 0.01, "fetch": 0.005,
               "host": 0.0}),
        _call("single", t + 0.35,
              {"feed": 0.02, "compile": 0.001, "dispatch": 0.04,
               "device": 0.03, "fetch": 0.005, "host": 0.004},
              {"feed": 0.01, "compile": 0.001, "dispatch": 0.01,
               "device": 0.0, "fetch": 0.002, "host": 0.003}),
        _call("multi_step", t + 0.78,
              {"feed": 0.01, "dispatch": 0.02, "device": 0.3, "fetch": 0.02,
               "host": 0.01},
              {"feed": 0.005, "dispatch": 0.01, "device": 0.0,
               "fetch": 0.01, "host": 0.005})]


def _as_calls_made_untraced(rounds, calls):
    """Executor calls made with request tracing off keep no CPU."""
    for d in calls:
        d["cpu"] = None


def _as_the_parent_keeps_them(rounds, calls):
    """``cpu`` on four span names, no handlers' account and no count of
    who wakes (``cancel_rows`` it has); plain records."""
    for r in rounds:
        for sp in r["spans"]:
            for key in ("handler_cpu", "handler_chunks",
                        "handler_wakeups", "watcher_cancel"):
                sp.pop(key, None)
            if sp["name"] not in ("round", "wait", "step", "step.dispatch"):
                sp["cpu"] = None
    for d in calls:
        del d["cpu"]


# a case: (shape of the rounds, what is done to the records)
CASES = {"base": ("base", None),
         "decoder": ("decoder", None),
         "calls_untraced": ("base", _as_calls_made_untraced),
         "parent": ("decoder", _as_the_parent_keeps_them)}
# the round less its wait is 0.9 s: cpu 0.2 and the records' device phase
# 0.03 + 0.3, so 0.37 s blocked. In the decoder shape 0.009 + 0.01 s of a
# ``.dispatch`` span lie outside the records: a column of their own, and
# still inside ``blocked``
EXPECTED = {
    "host_worker_lockwait_share": {
        "base": 100 * 0.37 / 0.9, "decoder": 100 * 0.37 / 0.9,
        "calls_untraced": 100 * 0.37 / 0.9, "parent": None},
    # 0.4 CPU seconds between two rounds' ends, 1 s apart
    "host_handler_cpu_share": {
        "base": 40.0, "decoder": 40.0, "calls_untraced": 40.0,
        "parent": None},
    # host phases: 0.02 + 0.025 + 0.03 s of CPU over three calls
    "host_exec_call_cpu_ms": {
        "base": 25.0, "decoder": 25.0, "calls_untraced": None,
        "parent": None},
    # their wall 0.08 + 0.069 + 0.06, less the CPU
    "host_exec_call_blocked_ms": {
        "base": 1e3 * (0.209 - 0.075) / 3,
        "decoder": 1e3 * (0.209 - 0.075) / 3,
        "calls_untraced": None, "parent": None},
    # 300 wake-ups between two rounds' ends, 1 s apart
    "host_handler_wakeups_per_s": {
        "base": 300.0, "decoder": 300.0, "calls_untraced": 300.0,
        "parent": None},
    # 26 verdicts for the 26 slots the rounds AFTER the first released
    # (the first's 13 were cancelled before its end: not the window's)
    "host_watcher_verdicts_per_cancel_row": {
        "base": 1.0, "decoder": 1.0, "calls_untraced": 1.0,
        "parent": None}}


def _fabricate(case):
    shape, change = CASES[case]
    rounds = [_round(100.0 + k, shape, k) for k in range(2)]
    calls = [d for k in range(2) for d in _calls(100.0 + k)]
    if change is not None:
        change(rounds, calls)
    return rounds, calls


def _records(rounds):
    """What ``run.execute`` hands a reader, as far as these read it: a
    device trace, and the benchmark's own stamps before each step."""
    steps = [[sp["t0"] - OPENING, 0.4] for r in rounds
             for sp in r["spans"] if sp["name"] == "step"]
    return {"trace": {"busy_s": 1.0},
            "serve": {"host": {"step": steps}, "seconds": 10.0}}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("name", [m[0] for m in METRICS])
def test_a_reader_on_fabricated_records(name, case, monkeypatch, capsys):
    rounds, calls = _fabricate(case)
    # a ramp round before the window and a record after the last round:
    # neither is read
    ramp = _round(90.0, CASES[case][0], 0)
    if CASES[case][1] is not None:
        CASES[case][1]([ramp], [])
    late = dict(calls[0], t1=150.0)
    monkeypatch.setattr(pr, "program_rounds", lambda: [ramp] + rounds)
    monkeypatch.setattr(pr, "program_dispatches",
                        lambda origin=None: calls + [late])
    reader = importlib.import_module("perfbench.layer_metrics." + name)
    records = _records(rounds)
    value = reader.read(records)
    want = EXPECTED[name][case]
    if want is None:
        assert value is None
    else:
        assert value == pytest.approx(want)
    # one ledger a run, whichever reader comes first
    reader.read(records)
    out = capsys.readouterr().out
    assert out.count("host ledger") == 1
    if case == "parent":
        assert "hold no thread account" in out
        assert "who wakes" not in out
    else:
        assert ("who wakes: the handlers 300.0 times a second; the watcher "
                "posted 26 cancel verdicts for the 26 slots") in out
        assert ("executor calls between the rounds: none" if case
                == "calls_untraced" else "single calls with the handlers "
                "quiet: 4 calls") in out
    # and nothing without a device trace
    assert reader.read({"trace": None}) is None


@pytest.mark.parametrize("case", ["base", "decoder", "calls_untraced"])
def test_the_workers_three_shares_sum_to_100(case):
    rounds, calls = _fabricate(case)
    win = host_ledger.window_of(rounds, calls)
    share = host_ledger.shares(host_ledger.worker_split(win))
    assert share["cpu"] + share["device"] + share["blocked"] \
        == pytest.approx(100.0)
    # the wait for the chip is the records' device phase and nothing
    # else; what of a ``.dispatch`` span the records do not cover (the
    # decoder shape's 9 + 10 ms a round) is its own column and stays
    # inside ``blocked``
    assert share["device"] == pytest.approx(100 * 0.33 / 0.9)
    outside = 0.019 if case == "decoder" else 0.0
    assert share["outside"] == pytest.approx(100 * outside / 0.9)
    # the table's rows split their own walls the same way
    table = host_ledger.span_table(win)
    for name, row in table.items():
        assert row["blocked"] == pytest.approx(
            row["wall"] - row["cpu"] - row["device"])
        assert 0.0 <= row["outside"] <= row["wall"]
    # a decode call's wait for the chip is the step's and the round's,
    # never another span's
    assert table["step.dispatch"]["device"] == pytest.approx(0.6)
    assert table["step"]["device"] == pytest.approx(0.6)
    assert table["handoff"]["device"] == table["wait"]["device"] == 0.0
    if case == "decoder":
        assert table["prefill.dispatch"]["device"] == pytest.approx(0.06)
        assert table["prefill.dispatch"]["outside"] == pytest.approx(0.038)
        assert table["admit"]["outside"] == pytest.approx(0.038)
        assert table["step"]["outside"] == pytest.approx(0.0)
    else:
        assert table["admit.dispatch"]["device"] == pytest.approx(0.06)
        assert table["cancel.dispatch"]["device"] == pytest.approx(0.0)
        assert table["round"]["outside"] == pytest.approx(0.0)
    assert table["round"]["device"] == pytest.approx(0.66)


def test_single_calls_split_by_the_handoff_before_them():
    """A call that begins 3 ms after a ``handoff`` ended runs beside the
    handlers that ``handoff`` woke, alone of the four; the decode calls
    are no ``single`` calls and in neither group."""
    rounds, calls = _fabricate("base")
    after, quiet = host_ledger.handoff_split(rounds, calls)
    assert after is None and quiet["n"] == 4   # 0.1 s of wait lie between
    handoff_end = rounds[0]["spans"][-1]["t1"]
    calls[3]["t1"] = handoff_end + 0.003 + calls[3]["wall_s"]
    after, quiet = host_ledger.handoff_split(rounds, calls)
    assert after["n"] == 1 and quiet["n"] == 3
    assert after["cpu_ms"] == pytest.approx(20.0)
    assert after["blocked_ms"] == pytest.approx(80.0 - 20.0)
    assert quiet["cpu_ms"] == pytest.approx((20.0 + 2 * 25.0) / 3)
    # no handoff at all: every call has quiet handlers
    for r in rounds:
        r["spans"] = [sp for sp in r["spans"] if sp["name"] != "handoff"]
    after, quiet = host_ledger.handoff_split(rounds, calls)
    assert after is None and quiet["n"] == 4
    # and a parent's records are in neither group
    _as_the_parent_keeps_them([], calls)
    assert host_ledger.handoff_split(rounds, calls) == (None, None)


def check_entry(bench, name, unit, better, source, layer):
    """BENCHMARK.json lists the reader, found by its NAME wherever in the
    list it stands, and says what it reads; the saturated serving cells
    alone may list it, ``serve_base_saturated`` among them (a cell whose
    tap fetches for itself reads its own fetch as ``blocked``:
    ``host_ledger.py``)."""
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"], entry["moves"]) == (
        unit, better, source, layer, "serve_tokens_per_s")
    assert "serve_base_saturated" in entry["workloads"]
    assert set(entry["workloads"]) <= set(CELLS)


def check_declared(bench, root):
    for metric in METRICS:
        check_entry(bench, *metric)


@pytest.mark.parametrize("name,unit,better,source,layer", METRICS)
def test_an_entry_of_the_benchmark_is_its_readers(name, unit, better, source,
                                                  layer):
    """The reader is a file the harness can load, it reads nothing
    without a device trace, and BENCHMARK.json declares it."""
    reader = harness.load_module(os.path.join(
        harness.ROOT, "perfbench", "layer_metrics", name + ".py"), name)
    assert reader.read({"trace": None}) is None
    check_entry(harness.load_json(os.path.join(
        harness.ROOT, "BENCHMARK.json")), name, unit, better, source, layer)


# -- the cells' own rounds, on the CPU ----------------------------------------

TINY = {"serve_base_saturated": tiny.tiny_cell,
        "serve_glm_saturated": _perfbench_glm_tiny.tiny_cell,
        "serve_jamba_saturated": _perfbench_jamba_tiny.tiny_cell,
        "serve_trinity_longctx": _perfbench_trinity_tiny.tiny_cell}


@pytest.mark.parametrize("cell", CELLS)
def test_a_cells_rehearsal_fills_the_ledger(cell, tmp_path, monkeypatch,
                                            capsys):
    """The traced rehearsal leaves the account in the program's rings;
    the ledger over them prints every line and every reading is a number (no
    device trace on the CPU, so the readers themselves give None: the
    arithmetic is called on the rings as ``window`` would)."""
    from paddle_tpu.observability import step_profiler, tracing

    make = TINY[cell]
    monkeypatch.setattr(tiny, "tiny_cell",
                        lambda name, root=tiny.ROOT: make(name, root))
    tracing.reset()
    step_profiler.reset()
    tiny.rehearse(cell, tmp_path, trace=1)
    capsys.readouterr()
    rounds = pr.program_rounds()
    win = host_ledger.window_of(
        rounds, pr.between_rounds(pr.program_dispatches(), rounds))
    assert len(pr.dispatched(rounds)) >= 4
    host_ledger.log_ledger(win)
    out = capsys.readouterr().out
    for text in ("host ledger of the window's", "step.dispatch", "handoff",
                 "the worker, of its rounds less their wait",
                 "the handlers:", "the interpreter's threads ran",
                 "executor calls between the rounds",
                 "single calls with the handlers quiet"):
        assert text in out, text
    share = host_ledger.shares(host_ledger.worker_split(win))
    assert share["cpu"] + share["device"] + share["blocked"] \
        == pytest.approx(100.0)
    assert share["cpu"] > 0 and share["device"] >= 0
    assert 0 <= share["outside"] < 100
    assert host_ledger.handler_cpu_share(rounds) > 0
    line = host_ledger.handler_line(rounds)
    assert line["cpu"] > 0 and line["chunks"] > 0 and line["worker_cpu"] > 0
    means = host_ledger.call_means(win["dispatches"])
    assert means["n"] == len(win["dispatches"]) > 0
    assert 0 < means["cpu_ms"] <= means["wall_ms"]
    # a window's reading is last less first: the handlers' account grows
    roots = [r["spans"][0] for r in rounds]
    assert [r["handler_chunks"] for r in roots] \
        == sorted(r["handler_chunks"] for r in roots)
    assert roots[-1]["handler_chunks"] > roots[0]["handler_chunks"]
