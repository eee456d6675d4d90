"""The arithmetic on the program's own records (``perfbench/
program_records.py``) on hand-made rounds, dispatch records and flat
traces, and every reader that uses it: a number with a device trace, None
without one (but for ``trace_lower_s``, a plain counter), None on a program
that keeps no such record."""

import glob
import json
import os

import pytest

from _perfbench_tiny import ROOT

from perfbench import harness
from perfbench import program_records as pr


def _span(name, t0, t1, parent, cpu=None, **counts):
    return dict({"name": name, "t0": t0, "t1": t1, "parent": parent,
                 "cpu": (t1 - t0) if cpu is None else cpu}, **counts)


def _round(rid=1, t=100.0):
    """A round of 1.000 s: wait 0.100, two admissions of 0.050 with an
    encoder dispatch of 0.030 each (the second rewrites the table too:
    0.005 more), a cancel of 0.040 with a dispatch of 0.025, a step of
    0.400 with a dispatch of 0.350, hand-off 0.200. The worker's thread
    ran for half of its host time."""
    return {"id": rid, "spans": [
        _span("round", t, t + 1.0, None, cpu=0.275,
              live=3, backlog=2, tokens=12),
        _span("wait", t, t + 0.1, 0, cpu=0.0),
        _span("cancel", t + 0.1, t + 0.14, 0),
        _span("cancel.dispatch", t + 0.11, t + 0.135, 2),
        _span("admit", t + 0.15, t + 0.2, 0),
        _span("admit.dispatch", t + 0.16, t + 0.19, 4),
        _span("admit", t + 0.2, t + 0.25, 0),
        _span("admit.dispatch", t + 0.205, t + 0.235, 6),
        _span("admit.dispatch", t + 0.24, t + 0.245, 6),
        _span("step", t + 0.3, t + 0.7, 0, cpu=0.05),
        _span("step.dispatch", t + 0.32, t + 0.67, 9, cpu=0.0),
        _span("handoff", t + 0.7, t + 0.9, 0),
    ]}


def test_self_time_is_span_less_children():
    spans = _round()["spans"]
    own = pr.self_times(spans)
    assert own[2] == pytest.approx(0.040 - 0.025)
    assert own[4] == pytest.approx(0.050 - 0.030)
    assert own[6] == pytest.approx(0.050 - 0.035)
    assert own[9] == pytest.approx(0.400 - 0.350)
    assert own[5] == pytest.approx(0.030)  # a leaf
    # the round: its wall less its direct children only
    assert own[0] == pytest.approx(
        1.0 - (0.1 + 0.04 + 0.05 + 0.05 + 0.4 + 0.2))
    assert pr.child_cover(_round()) == pytest.approx(0.84)
    assert [sp["name"] for sp in pr.children(spans, 6)] == [
        "admit.dispatch", "admit.dispatch"]


def test_round_statistics():
    queued_only = {"id": 9, "spans": [_span("round", 50.0, 50.001, None),
                                      _span("enqueue", 50.0, 50.001, 0)]}
    rounds = [_round(1, 100.0), queued_only, _round(2, 101.0)]
    assert len(pr.dispatched(rounds)) == 2
    assert pr.host_seconds(rounds[0]) == pytest.approx(1.0 - 0.35 - 0.1)
    assert pr.round_ms_p50(rounds) == pytest.approx(1000.0)
    assert pr.round_host_ms_p50(rounds) == pytest.approx(550.0)
    assert pr.span_ms_p50(rounds, "admit.dispatch") == pytest.approx(30.0)
    assert pr.span_ms_p50(rounds, "admit", True) == pytest.approx(
        20.0, abs=5.1)  # 20 and 15 ms, twice each
    assert pr.span_ms_p50(rounds, "cancel") == pytest.approx(40.0)
    assert pr.per_round_ms_p50(rounds, "handoff") == pytest.approx(200.0)
    assert pr.per_round_ms_p50(rounds, "admit") == pytest.approx(100.0)
    assert pr.span_ms_p50(rounds, "no-such-span") is None
    assert pr.round_ms_p50([queued_only]) is None
    table = pr.phase_table(rounds)
    assert table["admit"][0] == 2.0 and table["admit.dispatch"][0] == 3.0
    assert table["step"][1] == pytest.approx(400.0)
    assert table["step"][2] == pytest.approx(50.0)
    assert pr.round_counts(rounds) == {
        "live": [3, 3], "backlog": [2, 2], "tokens": [12, 12],
        "admit_rows": [], "cancel_rows": []}


def _ramp_round(rid, t):
    """A round of the ramp: no admission or cancel yet, 0.5 s."""
    return {"id": rid, "spans": [
        _span("round", t, t + 0.5, None, cpu=0.1, live=1, backlog=0,
              tokens=4),
        _span("step", t + 0.05, t + 0.45, 0, cpu=0.05),
        _span("step.dispatch", t + 0.07, t + 0.42, 1, cpu=0.0)]}


def _outside_steps(rounds, opening, late=0.0):
    """The benchmark's spans around the same ``session.step()`` calls:
    seconds after the window's opening, begun 20 us before the
    program's own."""
    return [(sp["t0"] - 2e-5 + late - opening, sp["t1"] + 2e-5 - opening, 3)
            for r in rounds for sp in r["spans"] if sp["name"] == "step"]


def test_the_window_is_placed_by_the_benchmarks_own_steps():
    """The readers keep the rounds that began inside the window, the
    population of the outside ``sat_dispatch_gap_p50_ms``: the rings also
    hold ramp and drain, whose rounds are shorter."""
    ramp = [_ramp_round(1, 98.0), _ramp_round(2, 98.5), _ramp_round(3, 99.0)]
    window = [_round(4, 100.0), _round(5, 101.0), _round(6, 102.0)]
    drain = [_ramp_round(7, 103.0), _ramp_round(8, 103.5)]
    rounds = ramp + window + drain
    # the benchmark clears its list at the opening (99.9) and copies it
    # when the clients are done: the last round came after the copy
    outside = _outside_steps(window + drain[:1], 99.9)
    records = {"serve": {"host": {"step": outside}, "seconds": 3.0}}
    assert pr.window_opening(records, rounds) == pytest.approx(99.9, abs=1e-4)
    assert pr.window_rounds(records, rounds) == window
    assert pr.round_host_ms_p50(rounds) == pytest.approx(150.0)
    assert pr.round_host_ms_p50(
        pr.window_rounds(records, rounds)) == pytest.approx(550.0)
    # a list that matches nowhere (another run's): every round, and a line
    off = {"serve": {"host": {"step": _outside_steps(window, 99.9, 0.01)
                              + [(9.0, 9.4, 3)]}, "seconds": 3.0}}
    assert pr.window_opening(off, rounds) is None
    assert pr.window_rounds(off, rounds) == rounds
    assert pr.window_opening({"serve": {}}, rounds) is None
    assert pr.window_opening(records, []) is None


def test_log_rounds_prints_the_rounds_counts_beside_the_clients_rate(capfd):
    """``live``, ``backlog`` and ``tokens`` on a round have a reader: the
    report's inside view, which checks the tokens the worker handed out
    against the rate the clients counted."""
    rounds = [_round(1, 100.0), _round(2, 101.0)]
    pr.log_rounds({"serve": {"seconds": 2.0},
                   "end_to_end": {"serve_tokens_per_s": 11.5}}, rounds)
    out = "".join(capfd.readouterr())
    assert "3 slots live and 2 requests queued" in out
    assert "24 tokens handed to the streams = 12.0 tokens/s" in out
    assert "against 11.5 that the clients counted" in out
    assert "admit.dispatch" in out
    # a program that counts no rows: no line of them
    assert "admit_rows" not in out
    # an ``admit`` or a ``cancel`` span is a batch: the report says how
    # many requests and slots the batches held (a round without the key
    # admitted or released none)
    rounds[0]["spans"][0].update(admit_rows=25, cancel_rows=27)
    rounds[1]["spans"][0].update(admit_rows=23)
    pr.log_rounds({}, rounds)
    out = "".join(capfd.readouterr())
    assert ("a round's batches held, in the mean: 24.0 requests admitted "
            "(admit_rows), 13.5 slots released by cancels (cancel_rows)"
            ) in out
    # a session that counts no admissions (it counts prompts a prefill)
    del rounds[0]["spans"][0]["admit_rows"], rounds[1]["spans"][0]["admit_rows"]
    pr.log_rounds({}, rounds)
    assert "in the mean: 13.5 slots released" in "".join(capfd.readouterr())


def test_offcpu_share():
    """Host time 0.55 s a round (wall less step.dispatch less wait); the
    thread ran 0.275 s of it: half was spent off the CPU."""
    rounds = [_round(1, 100.0), _round(2, 101.0)]
    assert pr.offcpu_share(rounds) == pytest.approx(50.0)
    busy = _round()
    for sp in busy["spans"]:
        sp["cpu"] = sp["t1"] - sp["t0"]
    assert pr.offcpu_share([busy]) == pytest.approx(0.0, abs=1e-9)
    assert pr.offcpu_share([]) is None
    # the program takes cpu on round, wait, step and step.dispatch only
    sparse = _round()
    for sp in sparse["spans"]:
        if sp["name"] not in ("round", "wait", "step", "step.dispatch"):
            sp["cpu"] = None
    assert pr.offcpu_share([sparse]) == pytest.approx(50.0)
    sparse["spans"][1]["cpu"] = None
    assert pr.offcpu_share([sparse]) is None


def test_dispatch_record_arithmetic():
    recs = [{"origin": "single", "t1": 100.5, "wall_s": 0.010,
             "phases": {"feed": 0.001, "compile": 0.002, "dispatch": 0.004,
                        "fetch": 0.001, "host": 0.002}},
            {"origin": "multi_step", "t1": 100.9, "wall_s": 0.140,
             "phases": {"feed": 0.001, "dispatch": 0.002, "device": 0.130,
                        "fetch": 0.003, "host": 0.004}},
            {"origin": "single", "t1": 250.0, "wall_s": 0.010,
             "phases": {"dispatch": 0.010}}]
    assert pr.exec_host_seconds(recs[0]) == pytest.approx(0.008)
    assert pr.exec_host_seconds(recs[1]) == pytest.approx(0.010)
    assert pr.exec_host_ms_mean(recs[:2]) == pytest.approx(9.0)
    assert pr.exec_host_ms_mean([]) is None
    means = pr.phase_means_ms(recs[:2])
    assert means["dispatch"] == pytest.approx(3.0)
    assert means["device"] == pytest.approx(65.0)
    assert means["compile"] == pytest.approx(1.0)
    inside = pr.between_rounds(recs, [_round(1, 100.0), _round(2, 101.0)])
    assert inside == recs[:2]
    assert pr.between_rounds(recs, []) == []


# -- idle gaps against the program's spans ------------------------------------

MS = 1e6  # ns


def _thread():
    """One worker thread: a round with an admission (its dispatch
    inside), a step (its dispatch, the device wait inside that), and a
    second round with nothing below it."""
    return [["round", 0 * MS, 100 * MS],
            ["admit", 10 * MS, 20 * MS],
            ["admit.dispatch", 12 * MS, 10 * MS],
            ["step", 40 * MS, 50 * MS],
            ["step.dispatch", 42 * MS, 46 * MS],
            ["device", 50 * MS, 30 * MS],
            ["round", 100 * MS, 20 * MS]]


def test_nest_builds_the_forest():
    roots = pr.nest(_thread())
    assert [r[0] for r in roots] == ["round", "round"]
    first = roots[0]
    assert [c[0] for c in first[3]] == ["admit", "step"]
    assert first[3][0][3][0][0] == "admit.dispatch"
    assert first[3][1][3][0][3][0][0] == "device"
    assert roots[1][3] == []


def test_idle_gap_goes_to_the_innermost_span_that_covers_most_of_it():
    threads = [_thread()]
    # under admit > admit.dispatch: the inner one
    assert pr.idle_by_span([(13 * MS, 21 * MS)], threads) == {
        "admit.dispatch": pytest.approx(0.008)}
    # under admit but mostly outside its dispatch: admit itself
    assert pr.idle_by_span([(22 * MS, 30 * MS)], threads) == {
        "admit": pytest.approx(0.008)}
    # three levels down
    assert pr.idle_by_span([(55 * MS, 60 * MS)], threads) == {
        "device": pytest.approx(0.005)}
    # between the children: the bare round
    assert pr.idle_by_span([(31 * MS, 39 * MS)], threads) == {
        "round": pytest.approx(0.008)}
    # under nothing at all
    assert pr.idle_by_span([(130 * MS, 140 * MS)], threads) == {
        "unattributed": pytest.approx(0.010)}
    # half under a span is enough, less is not
    assert pr.idle_by_span([(115 * MS, 125 * MS)], threads) == {
        "round": pytest.approx(0.010)}
    assert pr.idle_by_span([(119 * MS, 130 * MS)], threads) == {
        "unattributed": pytest.approx(0.011)}
    assert pr.idle_by_span([(1 * MS, 2 * MS)], []) == {
        "unattributed": pytest.approx(0.001)}


def test_unattributed_share_counts_the_bare_round():
    by_span = pr.idle_by_span(
        [(13 * MS, 21 * MS), (31 * MS, 39 * MS), (55 * MS, 60 * MS),
         (130 * MS, 134 * MS)], [_thread()])
    assert sum(by_span.values()) == pytest.approx(0.025)
    assert pr.unattributed_share(by_span) == pytest.approx(
        100.0 * (0.008 + 0.004) / 0.025)
    assert pr.unattributed_share({}) is None


def test_chip0_idle_takes_gaps_as_trace_reduce_does():
    flat = {"devices": {"0": {"modules": [], "ops": [
        ["%fusion.1 = f32[8]{0} fusion(...)", 10 * MS, 5 * MS],
        ["%while.2 = (s32[]) while(...)", 10 * MS, 60 * MS],  # a wrapper
        ["%fusion.3 = f32[8]{0} fusion(...)", 40 * MS, 10 * MS]]}},
        "host": []}
    gaps, t0, t1 = pr.chip0_idle(flat)
    assert (t0, t1) == (10 * MS, 70 * MS)
    assert gaps == [(15 * MS, 40 * MS), (50 * MS, 70 * MS)]
    assert pr.chip0_idle({"devices": {"0": {"modules": [], "ops": []}},
                          "host": []})[0] == []


def test_program_annotations_are_read_from_a_real_trace(tmp_path):
    """The program's round spans, written while a profiler session is
    live, come back from the ``.xplane.pb`` nested as they were opened
    (the host plane; no device plane on the CPU)."""
    import jax

    from paddle_tpu.observability import step_profiler, tracing

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        rd = tracing.round_begin()
        rd.begin("admit")
        rd.begin(".dispatch")
        with step_profiler.device_annotation():
            pass
        rd.end()
        rd.end()
        tracing.round_end(rd, keep=False)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    threads = pr.program_threads(path)
    assert len(threads) == 1
    (root,) = pr.nest(threads[0])
    assert root[0] == "round"
    assert root[3][0][0] == "admit"
    assert root[3][0][3][0][0] == "admit.dispatch"
    assert root[3][0][3][0][3][0][0] == "device"


# -- the readers --------------------------------------------------------------

# PR 24's twelve, in the order its issue declared them in ``per_layer``
NEW_METRICS = {
    "trace_lower_s": 65.5,
    "train_exec_host_ms_per_dispatch": 8.0,
    "round_ms_p50": 1000.0,
    "round_host_ms_p50": 550.0,
    "sat_round_host_ms_p50": 550.0,
    "sat_admit_self_ms_p50": 20.0,
    "sat_admit_dispatch_ms_p50": 30.0,
    "sat_cancel_ms_p50": 40.0,
    "sat_handoff_ms_p50": 200.0,
    "sat_worker_offcpu_share": 50.0,
    "sat_exec_host_ms_per_dispatch": 9.0,
    "sat_idle_unattributed_share": 48.0,
}


@pytest.fixture
def program(monkeypatch):
    """A program whose rings hold hand-made records, and a run whose
    trace file holds a hand-made flat trace."""
    window = [_round(2, 100.0), _round(3, 101.0), _round(4, 102.0)]
    rounds = [_ramp_round(1, 99.0)] + window + [_ramp_round(5, 103.5)]
    dispatches = [
        {"origin": "single", "t1": 99.2, "wall_s": 0.5,   # of the ramp
         "phases": {"feed": 0.5}},
        {"origin": "single", "t1": 100.5, "wall_s": 0.010,
         "phases": {"feed": 0.001, "compile": 0.002, "dispatch": 0.004,
                    "fetch": 0.001, "host": 0.002}},
        {"origin": "multi_step", "t1": 100.9, "wall_s": 0.140,
         "phases": {"feed": 0.001, "dispatch": 0.002, "device": 0.130,
                    "fetch": 0.003, "host": 0.004}}]
    monkeypatch.setattr(pr, "program_rounds", lambda: rounds)
    monkeypatch.setattr(
        pr, "program_dispatches",
        lambda origin=None: [d for d in dispatches
                             if origin in (None, d["origin"])])
    monkeypatch.setattr(pr, "trace_file", lambda records: "a.xplane.pb")
    monkeypatch.setattr(pr, "program_threads", lambda path: [_thread()])
    flat = {"devices": {"0": {"modules": [], "ops": [
        ["%a = f32[8]{0} fusion(...)", 0 * MS, 13 * MS],
        ["%b = f32[8]{0} fusion(...)", 21 * MS, 10 * MS],
        ["%c = f32[8]{0} fusion(...)", 39 * MS, 16 * MS],
        ["%d = f32[8]{0} fusion(...)", 60 * MS, 70 * MS],
        ["%e = f32[8]{0} fusion(...)", 134 * MS, 1 * MS]]}}, "host": []}
    monkeypatch.setattr(pr.trace_reduce, "flatten", lambda path: flat)
    return {"cache": {"trace_seconds": 60.0, "lower_seconds": 5.5,
                      "compile_seconds": 24.8},
            "train": {"dispatch_seconds": [0.009]},
            "serve": {"host": {"step": _outside_steps(rounds[1:], 99.9)},
                      "seconds": 3.2},
            "end_to_end": {"serve_tokens_per_s": 11.0}, "cell": None}


def _reader(name):
    return harness.load_module(
        os.path.join(ROOT, "perfbench", "layer_metrics", name + ".py"),
        "reader_" + name)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_reader_gives_a_number_with_a_device_trace_and_none_without(
        name, program, capsys):
    reader = _reader(name)
    with_trace = dict(program, trace={"window_s": 3.0, "busy_s": 1.0})
    got = reader.read(with_trace)
    assert isinstance(got, float)
    assert got == pytest.approx(NEW_METRICS[name], abs=5.1)
    # the CPU rehearsals have no device trace: nothing to read, except
    # set-up's trace-and-lower seconds, a plain counter of the compile
    # cache's statistics that a rehearsal and an untraced run hold too
    want = got if name == "trace_lower_s" else None
    assert reader.read(dict(program, trace=None)) == want
    assert reader.read({k: v for k, v in program.items()}) == want
    capsys.readouterr()


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_reader_gives_none_on_a_program_without_the_record(
        name, program, monkeypatch):
    """The parent of the PR that brought these records has no round ring,
    no dispatch ring, no trace-and-lower counter and writes no ``pt:``
    annotation: every reader says None and none raises."""
    monkeypatch.setattr(pr, "program_rounds", lambda: None)
    monkeypatch.setattr(pr, "program_dispatches", lambda origin=None: None)
    monkeypatch.setattr(pr, "program_threads", lambda path: [])
    records = dict(program, trace={"window_s": 3.0, "busy_s": 1.0},
                   cache={"compile_seconds": 24.8})
    assert _reader(name).read(records) is None


def test_idle_reader_needs_a_round_among_the_annotations(
        program, monkeypatch):
    """The executor's ``pt:device`` is written in any profiler session: a
    trace that holds it and no round has no record to lay the gaps at,
    which is nothing to read and not 100% unattributed."""
    monkeypatch.setattr(pr, "program_threads",
                        lambda path: [[["device", 50 * MS, 30 * MS]]])
    records = dict(program, trace={"window_s": 3.0, "busy_s": 1.0})
    assert pr.read_idle_unattributed(records) is None


def test_the_rings_are_read_by_import():
    """Against the real program: the lookups the readers make exist."""
    from paddle_tpu.observability import step_profiler, tracing

    assert pr.program_rounds() == tracing.rounds()
    assert pr.program_dispatches("no-such-origin") == []
    assert pr.PROGRAM_PREFIX == tracing.ANNOTATION_PREFIX
    assert callable(step_profiler.dispatch_records)


# 22 entries were in ``per_layer`` before PR 24's twelve (``NEW_METRICS``,
# in its issue's order)
ENTRIES_BEFORE_PR24 = 22


def check_new_metrics_declared(bench):
    """What PR 24 declared stays declared; a later PR appends its own
    entries after these and lists its cells where a metric is every
    cell's to report."""
    names = [m["name"] for m in bench["per_layer"]]
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert set(NEW_METRICS) <= set(declared)
    # in the issue's order, after what was there; not necessarily last
    at = [names.index(name) for name in NEW_METRICS]
    assert at == sorted(at) and at[0] >= ENTRIES_BEFORE_PR24
    for name in NEW_METRICS:
        m = declared[name]
        assert m["workloads"], name   # never every cell a later PR adds
        if name.startswith("sat_"):
            # the name says which cell; another cell brings its own names
            assert m["workloads"] == ["serve_base_saturated"]
            assert m["moves"] == "serve_tokens_per_s"
    # set-up's metric is any cell's to list itself under
    assert set(declared["trace_lower_s"]["workloads"]) >= {
        "train_big_1chip", "serve_base_steady", "serve_base_saturated"}
    assert declared["sat_idle_unattributed_share"]["source"] == \
        "device_trace"


def test_new_metrics_are_declared_with_their_cells_and_layers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        check_new_metrics_declared(json.load(f))
