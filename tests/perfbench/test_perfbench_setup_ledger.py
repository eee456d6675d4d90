"""The set-up ledger's readers (``perfbench/setup_ledger.py`` and the three
``layer_metrics/setup_*.py``): the arithmetic on a fabricated account, None
on a program without the table or without a device trace, and the
arithmetic (``numbers``) on two tiny CPU rehearsals (seconds of the host's
clock: never a device number, and not on the line)."""

import os

import pytest

import _perfbench_glm_tiny
import _perfbench_tiny as tiny

from perfbench import harness, run, setup_ledger

# (name, source, lists the trainer)
DECLARED = [("setup_spans_s", "program_span", False),
            ("setup_step_trace_lower_s", "program_counter", True),
            ("setup_shape_inference_s", "program_counter", True)]
READERS = [name for name, _s, _t in DECLARED]
SERVING = ["serve_base_steady", "serve_base_saturated",
           "serve_glm_saturated", "serve_jamba_saturated",
           "serve_trinity_longctx", "serve_glm52_longctx",
           "serve_solar_docreason", "serve_granite_sessions",
           "serve_longcat_agentic"]


def _reader(name):
    return harness.load_module(os.path.join(
        harness.ROOT, "perfbench", "layer_metrics", name + ".py"), name)


# -- declarations -------------------------------------------------------------

def check_declared(bench, root=harness.ROOT):
    """By NAME: what else is listed, and where, is a later PR's."""
    entries = {m["name"]: m for m in bench["per_layer"]}
    lists = entries["trace_lower_s"]["workloads"]
    for name, source, trainer in DECLARED:
        m = entries[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == ("s", "lower", source, "set-up", "setup_s")
        assert set(SERVING) <= set(m["workloads"]) <= set(lists)
        assert ("train_big_1chip" in m["workloads"]) == trainer
        assert os.path.exists(os.path.join(
            root, "perfbench", "layer_metrics", name + ".py"))


@pytest.mark.parametrize("name", READERS)
def test_an_entry_of_the_benchmark_is_its_reader(name):
    check_declared(harness.load_json(os.path.join(harness.ROOT,
                                                  "BENCHMARK.json")))
    assert callable(_reader(name).read)


def test_the_entries_come_after_every_entry_that_was_there():
    names = [m["name"] for m in harness.load_json(os.path.join(
        harness.ROOT, "BENCHMARK.json"))["per_layer"]]
    first = min(names.index(name) for name in READERS)
    assert first > names.index("longcat_prefill_pad_share")
    assert first > names.index("trace_lower_s")


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("bare", [
    {"trace": {}}, {"trace": {}, "cache": None},
    {"trace": {}, "cache": {"trace_seconds": 1.0, "lower_seconds": 2.0,
                            "compile_seconds": 3.0}},
    {"trace": None, "cache": {"by_function": {"split_step": [1, 1, 1, 1]}}}],
    ids=["no_cache", "cache_none", "the_parents_cache", "no_device_trace"])
def test_a_reader_reads_none_without_the_ledger(name, bare):
    assert _reader(name).read(dict(bare)) is None


def test_a_program_without_the_spans_reads_none(monkeypatch):
    from paddle_tpu.observability import explain

    monkeypatch.delattr(explain, "setup_spans")
    assert setup_ledger.program_account() is None
    cache = {"by_function": {"split_step": [1, 1.0, 1.0, 1.0]}}
    for name in READERS:
        assert _reader(name).read({"trace": {}, "cache": cache}) is None


# -- the arithmetic on a fabricated account -----------------------------------

def _span(index, name, path, t0, t1, parent):
    return dict(index=index, name=name, path=path, t0=t0, t1=t1,
                parent=parent)


def _event(seq, label, span, ops, ts):
    return {"event": "fresh_compile", "seq": seq, "label": label,
            "span": span, "ops": ops, "ts": ts, "changed": ["program"]}


def _account():
    """A session: a root of 10 s (and a root of 1 s beside it); ``cow/4`` (3 s, built and first run
    under it), ``step`` (2 s of IR; its executable first run later), an
    unnamed start-up program, and a prefill bucket first asked for after
    the harness's copy."""
    spans = [
        _span(0, "session.init", "session.init", 0.0, 10.0, None),
        _span(1, "cow/4", "session.init/cow/4", 1.0, 4.0, 0),
        _span(2, "step", "session.init/step", 4.0, 6.0, 0),
        _span(3, "prefill/64", "session.init/prefill/64", 6.0, 6.5, 0),
        _span(4, "elsewhere", "elsewhere", 11.0, 12.0, None)]
    events = [
        _event(0, None, None, 7, 0.5),
        _event(1, "session.init/cow/4", 1, 2200, 2.0),
        _event(2, "session.init/step", None, 900, 20.0),
        _event(3, "session.init/prefill/64", None, 1200, 90.0)]
    cache = {
        "trace_cache_misses": 3, "trace_seconds": 4.0, "lower_seconds": 5.0,
        "compile_seconds": 6.0, "trace_in_lower_seconds": 0.5,
        "by_function": {
            "split_step": [3, 1.0, 2.5, 4.0], "multi": [1, 0.25, 0.5, 1.0],
            "infer_op_shapes": [4307, 1.5, 0.0, 0.0],
            "wrapped": [6, 0.75, 0.0, 0.0],
            "reference": [1, 0.5, 2.0, 1.0]}}
    return cache, {"spans": spans, "events": events}


def test_the_three_numbers():
    cache, account = _account()
    got = setup_ledger.numbers(cache, account)
    assert got == {"setup_spans_s": 10.0 + 1.0,
                   "setup_step_trace_lower_s": 1.0 + 2.5 + 0.25 + 0.5,
                   "setup_shape_inference_s": 1.5}
    assert got["setup_step_trace_lower_s"] \
        < cache["trace_seconds"] + cache["lower_seconds"]
    # every root adds up (a second session's constructor, a builder's
    # span under no root); a child does not
    account["spans"].append(
        _span(5, "session.init", "session.init", 30.0, 32.0, None))
    account["spans"].append(_span(6, "pools", "session.init/pools",
                                  31.0, 31.5, 5))
    assert setup_ledger.numbers(cache, account)[
        "setup_spans_s"] == 13.0


def test_the_trainer_reports_the_counters_and_no_span(monkeypatch):
    cache, account = _account()
    bare = {"spans": [], "events": []}
    got = setup_ledger.numbers(cache, bare)
    assert sorted(got) == ["setup_shape_inference_s",
                           "setup_step_trace_lower_s"]
    monkeypatch.setattr(setup_ledger, "program_account", lambda: bare)
    records = {"trace": {}, "cache": cache}
    assert setup_ledger.read(records, "setup_spans_s") is None
    # a program that built no IR of its own reads 0 s of it
    del cache["by_function"]["infer_op_shapes"]
    assert setup_ledger.numbers(cache, account)[
        "setup_shape_inference_s"] == 0.0


def test_self_time_is_the_span_less_its_children():
    _cache, account = _account()
    own = setup_ledger.self_times(account["spans"])
    assert own == {0: 10.0 - 3.0 - 2.0 - 0.5, 1: 3.0, 2: 2.0, 3: 0.5,
                   4: 1.0}
    # a span still open has no length yet
    assert setup_ledger.length(_span(9, "x", "x", 5.0, None, None)) == 0.0


def test_the_ledger_is_printed_once_and_names_every_row(capsys, monkeypatch):
    cache, account = _account()
    monkeypatch.setattr(setup_ledger, "program_account", lambda: account)
    records = {"trace": {}, "cache": cache, "setup": {
        "import": 1.0, "program_build": 10.5, "reference_check": 9.0,
        "warmup_dispatches": 2.0}}
    assert _reader("setup_spans_s").read(records) == 11.0
    out = capsys.readouterr().out
    for text in ("set-up ledger",
                 "by jitted function, 5 names: trace 4.000 lower 5.000 "
                 "compile-or-load 6.000",
                 "0.500 s of the lowering enclosed tracing",
                 "split_step", "<- the executables' own steps",
                 "infer_op_shapes                4307 traces",
                 "<- the IR builder's shape inference",
                 "set-up spans 11.000 s (the harness's program_build "
                 "10.500, warmup_dispatches 2.000):",
                 "    session.init 10.000 s, 4.500 under no child span:",
                 "      cow/4               3.000  1 executable(s) "
                 "[#1 2200 ops]",
                 "      step                2.000  1 executable(s) "
                 "[#2 900 ops, first run later]",
                 "    elsewhere           1.000  0 executable(s)",
                 "executables: 3 asked for by the end of warm-up, 1 of "
                 "them under no name [#0 7 ops]",
                 "ASKED FOR AFTER THE OPENING: #3 session.init/prefill/64 "
                 "(1200 ops)",
                 "setup_spans_s 11.000, setup_step_trace_lower_s "
                 "4.250, setup_shape_inference_s 1.500"):
        assert text in out, text
    for name in READERS[1:]:
        assert _reader(name).read(records) is not None
    assert capsys.readouterr().out == ""


# -- two rehearsals, on the CPU -----------------------------------------------

TINY = {"serve_base_saturated": tiny.tiny_cell,
        "serve_glm_saturated": _perfbench_glm_tiny.tiny_cell}


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """{cell: the records the readers got, the line's metrics and the
    program's account}: each cell rehearsed once, traced."""
    from paddle_tpu.core import exec_cache
    from paddle_tpu.observability import explain

    out = {}
    mp = pytest.MonkeyPatch()
    read = harness.read_layer_metrics
    try:
        for cell, make in TINY.items():
            kept = {}

            def keep(c, records, kept=kept):
                got = read(c, records)
                kept.update(records=records, metrics=got,
                            account=setup_ledger.program_account())
                return got

            mp.setattr(tiny, "tiny_cell",
                       lambda name, root=tiny.ROOT, make=make:
                       make(name, root))
            mp.setattr(run.harness, "read_layer_metrics", keep)
            explain.reset()
            exec_cache.reset_stats()
            tiny.rehearse(cell, tmp_path_factory.mktemp(cell), trace=1)
            out[cell] = kept
    finally:
        mp.undo()
    return out


# the programs a tiny session resolves by the end of warm-up
PROGRAMS = {
    # the start-up program, the pools' init, four copy-on-write rungs,
    # release/8 and admit/8, admit/1, release/1, and the step program
    # twice (the logits tap fetches one name more than the worker)
    "serve_base_saturated": 12,
    # init, three prefill buckets, step
    "serve_glm_saturated": 5}


@pytest.mark.parametrize("cell", sorted(TINY))
def test_a_rehearsal_reads_numbers_that_fit_the_totals(cell, rehearsed,
                                                       monkeypatch):
    kept = rehearsed[cell]
    records = kept["records"]
    # no device trace on the CPU: the readers leave the line alone ...
    for name in READERS:
        assert _reader(name).read(records) is None
    assert not set(kept["metrics"]) & set(READERS)
    # ... and the arithmetic under them reads the rehearsal's set-up
    cache = records["cache"]
    got = setup_ledger.numbers(cache, kept["account"])
    assert sorted(got) == sorted(READERS)
    assert all(v > 0 for v in got.values())
    table = cache["by_function"]
    for col, total in ((1, "trace_seconds"), (2, "lower_seconds"),
                       (3, "compile_seconds")):
        assert sum(row[col] for row in table.values()) \
            == pytest.approx(cache[total])
    # the steps and the shape inference are parts of what trace_lower_s
    # reads, and the reference model and the weights are traced besides
    assert got["setup_step_trace_lower_s"] \
        + got["setup_shape_inference_s"] \
        < cache["trace_seconds"] + cache["lower_seconds"]
    assert got["setup_spans_s"] < records["setup"]["program_build"]
    assert sum(c for c, _t, _l, _b in setup_ledger.step_rows(table)) \
        >= PROGRAMS[cell]
    # with a device trace the readers give the same numbers (the account
    # as it stood after this cell's run: the fixture has run another since)
    monkeypatch.setattr(setup_ledger, "program_account",
                        lambda: kept["account"])
    traced = dict(records, trace={})
    traced.pop(setup_ledger.CACHE_KEY, None)
    for name in READERS:
        assert _reader(name).read(traced) == got[name]


@pytest.mark.parametrize("cell", sorted(TINY))
def test_a_rehearsal_counts_its_programs(cell, rehearsed):
    kept = rehearsed[cell]
    events = kept["account"]["events"]
    # the harness's copy counts the events before it: none came later
    assert kept["records"]["cache"]["trace_cache_misses"] \
        == PROGRAMS[cell] == len(events)
    assert [ev["seq"] for ev in events] == list(range(PROGRAMS[cell]))
    assert all(ev["ops"] >= 1 for ev in events)


def test_every_executable_of_a_session_has_a_label(rehearsed):
    for cell, kept in rehearsed.items():
        events = kept["account"]["events"]
        bare = [ev for ev in events if ev["label"] is None]
        # the Transformer's start-up program is the harness's to run
        transformer = cell == "serve_base_saturated"
        assert len(bare) == (1 if transformer else 0)
        labels = {ev["label"] for ev in events} - {None}
        # the decoder-only session's constructor has no root span
        prefix = "session.init/" if transformer else ""
        assert all(lb.startswith(prefix) for lb in labels)
        paths = {sp["path"] for sp in kept["account"]["spans"]}
        assert labels <= paths and prefix + "pools" in paths
        assert ("session.init" in paths) == transformer


def test_the_transformer_rehearsal_names_its_ladders(rehearsed):
    """The executables first run in the constructor carry the open span,
    those the warm-up first runs the stamp of the span they were built
    under: the step program, the prefill program and ``admit/1``."""
    events = rehearsed["serve_base_saturated"]["account"]["events"]
    labels = [ev["label"] for ev in events]
    for rung in (2, 4, 8, 16):
        assert labels.count("session.init/cow/%d" % rung) == 1
    built_in_init = [ev for ev in events if ev["span"] is not None]
    assert {ev["label"].split("/")[1] for ev in built_in_init} == {
        "init", "cow", "release", "admit"}
    later = {ev["label"] for ev in events
             if ev["span"] is None and ev["label"]}
    assert "session.init/step" in later and len(later) >= 3


def test_the_decoder_only_rehearsal_names_its_programs(rehearsed):
    events = rehearsed["serve_glm_saturated"]["account"]["events"]
    assert sorted(ev["label"] for ev in events) == sorted([
        "init", "prefill/8", "prefill/16", "prefill/32", "step"])
    by_name = {sp["name"]: sp for sp in
               rehearsed["serve_glm_saturated"]["account"]["spans"]}
    assert all(sp["parent"] is None for sp in by_name.values())
    # the init program is built under ``init`` and first run under ``pools``
    (init,) = [ev for ev in events if ev["label"] == "init"]
    assert init["span"] == by_name["pools"]["index"]
