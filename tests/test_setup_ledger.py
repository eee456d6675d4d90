"""The set-up ledger (docs/OBSERVABILITY.md): who asked for an executable
(``seq``, ``label``, ``span``, ``ops`` on the ``fresh_compile`` events of
``explain.events()``), the set-up spans, and ``exec_cache.stats()``'s
``by_function`` table. CPU only; no case asserts a wall-clock ratio."""

import itertools
import json
import logging
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import exec_cache
from paddle_tpu.observability import explain


_WIDTHS = itertools.count(37)
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def _mlp(name):
    """A program nothing else in this process builds (the width is part
    of its fingerprint, and executables are shared by fingerprint)."""
    width = next(_WIDTHS)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x_" + name, shape=[5], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, width))
    return main, startup, loss, {"x_" + name: np.ones((3, 5), "float32")}


@pytest.fixture
def ledger():
    explain.reset()
    exec_cache.reset_stats()
    yield
    explain.reset()
    exec_cache.reset_stats()


def _run_two(exe, scope):
    """Two programs, each built and first run under a named span."""
    made = []
    with explain.setup_span("session.init"):
        for name in ("a", "b"):
            with explain.setup_span("fam/" + name):
                main, startup, loss, feed = _mlp(name)
                exe.run(startup, scope=scope)
                exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
            made.append((main, loss, feed))
    return made


def test_two_programs_under_spans_leave_labelled_events(ledger):
    """The executors ask for an executable under whatever span is open:
    the event carries the span's path and index, and the program's
    operator count."""
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    made = _run_two(exe, scope)
    events = explain.events()
    assert [e["seq"] for e in events] == list(range(len(events)))
    assert [e["label"] for e in events] == [
        "session.init/fam/a"] * 2 + ["session.init/fam/b"] * 2
    spans = {sp["index"]: sp for sp in explain.setup_spans()}
    for rec in events:
        assert rec["event"] == "fresh_compile" and rec["changed"]
        span = spans[rec["span"]]
        assert span["path"] == rec["label"]
        assert span["t0"] <= rec["ts"] <= span["t1"]
        root = spans[span["parent"]]
        assert root["name"] == "session.init" and root["parent"] is None
        assert root["t0"] <= span["t0"] < span["t1"] <= root["t1"]
    main = made[0][0]
    assert events[1]["ops"] == sum(len(b.ops) for b in main.blocks) >= 3


def test_a_program_built_under_a_span_keeps_its_name_later(ledger):
    """A program stamped under a span carries the name to an executable
    the warm-up first asks for, when no span is open any more."""
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with explain.setup_span("session.init"):
        with explain.setup_span("step") as _span:
            main, startup, loss, feed = _mlp("d")
            assert explain.name_program(main) is main
        with explain.setup_span("prefill/8", startup):
            pass
    exe.run(startup, scope=scope)
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    first, second = explain.events()
    assert (first["label"], first["span"]) == ("session.init/prefill/8",
                                               None)
    assert (second["label"], second["span"]) == ("session.init/step", None)
    # and a program nobody named, asked for under no span, has none
    other, startup, loss, feed = _mlp("plain")
    exe.run(startup, scope=scope)
    assert explain.events()[-1]["label"] is None


def test_spans_nest_by_thread(ledger):
    """The innermost open span is the calling thread's."""
    seen = {}

    def work():
        with explain.setup_span("elsewhere"):
            seen["label"] = explain.record_compile(
                {"program": "q"})["label"]

    with explain.setup_span("session.init"):
        t = threading.Thread(target=work)
        t.start()
        t.join()
        here = explain.record_compile({"program": "r"})["label"]
    assert seen["label"] == "elsewhere" and here == "session.init"
    by_name = {sp["name"]: sp for sp in explain.setup_spans()}
    assert by_name["elsewhere"]["parent"] is None


def test_a_spanned_constructor_opens_the_root(ledger):
    class Session(object):
        @explain.spanned("session.init")
        def __init__(self, rungs):
            """Builds."""
            for rung in rungs:
                with explain.setup_span("cow/%d" % rung):
                    pass

    assert Session.__init__.__doc__ == "Builds."
    Session((1, 4))
    spans = explain.setup_spans()
    assert [sp["path"] for sp in spans] == [
        "session.init", "session.init/cow/1", "session.init/cow/4"]
    assert [sp["parent"] for sp in spans] == [None, 0, 0]
    assert all(sp["t1"] >= sp["t0"] for sp in spans)


def test_a_jit_called_alone_has_a_row_by_its_name(ledger):
    def lonely_helper(v):
        return jnp.tanh(v) * 2

    jax.jit(lonely_helper)(jnp.ones((3, 11)))
    calls, trace_s, lower_s, backend_s = exec_cache.stats()[
        "by_function"]["lonely_helper"]
    assert calls == 1 and trace_s > 0 and lower_s > 0 and backend_s > 0


def test_by_function_adds_up_to_the_totals(ledger):
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    _run_two(exe, scope)
    jax.jit(lambda v: v * 3 + 1)(jnp.arange(7.0))
    st = exec_cache.stats()
    table = st["by_function"]
    for col, total in ((1, "trace_seconds"), (2, "lower_seconds"),
                       (3, "compile_seconds")):
        assert sum(row[col] for row in table.values()) \
            == pytest.approx(st[total])
        assert st[total] > 0
    # the programs' own steps, and the IR builder's shape inference an op
    assert table["split_step"][0] >= 4 and table["split_step"][2] > 0
    assert table["infer_op_shapes"][0] >= 6
    assert table["infer_op_shapes"][2] == 0.0     # traced, never lowered


def test_a_snapshot_keeps_the_table_as_it_was(ledger):
    """The benchmark cuts at a SHALLOW copy of ``stats()`` taken at the
    end of warm-up: what is traced later must not reach it."""
    jax.jit(lambda v: v - 4)(jnp.arange(5.0))
    kept = dict(exec_cache.stats())
    before = {k: list(v) for k, v in kept["by_function"].items()}

    def later(v):
        return v * v

    jax.jit(later)(jnp.arange(6.0))
    jax.jit(lambda v: v - 4)(jnp.arange(15.0))
    assert kept["by_function"] == before and "later" not in before
    assert "later" in exec_cache.stats()["by_function"]
    exec_cache.reset_stats()
    assert exec_cache.stats()["by_function"] == {}


def test_a_helper_jitted_inside_is_counted_once(ledger):
    """The union: an inner jit's trace report lies inside the outer one's.
    ``trace_seconds`` counts the outer report once, each function's row
    its own part; one module is lowered a top-level jit, so no lowering
    report lies inside another and ``lower_seconds`` needs no union."""
    raw = {"trace": [], "lower": []}

    def tap(name, secs, fun_name=None, **kw):
        if name == _TRACE:
            raw["trace"].append((fun_name, secs))
        elif name == _LOWER:
            raw["lower"].append((fun_name, secs))

    jax.monitoring.register_event_duration_secs_listener(tap)
    try:
        @jax.jit
        def inner_helper(v):
            return jnp.sin(v) + 1

        def outer_step(v):
            return inner_helper(v) * inner_helper(v + 1)

        arg = jnp.ones((3, 13))
        exec_cache.reset_stats()
        del raw["trace"][:], raw["lower"][:]
        jax.jit(outer_step)(arg)
    finally:
        jax.monitoring.unregister_event_duration_listener(tap)
    reports = dict(raw["trace"])
    assert sum(s for _n, s in raw["trace"]) > reports["outer_step"]
    st = exec_cache.stats()
    # every trace report of the call lies inside outer_step's
    assert st["trace_seconds"] == pytest.approx(reports["outer_step"])
    table = st["by_function"]
    assert table["inner_helper"][0] >= 1
    assert 0 < table["outer_step"][1] < reports["outer_step"]
    assert sum(row[1] for row in table.values()) \
        == pytest.approx(reports["outer_step"])
    (module,) = [n for n, _s in raw["lower"]]   # one module, nothing nests
    assert "outer_step" in module
    assert st["lower_seconds"] == pytest.approx(raw["lower"][0][1])
    assert st["trace_in_lower_seconds"] == 0.0


def test_tracing_inside_a_lowering_stays_in_both_totals_and_is_said(ledger):
    """A lowering rule may trace (the first session took 2-3 s of a Jamba
    prefill program's lowering for Mosaic tracing the kernels' bodies
    again; the chip reads 0.05 s): ``lower_seconds`` stays the sum of the
    lowering reports, as every ledger line has read it, and
    ``trace_in_lower_seconds`` is the part ``trace_seconds`` has counted
    too."""
    exec_cache._tls.traces = []      # no earlier report of this thread
    # two traces that ended just now, the second enclosing the first,
    # then a lowering that says it has run for a second: it began before
    # either
    exec_cache._on_duration(_TRACE, 0.125, fun_name="inner")
    exec_cache._on_duration(_TRACE, 0.25, fun_name="wrapped")
    exec_cache._on_duration(_LOWER, 1.0, fun_name="jit_step")
    # a lowering that began after those traces encloses none of them
    exec_cache._on_duration(_LOWER, 1e-7, fun_name="jit_step")
    st = exec_cache.stats()
    assert st["trace_seconds"] == 0.25
    assert st["lower_seconds"] == pytest.approx(1.0 + 1e-7)
    assert st["trace_in_lower_seconds"] == 0.25
    assert st["by_function"]["wrapped"] == [1, 0.125, 0.0, 0.0]
    assert st["by_function"]["step"][2] == pytest.approx(1.0 + 1e-7)


def test_the_rings_are_bounded(ledger):
    for i in range(explain._MAX_EVENTS + 40):
        explain.record_compile({"program": "p%d" % i})
    events = explain.events()
    assert len(events) == explain._MAX_EVENTS
    assert events[-1]["seq"] == explain._MAX_EVENTS + 39
    for i in range(explain._MAX_SPANS + 10):
        with explain.setup_span("s%d" % i):
            pass
    spans = explain.setup_spans()
    assert len(spans) <= explain._MAX_SPANS
    assert spans[-1]["index"] == explain._MAX_SPANS + 9
    for i in range(exec_cache._FUNCTIONS_CAP + 30):
        exec_cache._on_duration(_LOWER, 1e-6, fun_name="jit_f%d" % i)
    table = exec_cache.stats()["by_function"]
    assert len(table) == exec_cache._FUNCTIONS_CAP + 1
    assert table["<other>"][2] == pytest.approx(30e-6)


def test_the_log_line_names_the_span(ledger, caplog):
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    main, startup, loss, feed = _mlp("e")
    exe.run(startup, scope=scope)
    with caplog.at_level(logging.INFO,
                         logger="paddle_tpu.observability.explain"):
        caplog.clear()
        with explain.setup_span("the_step"):
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    (line,) = [r.getMessage() for r in caplog.records
               if r.getMessage().startswith("recompile: ")]
    said = json.loads(line[len("recompile: "):])
    assert said["label"] == "the_step" and said["seq"] == 1
    assert said["ops"] >= 3


class _CountingLock(object):
    def __init__(self, lock):
        self.lock, self.taken = lock, 0

    def __enter__(self):
        self.taken += 1
        return self.lock.__enter__()

    def __exit__(self, *exc):
        return self.lock.__exit__(*exc)


def test_a_warm_dispatch_adds_no_event_and_takes_no_new_lock(
        ledger, monkeypatch):
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    # the second program's: the scope holds ITS parameters under the
    # names the two share
    _a, (main, loss, feed) = _run_two(exe, scope)
    records, spans = len(explain.events()), len(explain.setup_spans())
    table = exec_cache.stats()["by_function"]
    watched = _CountingLock(explain._lock)
    monkeypatch.setattr(explain, "_lock", watched)
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert watched.taken == 0
    monkeypatch.undo()
    assert len(explain.events()) == records
    assert len(explain.setup_spans()) == spans
    assert exec_cache.stats()["by_function"] == table
