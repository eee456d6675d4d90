"""Training-step observatory: the observe-don't-perturb contract
(OFF = silent, ON = bit-identical + zero fresh compiles), phase
coverage, the roofline/MFU join, starvation banking, the regression
detector naming the guilty phase, the bounded ring, and the JSONL
flush."""

import json
import math
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import unique_name
from paddle_tpu.core import exec_cache
from paddle_tpu.observability import step_profiler, telemetry
from paddle_tpu.resilience import chaos


@pytest.fixture(autouse=True)
def _quiet_profiler():
    """Profiler off + empty ring around every test; the process-global
    executable registry is purged so a structurally identical program
    from an earlier test can't hide a fresh compile from this one."""
    import paddle_tpu.executor as executor_mod

    executor_mod._shared_executables.clear()
    telemetry.enable(False)
    step_profiler.enable(False)
    step_profiler.reset()
    chaos.disable()
    yield
    step_profiler.enable(False)
    step_profiler.reset()
    chaos.disable()


def _build_mlp():
    unique_name.switch({})
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 11
    startup.random_seed = 11
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [6])
        hid = fluid.layers.fc(x, size=8, act="relu")
        loss = fluid.layers.mean(fluid.layers.fc(hid, size=2))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def _feed(bs=3):
    return {"x": np.arange(bs * 6, dtype="float32").reshape(bs, 6) / 10.0}


def _leg(exe, main, startup, loss, singles=2, multi=8):
    """One schedule on a SHARED Executor with the run counter rewound:
    the step PRNG key folds the counter in, so identical counters replay
    identical init and step keys — legs compare executable for
    executable."""
    exe._run_counter = 0
    exe.run(startup)
    out = []
    for _ in range(singles):
        out.append(exe.run(main, feed=_feed(), fetch_list=[loss])[0])
    out.append(
        exe.run_multi_step(main, multi, feed=_feed(), fetch_list=[loss])[0])
    return out


# -- the overhead contract ---------------------------------------------------

def test_off_is_silent_on_is_bit_identical_with_zero_fresh_compiles():
    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    _leg(exe, main, startup, loss)  # discarded: stabilizes scope-name keys

    off = _leg(exe, main, startup, loss)
    assert step_profiler.records() == []
    assert step_profiler.inflight() == []
    compiles_off = exec_cache.stats()["fresh_compiles"]

    step_profiler.enable(True)
    step_profiler.reset()
    try:
        on = _leg(exe, main, startup, loss)
    finally:
        step_profiler.enable(False)
    # the flag is deliberately NOT in core/fingerprint.TRACE_FLAGS:
    # flipping it can never bust a cache key
    assert exec_cache.stats()["fresh_compiles"] == compiles_off
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)
    assert step_profiler.records(), "profiled leg left no step records"
    assert step_profiler.inflight() == []


# -- coverage + the roofline join --------------------------------------------

def test_multi_step_record_covers_wall_and_joins_mfu():
    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    step_profiler.enable(True)
    try:
        exe.run(startup)
        exe.run_multi_step(main, 32, feed=_feed(), fetch_list=[loss])
    finally:
        step_profiler.enable(False)
    recs = [r for r in step_profiler.records()
            if not r.get("dispatch_only") and r["steps"] == 32]
    assert len(recs) == 1
    r = recs[0]
    assert set(r["phases"]) <= set(step_profiler.PHASES)
    assert r["phases"].get("dispatch", 0.0) > 0.0
    assert r["phases"].get("device", 0.0) > 0.0
    assert r["coverage"] >= 0.95, r
    assert r["step_s"] == pytest.approx(r["wall_s"] / 32)
    assert r["feed_bytes"] == _feed()["x"].nbytes
    assert r["fetch_bytes"] > 0
    # the one-shot cost join priced this executable: per-step FLOPs and
    # achieved-FLOP/s, finite and positive. The CPU test backend is not
    # in the chip table, so it has NO MFU and NO roofline — never the
    # nameplate of some chip it is not
    assert r["flops_per_step"] > 0
    assert math.isfinite(r["achieved_flops_per_sec"])
    assert r["achieved_flops_per_sec"] > 0
    assert r["achieved_mfu"] is None
    assert r["roofline_s"] is None and r["predicted_ratio"] is None
    assert r["bound"] in ("compute", "bandwidth", "input", "host", "device")
    assert r["fingerprint"] in step_profiler.cost_table()


def test_unknown_device_has_no_mfu_until_a_peak_is_stated():
    """An unknown device yields no MFU; an explicit FLAGS_peak_tflops is
    the only way a device outside the chip table gets one."""
    from paddle_tpu import flags
    from paddle_tpu.observability import telemetry

    assert telemetry.chip_peaks() is None  # cpu is not in the table
    assert telemetry.peak_flops() is None
    assert telemetry.CHIP_PEAKS["tpu v5 lite"].bf16_flops == 197e12
    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    flags.set_flag("peak_tflops", 2.0)
    step_profiler.enable(True)
    try:
        assert telemetry.peak_flops() == 2e12
        exe.run(startup)
        exe.run_multi_step(main, 8, feed=_feed(), fetch_list=[loss])
    finally:
        step_profiler.enable(False)
        flags.set_flag("peak_tflops", 0.0)
    r = [r for r in step_profiler.records() if r["steps"] == 8][0]
    assert r["achieved_mfu"] == pytest.approx(
        r["achieved_flops_per_sec"] / 2e12)
    assert r["roofline_s"] is None  # the flag states FLOP/s, not HBM


def test_cost_join_is_one_shot_per_executable():
    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    step_profiler.enable(True)
    try:
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed=_feed(), fetch_list=[loss])
    finally:
        step_profiler.enable(False)
    table = step_profiler.cost_table()
    # recs[0] is the startup run (its own executable); the three train
    # steps share ONE fingerprint and price identically off the single
    # join
    train = [r for r in step_profiler.records()
             if not r.get("dispatch_only")][1:]
    assert len(train) == 3
    fps = {r["fingerprint"] for r in train}
    assert len(fps) == 1 and fps <= set(table)
    assert len({r["flops_per_step"] for r in train}) == 1
    assert all(r["flops_per_step"] > 0 for r in train)


# -- starvation banking ------------------------------------------------------

def test_input_wait_banked_to_the_calling_threads_next_step():
    step_profiler.enable(True)
    try:
        step_profiler.note_input_wait(0.05, site="test")
        sp = step_profiler.begin("t")
        assert sp.input_wait == pytest.approx(0.05)
        rec = step_profiler.finish(sp)
        assert rec["phases"]["input_wait"] == pytest.approx(0.05)
        assert rec["starvation_fraction"] > 0.0
        assert rec["bound"] == "input"
        # claimed exactly once: the next step starts clean
        assert step_profiler.begin("t").input_wait == 0.0
    finally:
        step_profiler.enable(False)


# -- the regression detector -------------------------------------------------

def test_detector_names_dispatch_on_injected_stall():
    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    step_profiler.enable(True)
    try:
        exe.run(startup)
        exe.run(main, feed=_feed(), fetch_list=[loss])
        fp = step_profiler.records()[-1]["fingerprint"]
        # the baseline as numbers (steps timed under six test workers
        # are not identical): enough 1 ms steps for the rolling
        # median+MAD window to open (silent below _REG_MIN samples)
        with step_profiler._lock:
            step_profiler._reg.pop(fp, None)
            for _ in range(step_profiler._REG_MIN + 2):
                assert step_profiler._detect_regression(
                    fp, 0.001, {"dispatch": 0.0006, "host": 0.0004}) is None
        # one injected 0.25s stall INSIDE the dispatch bracket, for real
        chaos.configure("slow@site=exec.dispatch,n=1,secs=0.25")
        exe.run(main, feed=_feed(), fetch_list=[loss])
        rec = [r for r in step_profiler.records()
               if r.get("regression")][-1]
    finally:
        chaos.disable()
        step_profiler.enable(False)
    v = rec["regression"]
    assert v["kind"] == "excursion"
    assert v["phase"] == "dispatch", v
    assert v["step_s"] > v["threshold_s"] > v["median_s"]
    assert v["phase_s"] > 0.2


def test_detector_rebases_after_sustained_drift():
    key = "drift-test"
    for _ in range(step_profiler._REG_MIN):
        with step_profiler._lock:
            step_profiler._detect_regression(key, 0.001, {"host": 0.001})
    kinds = []
    for _ in range(step_profiler._DRIFT_N + 1):
        with step_profiler._lock:
            v = step_profiler._detect_regression(key, 0.01, {"host": 0.01})
        kinds.append(v["kind"] if v else None)
    # excursions until the streak matures, ONE drift, then the rebased
    # baseline accepts the new regime (the +1th sample is healthy)
    assert kinds[:step_profiler._DRIFT_N - 1] == \
        ["excursion"] * (step_profiler._DRIFT_N - 1)
    assert kinds[step_profiler._DRIFT_N - 1] == "drift"
    assert kinds[step_profiler._DRIFT_N] is None


# -- the ring ----------------------------------------------------------------

def test_ring_is_bounded_and_snapshots_oldest_first():
    step_profiler.enable(True)
    try:
        for i in range(step_profiler.RING_CAP + 57):
            sp = step_profiler.begin("ring-%d" % i)
            step_profiler.finish(sp)
    finally:
        step_profiler.enable(False)
    recs = step_profiler.records()
    assert len(recs) == step_profiler.RING_CAP
    assert recs[0]["origin"] == "ring-57"
    assert recs[-1]["origin"] == "ring-%d" % (step_profiler.RING_CAP + 56)


def test_inflight_exposes_open_bracket_and_clears_on_finish():
    sp = step_profiler.begin("watchdog-target")
    sp.enter("dispatch")
    snap = step_profiler.inflight()
    assert len(snap) == 1
    assert snap[0]["origin"] == "watchdog-target"
    assert snap[0]["phase"] == "dispatch"
    step_profiler.finish(sp)
    assert step_profiler.inflight() == []


# -- the ledger round trip ---------------------------------------------------

class _SteppedClock(object):
    """``step_profiler``'s ``time``, under the test's control: a reading
    of ``perf_counter`` moves it a microsecond, ``advance`` by what a
    dispatch is said to take; every other clock is the real one's."""

    def __init__(self):
        self.now = 1000.0

    def perf_counter(self):
        self.now += 1e-6
        return self.now

    def advance(self, seconds):
        self.now += seconds

    def __getattr__(self, name):
        return getattr(time, name)


def test_jsonl_flush_writes_every_record_with_its_brackets(
        tmp_path, monkeypatch):
    """The brackets' arithmetic on a clock the test controls (a loaded
    host once read ``phase_coverage`` 0.6553 here, ROADMAP S6(d)): every
    dispatch takes 10 ms inside its ``dispatch`` bracket and a microsecond
    a clock reading everywhere else, so the phases cover the wall but for
    the handful of readings outside them, on any host."""
    from paddle_tpu import executor as executor_mod

    clock = _SteppedClock()
    dispatch = executor_mod.Executor._dispatch

    def timed_dispatch(*args, **kwargs):
        clock.advance(0.010)
        return dispatch(*args, **kwargs)

    monkeypatch.setattr(step_profiler, "time", clock)
    monkeypatch.setattr(executor_mod.Executor, "_dispatch",
                        staticmethod(timed_dispatch))
    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    step_profiler.enable(True)
    try:
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed=_feed(), fetch_list=[loss])
    finally:
        step_profiler.enable(False)
    jsonl = tmp_path / "t.stepprof.jsonl"
    n = step_profiler.write_stepprof_jsonl(str(jsonl))
    assert n == len(step_profiler.records())
    lines = [json.loads(l) for l in jsonl.read_text().splitlines()]
    assert len(lines) == n == 4  # startup + 3 train steps
    for rec in lines:
        # 10 ms in a bracket, a few readings of 1 us outside every bracket
        assert 0.999 <= rec["coverage"] <= 1.0
        assert rec["step_s"] == pytest.approx(0.010, abs=5e-5)
        assert not rec.get("regression")
        assert rec.get("achieved_mfu") is None  # cpu: not measured


# -- the always-on dispatch record -------------------------------------------

def _run_single(exe, main, loss):
    exe.run(main, feed=_feed(), fetch_list=[loss])


def _run_async(exe, main, loss):
    exe.run_async(main, feed=_feed(), fetch_list=[loss]).result()


def _run_multi(exe, main, loss):
    exe.run_multi_step(main, 4, feed=_feed(), fetch_list=[loss])


@pytest.mark.parametrize("origin,call,waits", [
    ("single", _run_single, True), ("async", _run_async, False),
    ("multi_step", _run_multi, True)])
def test_dispatch_records_with_the_flag_off(origin, call, waits):
    """Every executor dispatch leaves its small record with
    FLAGS_step_profile off, the observatory's own ring stays empty, and
    the phases (the residual ``host`` included) sum to the wall."""
    import time

    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    step_profiler.reset()
    t_before = time.time()
    for _ in range(3):
        call(exe, main, loss)
    assert step_profiler.records() == []
    assert step_profiler.inflight() == []
    recs = step_profiler.dispatch_records()
    assert [r["origin"] for r in recs] == [origin] * 3
    assert step_profiler.dispatch_records(origin) == recs
    assert step_profiler.dispatch_records("no-such-origin") == []
    for r in recs:
        assert set(r["phases"]) <= set(step_profiler.PHASES)
        assert r["phases"]["dispatch"] > 0.0
        assert ("device" in r["phases"]) == waits
        assert sum(r["phases"].values()) == pytest.approx(r["wall_s"])
        measured = sum(v for p, v in r["phases"].items() if p != "host")
        assert measured <= r["wall_s"] * (1 + 1e-9)
        # the end stamp is on time.time(), the request traces' clock
        assert t_before <= r["t1"] <= time.time()
        # request tracing is off too: the thread's CPU is not kept
        assert r["cpu"] is None
    assert [r["t1"] for r in recs] == sorted(r["t1"] for r in recs)


@pytest.mark.parametrize("origin,call", [
    ("single", _run_single), ("async", _run_async),
    ("multi_step", _run_multi)])
def test_dispatch_records_hold_the_threads_cpu_with_request_tracing_on(
        origin, call, monkeypatch):
    """With ``tracing.ENABLED`` a dispatch record says how long the
    calling thread ran in each phase of ``phases``; with it off the CPU
    clock is not read and the key holds None."""
    import time

    from paddle_tpu.observability import tracing

    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    call(exe, main, loss)
    step_profiler.reset()
    readings = []
    thread_time = time.thread_time
    monkeypatch.setattr(time, "thread_time",
                        lambda: readings.append(1) or thread_time())
    call(exe, main, loss)
    assert readings == []
    tracing.enable(True)
    try:
        call(exe, main, loss)
    finally:
        tracing.enable(False)
    off, on = step_profiler.dispatch_records(origin)
    assert off["cpu"] is None
    # a reading at every bracket and one at the end; the first bracket's
    # is the span's first (``begin`` takes none of its own)
    assert len(readings) >= len(on["phases"])
    assert set(on["cpu"]) == set(on["phases"])
    assert "host" in on["cpu"] and "dispatch" in on["cpu"]
    # the readings lie inside the wall's stamps
    assert 0.0 <= sum(on["cpu"].values()) <= on["wall_s"]
    assert all(v >= -1e-9 for v in on["cpu"].values())


@pytest.mark.parametrize("call", [_run_single, _run_multi])
def test_a_dispatch_that_raises_leaves_no_inflight_entry(call, monkeypatch):
    """Only ``finish`` pops the thread's in-flight entry, and the
    brackets run with the flag off: a dispatch that raises inside them
    must not leave the watchdog a phase that stalls for ever."""
    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    call(exe, main, loss)
    seen = []
    enter = step_profiler.StepSpan.enter

    def boom(self, phase):
        enter(self, phase)
        if phase == "dispatch":
            seen.append(step_profiler.inflight())
            raise RuntimeError("injected")

    monkeypatch.setattr(step_profiler.StepSpan, "enter", boom)
    with pytest.raises(RuntimeError, match="injected"):
        call(exe, main, loss)
    monkeypatch.undo()
    assert seen and seen[0][0]["phase"] == "dispatch"
    assert step_profiler.inflight() == []
    call(exe, main, loss)  # and the executor lives on
    assert step_profiler.inflight() == []


def test_parallel_executor_dispatch_records_with_the_flag_off():
    from paddle_tpu.parallel_executor import ParallelExecutor

    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    pe = ParallelExecutor(loss_name=loss.name, main_program=main,
                          use_tpu=False)
    step_profiler.reset()
    for _ in range(2):
        pe.run(fetch_list=[loss], feed=_feed(bs=pe.device_count))
    assert step_profiler.records() == []
    recs = step_profiler.dispatch_records("parallel")
    assert len(recs) == 2 == len(step_profiler.dispatch_records())
    for r in recs:
        assert r["phases"]["dispatch"] > 0.0 and "device" in r["phases"]
        assert sum(r["phases"].values()) == pytest.approx(r["wall_s"])


def test_dispatch_ring_is_bounded_and_the_flag_adds_the_step_record():
    for i in range(step_profiler.DISPATCH_RING_CAP + 3):
        step_profiler.finish(step_profiler.begin("ring"))
    assert len(step_profiler.dispatch_records()) == \
        step_profiler.DISPATCH_RING_CAP
    assert step_profiler.records() == []
    step_profiler.reset()
    assert step_profiler.dispatch_records() == []
    step_profiler.enable(True)
    try:
        sp = step_profiler.begin("both")
        sp.enter("dispatch")
        sp.exit()
        rec = step_profiler.finish(sp)
    finally:
        step_profiler.enable(False)
    (light,) = step_profiler.dispatch_records()
    assert light["origin"] == "both" == rec["origin"]
    assert light["wall_s"] == rec["wall_s"]
    assert light["phases"]["dispatch"] == rec["phases"]["dispatch"]
    assert step_profiler.finish(step_profiler.begin("off")) is None


def test_device_annotation_is_a_plain_trace_annotation():
    """Whoever opened the profiler session finds the device wait under
    the program's prefix; no session, no cost and no error."""
    from jax.profiler import TraceAnnotation

    ann = step_profiler.device_annotation()
    assert isinstance(ann, TraceAnnotation)
    with ann:
        pass
    assert not hasattr(step_profiler, "_NullAnnotation")
