"""Step-observatory smoke: prove the profiler is FREE when off and
ACCOUNTABLE when on, over a real training loop.

One process, two legs over the SAME seeded MLP training job (fresh
Executor per leg, so run counters and step keys line up exactly):

* **Leg A (control, FLAGS_step_profile unset)** runs single steps plus
  repeated ``run_multi_step`` dispatches, banks every fetch and the
  per-rep walls, and asserts the profiler stayed silent: no records, no
  in-flight phases.

* **Leg B (profiled)** replays the identical schedule with the
  observatory on and asserts the observe-don't-perturb contract:

    - every fetch bit-identical to the control leg;
    - **0 fresh compiles** — the profiled leg pays the exact compile
      bill the control leg already paid: none;
    - every timed step record attributes >= 95% of its wall to named
      phases (feed/compile/dispatch/device/fetch/host);
    - achieved FLOP/s joined from the cost model is finite on every
      record (MFU is None: this CPU backend has no published peak),
      and the bound classification is from the closed vocabulary;
    - the wall-clock overhead ratio (profiled / unprofiled over
      INTERLEAVED off/on multi-step pairs on the warm executable, so
      machine drift between measurements cancels) lands in the capture
      for the budget gate.

The profiled leg's ring then round-trips the offline toolchain:
``write_stepprof_jsonl`` -> ``tools/step_breakdown.py --steps`` ->
``tools/perf_ledger.py append/show/diff`` (two entries, relative gate
clean).

The capture (``$D/stepprof.json``: phase_coverage, fresh_compiles,
achieved_flops_per_sec, starvation_fraction, stepprof_overhead) gates via
``tools/perf_diff.py --budgets benchmark/budgets.json --models
stepprof``.
"""

import json
import math
import os
import subprocess
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

STEPS = 1024        # per run_multi_step dispatch: the profiler's cost is
REPS = 4            # fixed per DISPATCH (~100µs of brackets + record
SINGLES = 3         # assembly), so a real scan length amortizes it to
                    # well under the 2% budget per step
COVERAGE_FLOOR = 0.95
BOUNDS = ("compute", "bandwidth", "input", "host", "device")


def _build_mlp():
    import paddle_tpu as fluid
    from paddle_tpu import unique_name

    unique_name.switch({})
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 11
    startup.random_seed = 11
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [16])
        hid = fluid.layers.fc(x, size=32, act="relu")
        loss = fluid.layers.mean(fluid.layers.fc(hid, size=4))
        # small lr: ~1800 SGD steps on an unbounded toy loss must stay
        # finite, or leg parity would compare NaN against NaN
        fluid.optimizer.SGD(learning_rate=0.001).minimize(loss)
    return main, startup, loss


def _feed():
    return {"x": (np.arange(4 * 16, dtype="float32")
                  .reshape(4, 16) / 100.0)}


def _leg(exe, main, startup, loss):
    """One full schedule; -> fetches. Legs share ONE Executor
    (``run_multi_step`` executables live in the per-instance cache, so a
    fresh Executor would re-trace) and each leg rewinds the run counter:
    the step PRNG key folds it in, so identical counters mean identical
    startup init and step keys — the legs replay the exact same
    computation, executable for executable."""
    exe._run_counter = 0
    feed = _feed()
    exe.run(startup)
    fetches = []
    for _ in range(SINGLES):
        fetches.append(exe.run(main, feed=feed, fetch_list=[loss])[0])
    for _ in range(1 + REPS):
        fetches.append(
            exe.run_multi_step(main, STEPS, feed=feed,
                               fetch_list=[loss])[0])
    return fetches


def _time_overhead(exe, main, loss):
    """Profiled/unprofiled wall ratio over ADJACENT off/on multi-step
    pairs on the warm executable. Interleaving is the drift killer: the
    process speeds up over its first seconds (allocator warmup, branch
    caches), so a leg-vs-leg ratio inherits whatever the machine was
    doing minutes apart — pairing each profiled rep with an unprofiled
    neighbor cancels it. Min-of-reps on each side then drops scheduler
    jitter, which only ever ADDS time."""
    feed = _feed()
    walls_off, walls_on = [], []
    from paddle_tpu.observability import step_profiler
    try:
        for _ in range(REPS):
            for armed, walls in ((False, walls_off), (True, walls_on)):
                step_profiler.enable(armed)
                t0 = time.perf_counter()
                exe.run_multi_step(main, STEPS, feed=feed,
                                   fetch_list=[loss])
                walls.append(time.perf_counter() - t0)
    finally:
        step_profiler.enable(False)
    return min(walls_on) / max(min(walls_off), 1e-9)


def _median(vals):
    s = sorted(vals)
    return s[len(s) // 2]


def _assert_tools_round_trip(workdir, jsonl, n_timed):
    """step_breakdown reads the flushed ring; perf_ledger appends two
    trajectory points and gates the newest against the previous."""
    tools = os.path.dirname(os.path.abspath(__file__))
    brk = subprocess.run(
        [sys.executable, os.path.join(tools, "step_breakdown.py"),
         "--steps", jsonl, "--top", "2"],
        capture_output=True, text=True)
    assert brk.returncode == 0, (
        "step_breakdown --steps failed: %s" % brk.stderr)
    fleet = json.loads(brk.stdout.splitlines()[0])
    assert fleet["step_records"] >= n_timed, fleet
    assert fleet["coverage_min"] >= COVERAGE_FLOOR, fleet
    ledger = os.path.join(workdir, "ledger.jsonl")
    for label in ("smoke-a", "smoke-b"):
        app = subprocess.run(
            [sys.executable, os.path.join(tools, "perf_ledger.py"),
             "append", "--ledger", ledger, "--stepprof", jsonl,
             "--label", label],
            capture_output=True, text=True)
        assert app.returncode == 0, (
            "perf_ledger append failed: %s" % app.stderr)
    assert json.loads(app.stdout)["entries"] == 2, app.stdout
    show = subprocess.run(
        [sys.executable, os.path.join(tools, "perf_ledger.py"),
         "show", "--ledger", ledger, "--model", "stepprof"],
        capture_output=True, text=True)
    assert show.returncode == 0 and "phase_coverage" in show.stdout, (
        "perf_ledger show lost the trajectory: %s" % show.stdout)
    diff = subprocess.run(
        [sys.executable, os.path.join(tools, "perf_ledger.py"),
         "diff", "--ledger", ledger],
        capture_output=True, text=True)
    assert diff.returncode == 0, (
        "identical trajectory points must gate clean:\n%s%s"
        % (diff.stdout, diff.stderr))


def main():
    workdir = sys.argv[1] if len(sys.argv) > 1 else None
    if not workdir:
        print("usage: stepprof_smoke.py <workdir>", file=sys.stderr)
        return 2
    import paddle_tpu as fluid
    from paddle_tpu.core import exec_cache
    from paddle_tpu.observability import step_profiler

    # -- leg 0: discarded warmup --------------------------------------------
    # The first schedule's own runs create scope vars, and scope names
    # are part of the trace-cache key — so the SECOND schedule over the
    # shared global scope retraces once for startup and once for the
    # multi-step executable no matter what. One throwaway schedule
    # stabilizes the keys; legs A and B then share every executable.
    assert not step_profiler.ENABLED, \
        "control leg started with FLAGS_step_profile set"
    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    _leg(exe, main, startup, loss)

    # -- leg A: control, profiler off ---------------------------------------
    fetches_off = _leg(exe, main, startup, loss)
    assert not step_profiler.records() and not step_profiler.inflight(), \
        "profiler-off leg accumulated step records"
    compiles_off = exec_cache.stats()["fresh_compiles"]

    # -- leg B: profiled, same schedule -------------------------------------
    step_profiler.enable(True)
    step_profiler.reset()
    try:
        fetches_on = _leg(exe, main, startup, loss)
    finally:
        step_profiler.enable(False)
    fresh = exec_cache.stats()["fresh_compiles"] - compiles_off
    assert fresh == 0, (
        "profiled leg paid %d fresh compile(s) the control leg didn't"
        % fresh)
    assert len(fetches_on) == len(fetches_off)
    for i, (a, b) in enumerate(zip(fetches_off, fetches_on)):
        assert np.array_equal(a, b), (
            "fetch %d diverged between the control and profiled legs"
            % i)

    # -- the records: coverage, MFU join, classification --------------------
    recs = [r for r in step_profiler.records()
            if not r.get("dispatch_only")]
    # the startup run is profiled too: 1 + singles + warmup multi + reps
    assert len(recs) == 1 + SINGLES + 1 + REPS, (
        "expected %d step records, ring holds %d"
        % (1 + SINGLES + 1 + REPS, len(recs)))
    cov = min(r["coverage"] for r in recs)
    assert cov >= COVERAGE_FLOOR, (
        "worst step attributes only %.4f of its wall to phases: %r"
        % (cov, min(recs, key=lambda r: r["coverage"])))
    train = recs[1:]  # recs[0] is the startup run: init, ~0 FLOPs
    for r in train:
        assert math.isfinite(r["achieved_flops_per_sec"]) and \
            r["achieved_flops_per_sec"] > 0, (
                "cost join produced no finite achieved FLOP/s: %r" % r)
        # this CPU backend is not in the chip table: no MFU, not even
        # a small one against some chip's nameplate
        assert r["achieved_mfu"] is None, r
    for r in recs:
        assert r["bound"] in BOUNDS, r
        assert r["starvation_fraction"] == 0.0, (
            "feed-dict job reported input starvation: %r" % r)
    assert not step_profiler.inflight(), \
        "in-flight phases leaked after the profiled leg finished"

    # -- offline round trip --------------------------------------------------
    jsonl = os.path.join(workdir, "m.stepprof.jsonl")
    n = step_profiler.write_stepprof_jsonl(jsonl)
    assert n >= len(recs), (
        "ring flushed %d records, expected >= %d" % (n, len(recs)))
    _assert_tools_round_trip(workdir, jsonl, len(recs))

    # -- overhead: interleaved off/on pairs on the warm executable ----------
    overhead = _time_overhead(exe, main, loss)
    flops_p50 = _median(sorted(r["achieved_flops_per_sec"]
                               for r in train))
    rec = {
        "metric": "stepprof_phase_coverage",
        "value": round(cov, 4),
        "unit": "fraction of step wall attributed to phases",
        "vs_baseline": None,
        "phase_coverage": round(cov, 4),
        "fresh_compiles": fresh,
        "achieved_flops_per_sec": round(flops_p50, 1),
        "starvation_fraction": 0.0,
        "stepprof_overhead": round(overhead, 4),
        "step_records": len(recs),
        "steps": STEPS * (REPS + 1) + SINGLES,
        "platform": "cpu",
    }
    print("stepprof_smoke: %s" % json.dumps(rec))
    with open(os.path.join(workdir, "stepprof.json"), "w") as f:
        json.dump({"models": {"stepprof": rec}}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
