"""Memory-observability coverage: the live-buffer ledger balances, the
memory plan brackets the measured watermark, M001 OOM forensics name the
top holders in the black box, and the perf/memory regression sentry
(tools/perf_diff.py) gates on injected regressions."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, profiler
from paddle_tpu.observability import blackbox, memory, telemetry
from paddle_tpu.resilience import chaos, retry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


@pytest.fixture(autouse=True)
def _quiet_memory():
    """Memory/forensics subsystems off and empty around every test; the
    shared executable registry is purged so per-executable one-shots
    (plan registration, kind classification) run inside the test."""
    import paddle_tpu.executor as executor_mod

    executor_mod._shared_executables.clear()
    telemetry.enable(False)
    telemetry.reset(flops=True)
    memory.reset()
    blackbox.disable()
    blackbox.reset()
    chaos.disable()
    yield
    chaos.disable()
    blackbox.disable()
    blackbox.reset()
    telemetry.enable(False)
    telemetry.reset(flops=True)
    memory.reset()
    flags.set_flag("dispatch_retries", 0)


def _mlp_program(seed=13):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [32])
        label = fluid.layers.data("label", [1], dtype="int64")
        h = fluid.layers.fc(x, size=64, act="relu")
        logits = fluid.layers.fc(h, size=4)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        # Momentum: velocity accumulators exercise the opt_state kind
        fluid.optimizer.Momentum(
            learning_rate=0.1, momentum=0.9).minimize(loss)
    return main, startup, loss


def _feed(bs=8):
    r = np.random.RandomState(7)
    return {"x": r.rand(bs, 32).astype("float32"),
            "label": r.randint(0, 4, (bs, 1)).astype("int64")}


# ---------------------------------------------------------------------------
# ledger mechanics
# ---------------------------------------------------------------------------


def test_ledger_tracks_replaces_and_balances():
    memory.track("w", 1000, "param", "cpu:0")
    memory.track("m", 500, "opt_state", "cpu:0")
    assert memory.live_bytes() == 1500
    # re-tracking the same key REPLACES (donation successor semantics)
    memory.track("w", 2000, "param", "cpu:0")
    assert memory.live_bytes() == 2500
    assert memory.live_by_kind() == {"param": 2000, "opt_state": 500}
    assert memory.take_step_peak() == 2500
    # every byte registered comes back out
    assert memory.drop("w", "param", "cpu:0")
    assert memory.drop("m", "opt_state", "cpu:0")
    assert memory.live_bytes() == 0
    # double-drop is a tolerated no-op, not a negative balance
    assert not memory.drop("w", "param", "cpu:0")
    assert memory.live_bytes() == 0


def test_top_holders_ordered():
    memory.track("big", 300, "activation", "cpu:0")
    memory.track("mid", 200, "feed", "cpu:0")
    memory.track("small", 100, "param", "cpu:0")
    top = memory.top_holders(2)
    assert [h["name"] for h in top] == ["big", "mid"]
    assert top[0] == {"name": "big", "kind": "activation",
                      "device": "cpu:0", "bytes": 300}


def test_executor_ledger_balance_after_steps():
    """After sync steps: feeds and fetched activations are fully
    released; what stays live is exactly the scope's persistable state
    (params + optimizer accumulators), byte for byte."""
    main, startup, loss = _mlp_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    telemetry.enable(True)
    for _ in range(3):
        exe.run(main, feed=_feed(), fetch_list=[loss])
    kinds = memory.live_by_kind()
    assert set(kinds) == {"param", "opt_state"}, kinds
    assert kinds["param"] > 0 and kinds["opt_state"] > 0
    # cross-check against the scope's actual arrays
    scope = fluid.global_scope()
    expected = 0
    for (_dev, _kind, name), b in list(memory._live.items()):
        val = scope.get_value(name)
        assert val is not None, name
        assert b == val.nbytes, (name, b, val.nbytes)
        expected += val.nbytes
    assert memory.live_bytes() == expected
    # per-step record carries the watermark + the plan's prediction
    rec = telemetry.step_records()[-1]
    assert rec["peak_hbm_bytes"] >= memory.live_bytes()
    assert rec["predicted_peak_bytes"] > 0
    assert rec["hbm_top"], "records must name the top holders"


def test_async_fetch_releases_on_result():
    main, startup, loss = _mlp_program(seed=14)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    telemetry.enable(True)
    handle = exe.run_async(main, feed=_feed(), fetch_list=[loss])
    assert "activation" in memory.live_by_kind()
    handle.result()
    assert "activation" not in memory.live_by_kind()


def test_checkpoint_snapshot_enters_and_leaves_ledger(tmp_path):
    from paddle_tpu.resilience.checkpoint import CheckpointManager

    main, startup, loss = _mlp_program(seed=15)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    telemetry.enable(True)
    exe.run(main, feed=_feed(), fetch_list=[loss])
    mgr = CheckpointManager(str(tmp_path), executor=exe,
                            main_program=main)
    mgr.save(step=1)
    # the host snapshot was tracked under 'cache' during the write and
    # released when it completed — the sync save returns after both
    assert "cache" not in memory.live_by_kind()


# ---------------------------------------------------------------------------
# predicted-memory planning
# ---------------------------------------------------------------------------


def test_memory_plan_shape_and_ordering():
    main, _startup, loss = _mlp_program(seed=16)
    plan = main.memory_plan(feed_shapes={"x": (8, 32), "label": (8, 1)},
                            fetch_names=[loss.name])
    assert plan.peak_bytes > 0 and np.isfinite(plan.peak_bytes)
    assert plan.n_ops == len(main.global_block().ops)
    assert 0 <= plan.peak_op_idx < plan.n_ops
    assert plan.peak_bytes == max(plan.per_op_bytes)
    assert all(b >= 0 for b in plan.per_op_bytes)
    top = plan.top(5)
    assert top and all(top[i][1] >= top[i + 1][1]
                       for i in range(len(top) - 1)), "top must be sorted"
    # params are resident the whole step: the peak can't be below them
    param_bytes = sum(
        b for _n, b in top if _n.endswith(".w_0") or _n.endswith(".b_0"))
    assert plan.peak_bytes >= param_bytes
    d = plan.as_dict()
    assert d["peak_bytes"] == plan.peak_bytes and d["top_live"]


def test_memory_plan_within_2x_of_measured():
    """Predicted (liveness-sweep) vs measured (ledger watermark) peak on
    the CPU backend: the plan adds transient activations/grads the
    ledger never sees, the ledger adds buffers XLA already freed — both
    views must still land within 2x of each other or one of them is
    lying."""
    main, startup, loss = _mlp_program(seed=17)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    telemetry.enable(True)
    for _ in range(2):
        exe.run(main, feed=_feed(), fetch_list=[loss])
    ms = profiler.memory_stats()
    assert ms["measured_peak_bytes"] and ms["predicted_peak_bytes"]
    ratio = ms["predicted_peak_bytes"] / ms["measured_peak_bytes"]
    assert 0.5 <= ratio <= 2.0, (
        "predicted/measured peak ratio %.3f outside [0.5, 2]" % ratio)
    assert ms["predicted_plan"]["peak_op_type"]
    assert ms["top_holders"]


def test_every_golden_model_reports_memory():
    """Acceptance: every golden model reports BOTH predicted and
    measured peak HBM through profiler.memory_stats() on the CPU
    backend, and the plan's curve is well-formed."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from golden_models import GOLDEN_MODELS, build_golden
    from paddle_tpu.core.scope import Scope

    for name in sorted(GOLDEN_MODELS):
        telemetry.reset(flops=True)
        memory.reset()
        with fluid.scope_guard(Scope()):
            program, _feed_names, fetch, feed, exe = build_golden(name)
            telemetry.enable(True)
            exe.run(program, feed=feed, fetch_list=[fetch.name])
            ms = profiler.memory_stats()
            telemetry.enable(False)
        assert ms["measured_peak_bytes"], "%s: no measured peak" % name
        assert ms["predicted_peak_bytes"], "%s: no predicted peak" % name
        assert np.isfinite(ms["predicted_peak_bytes"]), name
        plan = ms["predicted_plan"]
        assert plan["peak_bytes"] == ms["predicted_peak_bytes"], name
        assert plan["top_live"], "%s: plan names no live tensors" % name


# ---------------------------------------------------------------------------
# M001 OOM forensics
# ---------------------------------------------------------------------------


def test_oom_classified_never_transient():
    assert not retry.is_transient(
        RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating "
                     "123456 bytes"))
    assert not retry.is_transient(chaos.ChaosOOMError(
        "RESOURCE_EXHAUSTED: chaos: injected out-of-memory at x"))
    assert not retry.is_transient(MemoryError())
    # the transient family still retries
    assert retry.is_transient(RuntimeError("UNAVAILABLE: peer reset"))
    assert retry.is_transient(retry.TransientError("flaky"))


def test_oom_burns_no_retry_budget():
    attempts = []

    def dies_oom():
        attempts.append(1)
        raise chaos.ChaosOOMError(
            "RESOURCE_EXHAUSTED: chaos: injected out-of-memory at t")

    with pytest.raises(chaos.ChaosOOMError):
        retry.call(dies_oom, origin="test", retries=3)
    assert len(attempts) == 1, (
        "a deterministic OOM must die on the FIRST attempt, "
        "ran %d" % len(attempts))


def test_chaos_skip_param_defers_deterministically():
    chaos.configure("oom@site=exec.dispatch,skip=2,n=1")
    chaos.fault("exec.dispatch")  # visit 1: skipped
    chaos.fault("exec.dispatch")  # visit 2: skipped
    with pytest.raises(chaos.ChaosOOMError):
        chaos.fault("exec.dispatch")  # visit 3: fires
    assert chaos.fires("exec.dispatch") == 1
    chaos.fault("exec.dispatch")  # budget n=1 exhausted: quiet


def test_m001_blackbox_dump_names_top_holders(tmp_path):
    """An induced OOM at dispatch produces a black-box dump whose M001
    diagnostic names the top-3 live-buffer holders and the predicted
    peak, and tools/blackbox_dump.py surfaces it with exit code 4."""
    import blackbox_dump

    box = str(tmp_path / "box.json")
    main, startup, loss = _mlp_program(seed=18)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    telemetry.enable(True)
    exe.run(main, feed=_feed(), fetch_list=[loss])  # populate the ledger
    blackbox.enable(box, handlers=False)
    chaos.configure("oom@site=exec.dispatch,n=1")
    with pytest.raises(memory.MemoryExhaustedError) as ei:
        exe.run(main, feed=_feed(), fetch_list=[loss])
    chaos.disable()
    diag = ei.value.diagnostic
    assert diag.rule == "M001" and diag.severity == "error"
    assert "RESOURCE_EXHAUSTED" in str(ei.value)
    with open(box) as f:
        snap = json.load(f)
    d = snap["oom_diagnostic"]
    assert d["rule"] == "M001"
    holders = d["top_holders"]
    assert len(holders) == 3, holders
    assert holders[0]["bytes"] >= holders[1]["bytes"] >= \
        holders[2]["bytes"]
    assert d["predicted_peak_bytes"] > 0
    assert any(e["kind"] == "oom_diagnostic" for e in snap["events"])
    rc = blackbox_dump.main([box])
    assert rc == 4, "blackbox_dump must exit 4 on an M001 dump"


def test_oom_not_enriched_when_not_oom():
    """An ordinary dispatch failure must NOT be rebranded M001."""
    main, startup, loss = _mlp_program(seed=19)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    chaos.configure("compile@site=exec.dispatch,n=1")
    with pytest.raises(chaos.ChaosTransientError):
        exe.run(main, feed=_feed(), fetch_list=[loss])


# ---------------------------------------------------------------------------
# perf/memory regression sentry
# ---------------------------------------------------------------------------


def _bench_artifact(path, fresh_compiles=4, p50=50.0, peak=1000000,
                    predicted=2000000, value=10.0):
    rec = {"models": {"resnet50": {
        "value": value, "unit": "images/sec",
        "step_ms": {"p50": p50, "p95": p50 * 4},
        "compile_seconds_cold": 10.0,
        "exec_cache": {"fresh_compiles": fresh_compiles},
        "peak_hbm_bytes": peak, "predicted_peak_bytes": predicted,
    }}}
    with open(path, "w") as f:
        f.write(json.dumps(rec) + "\n")
    return str(path)


def test_perf_diff_clean_and_fresh_compile_regression(tmp_path):
    import perf_diff

    base = _bench_artifact(tmp_path / "base.json")
    same = _bench_artifact(tmp_path / "same.json")
    # identical artifacts: clean (returns, no SystemExit)
    perf_diff.main([same, "--baseline", base])
    # +30% fresh compiles: deterministic counter, must gate HARD even
    # though it sits inside any noise band
    worse = _bench_artifact(tmp_path / "worse.json",
                            fresh_compiles=int(4 * 1.3) + 1)
    with pytest.raises(SystemExit) as ei:
        perf_diff.main([worse, "--baseline", base])
    assert ei.value.code == 1


def test_perf_diff_timing_noise_band(tmp_path):
    import perf_diff

    base = _bench_artifact(tmp_path / "base.json")
    # +20% p50 sits inside the default 25% band: noise, not regression
    noisy = _bench_artifact(tmp_path / "noisy.json", p50=60.0)
    perf_diff.main([noisy, "--baseline", base])
    # +60% p50 is a regression
    slow = _bench_artifact(tmp_path / "slow.json", p50=80.0)
    with pytest.raises(SystemExit) as ei:
        perf_diff.main([slow, "--baseline", base])
    assert ei.value.code == 1
    # a higher predicted peak is deterministic: gates hard at any size
    fat = _bench_artifact(tmp_path / "fat.json", predicted=2000001)
    with pytest.raises(SystemExit) as ei:
        perf_diff.main([fat, "--baseline", base])
    assert ei.value.code == 1


def test_perf_diff_budget_mode(tmp_path):
    import perf_diff

    cand = _bench_artifact(tmp_path / "cand.json")
    budgets = tmp_path / "budgets.json"
    budgets.write_text(json.dumps({
        "band": 0.5,
        "models": {"resnet50": {
            "fresh_compiles": {"max": 4, "why": "seed"},
            "predicted_peak_bytes": {"max": 2000000, "why": "seed"},
            "step_ms_p50": {"max": 50.0, "why": "seed"},
            "throughput": {"min": 10.0, "why": "seed"},
        }}}))
    perf_diff.main([cand, "--budgets", str(budgets)])
    over = _bench_artifact(tmp_path / "over.json", fresh_compiles=5)
    with pytest.raises(SystemExit) as ei:
        perf_diff.main([over, "--budgets", str(budgets)])
    assert ei.value.code == 1


def test_perf_diff_budget_mode_fails_on_missing_metric(tmp_path):
    """A budgeted metric absent from the candidate is a FAILURE, not a
    silent skip — a PR that breaks the telemetry capture must not turn
    the gate green by shrinking what it checks."""
    import perf_diff

    budgets = tmp_path / "budgets.json"
    budgets.write_text(json.dumps({
        "band": 0.5,
        "models": {"resnet50": {
            "fresh_compiles": {"max": 4, "why": "seed"},
            "throughput": {"min": 10.0, "why": "seed"},
        }}}))
    # a capture that lost its exec-cache counters: throughput survives,
    # fresh_compiles is gone
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps(
        {"models": {"resnet50": {"value": 10.0}}}) + "\n")
    with pytest.raises(SystemExit) as ei:
        perf_diff.main([str(bare), "--budgets", str(budgets)])
    assert ei.value.code == 1


def test_predicted_peak_no_cross_executable_fallback():
    """An explicit fingerprint with no registered plan must report None,
    not another executable's prediction."""
    memory.register_plan("fp_a", {"peak_bytes": 123, "peak_op_idx": 0,
                                  "peak_op_type": "mul", "n_ops": 1,
                                  "top_live": []})
    assert memory.predicted_peak("fp_a") == 123
    assert memory.predicted_peak("fp_unplanned") is None
    assert memory.predicted_peak() == 123  # no fingerprint: last plan


def test_perf_diff_unreadable_exits_2(tmp_path):
    import perf_diff

    bad = tmp_path / "bad.json"
    bad.write_text("not json at all {{{")
    with pytest.raises(SystemExit) as ei:
        perf_diff.main([str(bad), "--baseline", str(bad)])
    assert ei.value.code == 2


def test_committed_budgets_parse_and_cover_the_gate():
    """The checked-in budgets file must parse, carry lineage for every
    number, and budget the deterministic counters the gate exists for."""
    with open(os.path.join(REPO, "benchmark", "budgets.json")) as f:
        budgets = json.load(f)
    assert budgets["models"], "budgets must cover at least one model"
    for model, entries in budgets["models"].items():
        assert "fresh_compiles" in entries, model
        if model not in ("servechaos", "router", "trace", "stepprof"):
            # every bench-leg model budgets its memory plan; the
            # servechaos/router/trace/stepprof smoke captures have no
            # memory_plan surface — their deterministic gate is
            # fresh_compiles == 0 (in the RESTORED process / on the
            # failover survivor / across the tracing-ON wire leg /
            # across the profiled replay)
            assert "predicted_peak_bytes" in entries, model
        for metric, spec in entries.items():
            assert spec.get("why"), (
                "budget %s/%s needs a lineage 'why'" % (model, metric))
            assert "max" in spec or "min" in spec, (model, metric)


# ---------------------------------------------------------------------------
# offline tooling
# ---------------------------------------------------------------------------


def test_step_breakdown_memory_view(tmp_path):
    main, startup, loss = _mlp_program(seed=20)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    telemetry.enable(True)
    for _ in range(3):
        exe.run(main, feed=_feed(), fetch_list=[loss])
    snap = str(tmp_path / "steps.jsonl")
    telemetry.write_steps_jsonl(snap)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "step_breakdown.py"),
         "--from-jsonl", snap, "--memory"],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode == 0, proc.stderr
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.strip()]
    mem = next(l for l in lines if "peak_hbm_mb" in l)
    assert mem["peak_hbm_mb"]["max"] > 0
    assert mem["predicted_peak_mb"] > 0
    assert mem["top_holders"], "memory view must name the top holders"
