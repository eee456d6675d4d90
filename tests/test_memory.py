"""Memory-observability coverage: the live-buffer ledger balances, the
memory plan brackets the measured watermark, M001 OOM forensics name the
top holders in the black box. That every golden model reports both peaks
is a case a model beside its regression run (tests/test_golden_cpp.py)."""

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


def test_predicted_peak_no_cross_executable_fallback():
    """An explicit fingerprint with no registered plan must report None,
    not another executable's prediction."""
    memory.register_plan("fp_a", {"peak_bytes": 123, "peak_op_idx": 0,
                                  "peak_op_type": "mul", "n_ops": 1,
                                  "top_live": []})
    assert memory.predicted_peak("fp_a") == 123
    assert memory.predicted_peak("fp_unplanned") is None
    assert memory.predicted_peak() == 123  # no fingerprint: last plan


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
