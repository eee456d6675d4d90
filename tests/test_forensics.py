"""Failure-forensics coverage: black-box dumps on induced failures, the
hang watchdog, NaN provenance blaming the exact op, and the per-device
multichip metric surface on the 8-device virtual CPU mesh.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import flags
from paddle_tpu.observability import (
    blackbox,
    explain,
    nan_provenance,
    telemetry,
    watchdog,
)
from paddle_tpu.observability.metrics_registry import REGISTRY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _quiet_forensics():
    """Forensics subsystems off and empty around every test; the shared
    executable registry is purged so dispatch/compile events are scoped
    to the test."""
    import paddle_tpu.executor as executor_mod

    executor_mod._shared_executables.clear()
    telemetry.enable(False)
    telemetry.reset(flops=True)
    explain.reset()
    blackbox.disable()
    blackbox.reset()
    watchdog.stop()
    yield
    watchdog.stop()
    blackbox.disable()
    blackbox.reset()
    telemetry.enable(False)
    telemetry.reset(flops=True)
    explain.reset()


def _nan_program():
    """x -> scale -> log -> mean; feeding a zero makes op 1 (log) emit
    -inf while its inputs are finite."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4])
        h = fluid.layers.scale(x, scale=2.0)
        y = fluid.layers.log(h)
        out = fluid.layers.mean(y)
    return main, startup, out


def _mlp_program(seed=11):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [32])
        label = fluid.layers.data("label", [1], dtype="int64")
        h = fluid.layers.fc(x, size=16, act="relu")
        logits = fluid.layers.fc(h, size=4)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


# -- black box ---------------------------------------------------------------

def test_blackbox_dump_on_induced_executor_exception(tmp_path):
    box = str(tmp_path / "box.json")
    blackbox.enable(box, handlers=False)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4])
        fluid.layers.scale(x, scale=2.0)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    with pytest.raises(RuntimeError):
        exe.run(main, feed={"x": np.ones((2, 4), "float32")},
                fetch_list=["never_produced"])
    snap = json.load(open(box))
    assert snap["reason"] == "unhandled_exception:Executor.run"
    kinds = [e["kind"] for e in snap["events"]]
    # the ring ends with the failing step: its dispatch, then the error
    assert kinds[-1] == "exception"
    assert "dispatch" in kinds
    last = snap["events"][-1]
    assert last["origin"] == "Executor.run"
    assert "never_produced" in last["exc_message"]
    disp = [e for e in snap["events"] if e["kind"] == "dispatch"][-1]
    assert disp["fetch_names"] == ["never_produced"]
    assert any(n == "x" for n, _s, _d in disp["feed_specs"])
    # a dump is a full incident report: flag snapshot + explainer tail
    assert snap["flags"]["check_nan_inf"] is False
    assert isinstance(snap["recompiles"], list)


def test_blackbox_dump_once_per_exception_across_layers(tmp_path):
    """Predictor wrapping Executor records two origins but writes ONE
    dump for one exception object."""
    box = str(tmp_path / "box.json")
    blackbox.enable(box, handlers=False)
    err = ValueError("boom")
    blackbox.record_exception("Executor.run", err)
    first = os.path.getmtime(box)
    time.sleep(0.02)
    blackbox.record_exception("Predictor.run", err)
    assert os.path.getmtime(box) == first  # no second write
    origins = [e.get("origin") for e in blackbox.events()
               if e["kind"] == "exception"]
    assert origins == ["Executor.run", "Predictor.run"]


def test_blackbox_disabled_records_nothing(tmp_path):
    assert not blackbox.ENABLED
    exe = fluid.Executor(fluid.CPUPlace())
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4])
        out = fluid.layers.mean(fluid.layers.scale(x, scale=1.0))
    exe.run(startup)
    exe.run(main, feed={"x": np.ones((1, 4), "float32")},
            fetch_list=[out])
    assert blackbox.events() == []
    assert blackbox.dump() is None  # no path configured


def test_subprocess_killed_by_signal_leaves_readable_box(tmp_path):
    """The acceptance path: a SIGTERM'd process dies BY the signal and
    still leaves a dump whose events end at the failing point."""
    box = str(tmp_path / "sig.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu", FLAGS_blackbox_path=box)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "forensics_smoke.py"),
         "child-signal", box],
        env=env, capture_output=True, timeout=180)
    assert proc.returncode == -signal.SIGTERM, proc.stderr.decode()[-500:]
    snap = json.load(open(box))
    assert snap["reason"] == "fatal_signal:SIGTERM"
    kinds = [e["kind"] for e in snap["events"]]
    assert kinds[-1] == "fatal_signal" and "dispatch" in kinds
    assert snap["thread_stacks"]


# -- watchdog ----------------------------------------------------------------

def test_watchdog_fires_on_stalled_fetch(tmp_path):
    box = str(tmp_path / "hang.json")
    blackbox.enable(box, handlers=False)
    fired = []
    before = REGISTRY.counter("paddle_tpu_watchdog_fires_total").value()
    watchdog.start(timeout=0.2, on_hang=fired.append, abort=False)
    token = watchdog.arm("FetchHandle.result")  # the artificial stall
    deadline = time.time() + 5.0
    while not fired and time.time() < deadline:
        time.sleep(0.05)
    watchdog.disarm(token)
    assert len(fired) == 1
    report = fired[0]
    assert report["stalled"][0]["tag"] == "FetchHandle.result"
    assert report["timeout_s"] == pytest.approx(0.2)
    assert report["dump_path"] == box
    snap = json.load(open(box))
    assert snap["reason"] == "watchdog_hang"
    assert snap["thread_stacks"]  # every live thread, formatted
    assert snap["watchdog"]["stalled"][0]["tag"] == "FetchHandle.result"
    c = REGISTRY.counter("paddle_tpu_watchdog_fires_total")
    assert c.value() == before + 1
    assert watchdog.last_hang()["stalled"] == report["stalled"]


def test_watchdog_idle_gap_does_not_instafire():
    """An idle process (nothing armed) accrues no hang debt: work armed
    after a gap longer than the timeout starts a fresh clock."""
    fired = []
    watchdog.start(timeout=0.2, on_hang=fired.append, abort=False)
    time.sleep(0.45)  # idle > timeout
    token = watchdog.arm("late-work")
    time.sleep(0.1)   # younger than the timeout
    assert fired == []
    watchdog.disarm(token)


def test_watchdog_wedged_token_not_masked_by_other_threads():
    """Per-token aging: one wedged fetch fires (once) even while other
    work keeps arming/disarming, and progress() on the wedged token
    re-arms its episode."""
    fired = []
    watchdog.start(timeout=0.25, on_hang=fired.append, abort=False)
    wedged = watchdog.arm("wedged-fetch")
    deadline = time.time() + 4.0
    while not fired and time.time() < deadline:
        t = watchdog.arm("healthy")
        time.sleep(0.05)
        watchdog.disarm(t)
    assert len(fired) == 1
    assert fired[0]["stalled"][0]["tag"] == "wedged-fetch"
    time.sleep(0.4)
    assert len(fired) == 1  # once per stall episode
    watchdog.progress(wedged)  # it moved: a new stall is a new episode
    deadline = time.time() + 4.0
    while len(fired) < 2 and time.time() < deadline:
        time.sleep(0.05)
    assert len(fired) == 2
    watchdog.disarm(wedged)


def test_watchdog_suspend_covers_slow_compiles():
    """watchdog.suspend() (wrapped around executable resolution in
    core/lowering.py) masks slow-but-alive host work, and the armed
    clocks restart on exit."""
    fired = []
    watchdog.start(timeout=0.2, on_hang=fired.append, abort=False)
    token = watchdog.arm("Executor.run")
    with watchdog.suspend():
        time.sleep(0.5)  # "compiling": longer than the timeout
    time.sleep(0.1)      # clock restarted on exit, still young
    assert fired == []
    watchdog.disarm(token)


def test_watchdog_quiet_while_progress_flows():
    fired = []
    watchdog.start(timeout=0.2, on_hang=fired.append, abort=False)
    token = watchdog.arm("Executor.run")
    for _ in range(5):
        time.sleep(0.08)
        watchdog.progress()  # advancing work must never trip it
    watchdog.disarm(token)
    time.sleep(0.3)  # disarmed + idle: nothing armed, nothing fires
    assert fired == []


def test_watchdog_auto_timeout_follows_p95():
    telemetry.enable(True)
    for _ in range(20):
        telemetry.record_step("single", 2.0)  # p95 = 2s
    watchdog.start(abort=False)  # no explicit timeout, flag is 0
    try:
        assert watchdog.effective_timeout() == pytest.approx(
            max(2.0 * watchdog._AUTO_MULT, watchdog._AUTO_MIN))
    finally:
        watchdog.stop()
    telemetry.reset()
    # no telemetry window -> the fixed default
    assert watchdog.effective_timeout() == watchdog._AUTO_DEFAULT


def test_executor_run_arms_and_disarms_watchdog():
    """Executor.run wears the blackbox.guard shell: every run arms the
    watchdog with its origin and disarms on completion."""
    events = []
    real_arm, real_disarm = watchdog.arm, watchdog.disarm

    def arm(tag, scale=1):
        events.append(("arm", tag))
        return real_arm(tag, scale=scale)

    def disarm(tok):
        events.append(("disarm", tok))
        return real_disarm(tok)

    watchdog.start(timeout=60.0, abort=False)
    try:
        watchdog.arm, watchdog.disarm = arm, disarm
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", [4])
            out = fluid.layers.mean(fluid.layers.scale(x, scale=1.0))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((1, 4), "float32")},
                fetch_list=[out])
    finally:
        watchdog.arm, watchdog.disarm = real_arm, real_disarm
        watchdog.stop()
    arms = [e for e in events if e[0] == "arm"]
    disarms = [e for e in events if e[0] == "disarm"]
    assert len(arms) >= 2 and len(arms) == len(disarms)
    assert all(tag == "Executor.run" for _, tag in arms)


# -- NaN provenance ----------------------------------------------------------

def test_nan_provenance_blames_exact_op(tmp_path):
    box = str(tmp_path / "nan.json")
    blackbox.enable(box, handlers=False)
    main, startup, out = _nan_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    flags.set_flag("check_nan_inf", True)
    try:
        with pytest.raises(RuntimeError, match="NaN/Inf") as ei:
            exe.run(main,
                    feed={"x": np.array([[1.0, 2.0, 0.0, 3.0]],
                                        "float32")},
                    fetch_list=[out])
    finally:
        flags.set_flag("check_nan_inf", False)
    assert isinstance(ei.value, nan_provenance.NonFiniteError)
    d = ei.value.diagnostic
    assert d.rule == "N001" and d.severity == "error"
    assert d.op_type == "log" and d.op_idx == 1 and d.block_idx == 0
    assert d.var_names == ("log_0.tmp_0",)
    assert "clip" in d.hint
    # the finding is in the black box for post-mortem tooling
    snap = json.load(open(box))
    assert snap["nan_diagnostic"]["op_type"] == "log"


def test_nan_provenance_async_result_path():
    main, startup, out = _nan_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    flags.set_flag("check_nan_inf", True)
    try:
        handle = exe.run_async(
            main, feed={"x": np.array([[0.5, 0.0, 1.0, 2.0]], "float32")},
            fetch_list=[out])
        with pytest.raises(nan_provenance.NonFiniteError) as ei:
            handle.result()
    finally:
        flags.set_flag("check_nan_inf", False)
    assert ei.value.diagnostic.op_type == "log"


def test_nan_provenance_blames_poisoned_feed():
    main, startup, out = _nan_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    flags.set_flag("check_nan_inf", True)
    try:
        with pytest.raises(RuntimeError, match="NaN/Inf") as ei:
            exe.run(main,
                    feed={"x": np.array([[1.0, np.nan, 1.0, 1.0]],
                                        "float32")},
                    fetch_list=[out])
    finally:
        flags.set_flag("check_nan_inf", False)
    d = getattr(ei.value, "diagnostic", None)
    assert d is not None and d.op_idx is None  # var-level: upstream
    assert "x" in d.var_names
    assert "upstream" in d.hint


def test_nan_provenance_off_keeps_plain_error():
    main, startup, out = _nan_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    flags.set_flag("check_nan_inf", True)
    flags.set_flag("nan_provenance", False)
    try:
        with pytest.raises(RuntimeError, match="NaN/Inf") as ei:
            exe.run(main,
                    feed={"x": np.array([[1.0, 0.0, 1.0, 1.0]],
                                        "float32")},
                    fetch_list=[out])
    finally:
        flags.set_flag("check_nan_inf", False)
        flags.set_flag("nan_provenance", True)
    assert not isinstance(ei.value, nan_provenance.NonFiniteError)


def test_blame_step_clean_program_returns_none():
    main, startup, out = _nan_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    key = jax.random.PRNGKey(0)
    diag = nan_provenance.blame_step(
        main, {}, {"x": np.ones((1, 4), "float32")}, key)
    assert diag is None


# -- per-device multichip observability --------------------------------------

def test_per_device_metrics_one_label_per_device():
    from paddle_tpu.parallel_executor import ParallelExecutor

    main, startup, loss = _mlp_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    telemetry.enable(True)
    telemetry.reset()
    pe = ParallelExecutor(loss_name=loss.name, main_program=main,
                          use_tpu=False)
    n_dev = pe.device_count
    assert n_dev == 8
    xfer = REGISTRY.counter("paddle_tpu_device_transfer_bytes_total",
                            labels=("device",))
    # the counter is the process's: a file run earlier by this worker
    # (tests/test_sharding.py) may have fed a mesh with telemetry on
    before = {dict(k)["device"]: v for k, v in xfer._series().items()}
    rng = np.random.RandomState(0)
    for _ in range(2):
        pe.run(fetch_list=[loss],
               feed={"x": rng.randn(32, 32).astype("float32"),
                     "label": rng.randint(0, 4, (32, 1)).astype("int64")})
    labels = {"cpu:%d" % i for i in range(n_dev)}
    step_g = REGISTRY.gauge("paddle_tpu_device_step_seconds",
                            labels=("device",))
    assert {dict(k)["device"] for k in step_g._series()} == labels
    series = {dict(k)["device"]: v - before.get(dict(k)["device"], 0)
              for k, v in xfer._series().items()}
    assert set(series) == labels
    # x sharded over data axis: 32x32 f32 / 8 = 512B; label 32x1 i64 / 8
    # = 32B; two steps
    assert all(v == 2 * (512 + 32) for v in series.values())
    assert REGISTRY.gauge("paddle_tpu_device_step_imbalance").value() >= 1.0
    rec = telemetry.step_records()[-1]
    assert set(rec["device_times"]) == labels
    # the Prometheus scrape carries the labeled series
    text = REGISTRY.to_prometheus()
    assert 'paddle_tpu_device_step_seconds{device="cpu:7"}' in text
    assert REGISTRY.gauge("paddle_tpu_mesh_devices").value() == n_dev


def test_device_memory_sums_across_devices(monkeypatch):
    class _Dev(object):
        def __init__(self, i, b):
            self.platform, self.id, self._b = "tpu", i, b

        def memory_stats(self):
            return {"bytes_in_use": self._b}

    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_Dev(0, 100), _Dev(1, 250)])
    assert telemetry.device_memory_bytes() == 350  # sum, not device 0
    assert telemetry.device_memory_bytes(per_device=True) == {
        "tpu:0": 100, "tpu:1": 250}
    # the aggregate gauge keeps its pre-existing name; per-device series
    # land on the labeled twin
    telemetry.record_step("single", 0.01)
    assert REGISTRY.gauge(
        "paddle_tpu_device_bytes_in_use").value() == 350
    per = REGISTRY.gauge("paddle_tpu_device_bytes_in_use_per_device",
                         labels=("device",))
    assert per.value(device="tpu:1") == 250


def test_pipeline_occupancy_gauge():
    occ = telemetry.record_pipeline_occupancy(4, 8)
    assert occ == pytest.approx(8.0 / 11.0)
    g = REGISTRY.gauge("paddle_tpu_pipeline_stage_occupancy",
                       labels=("stage",))
    assert {dict(k)["stage"] for k in g._series()} >= {"0", "1", "2", "3"}
    assert g.value(stage="3") == pytest.approx(8.0 / 11.0)


# -- tool CLIs (jax-free: fast subprocesses) ---------------------------------

def test_blackbox_dump_cli_friendly_on_missing_file(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "blackbox_dump.py"),
         str(tmp_path / "nope.json")],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 2
    assert "FLAGS_blackbox_path" in proc.stdout
    assert "Traceback" not in proc.stderr


def test_blackbox_dump_cli_exit_codes(tmp_path):
    clean = str(tmp_path / "clean.json")
    blackbox.enable(clean, handlers=False)
    blackbox.record_dispatch("Executor.run", fetch_names=["loss"])
    blackbox.dump(reason="on_demand")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "blackbox_dump.py"),
         clean], capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    from paddle_tpu.analysis.diagnostics import Diagnostic

    blackbox.record_nan_diagnostic(Diagnostic(
        "N001", "non-finite-output", "error", "op 'log' went non-finite",
        block_idx=0, op_idx=3, op_type="log", var_names=("y",),
        hint="clip it"))
    blackbox.dump(reason="nan_diagnostic")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "blackbox_dump.py"),
         clean], capture_output=True, text=True, timeout=60)
    assert proc.returncode == 3
    assert "N001" in proc.stdout and "clip it" in proc.stdout


def test_step_breakdown_friendly_on_missing_jsonl(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "step_breakdown.py"),
         "--from-jsonl", str(tmp_path / "none.steps.jsonl")],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    out = proc.stdout + proc.stderr
    assert "FLAGS_telemetry" in out and "Traceback" not in out


def test_step_breakdown_per_device_view(tmp_path):
    path = str(tmp_path / "steps.jsonl")
    with open(path, "w") as f:
        for wall, dt in ((0.010, {"cpu:0": 0.009, "cpu:1": 0.013}),
                         (0.012, {"cpu:0": 0.010, "cpu:1": 0.014})):
            f.write(json.dumps({
                "ts": 1.0, "executor": "parallel", "wall_s": wall,
                "steps": 1, "step_s": wall, "feed_bytes": 64,
                "fetch_bytes": 4, "device_times": dt}) + "\n")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "step_breakdown.py"),
         "--from-jsonl", path, "--per-device"],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l.strip()]
    per_dev = next(l for l in lines if "per_device" in l)
    assert per_dev["most_frequent_straggler"] == "cpu:1"
    assert per_dev["per_device"]["cpu:1"]["steps"] == 2
