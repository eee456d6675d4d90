"""Resilience tests: atomic/digest-verified checkpoints, crash/resume
bit-equality (in-process and via SIGKILLed subprocesses), retry
classification, chaos determinism, master-restart client survival.

The headline contracts (ISSUE 5 acceptance):
* a TrainSession child SIGKILLed mid-step resumes from the newest
  COMPLETE serial and reproduces the uninterrupted run's loss trajectory
  bit-exactly;
* a child killed mid-checkpoint-write leaves only a temp dir, which the
  restart ignores;
* a corrupted latest checkpoint is quarantined (kept for autopsy, out of
  the serial namespace) and the previous complete serial loads instead.
"""

import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags
from paddle_tpu.resilience import chaos, retry
from paddle_tpu.resilience.checkpoint import (
    CheckpointManager, complete_serials, read_manifest,
    verify_checkpoint_dir)
from paddle_tpu.resilience.session import TrainSession

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# shared tiny model
# ---------------------------------------------------------------------------

def _build_model(seed=17, dropout=False):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4], stop_gradient=False)
        y = fluid.layers.data("y", [1])
        h = fluid.layers.fc(x, 8, act="relu")
        if dropout:
            h = fluid.layers.dropout(h, 0.3)
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.05).minimize(loss)
    main.random_seed = seed
    return main, startup, loss


def _feed_for(step):
    r = np.random.RandomState(1000 + step)
    return {"x": r.rand(8, 4).astype("float32"),
            "y": r.rand(8, 1).astype("float32")}


def _session(exe, ckpt_dir, main, **kw):
    kw.setdefault("install_signal_handlers", False)
    kw.setdefault("emergency_on_hang", False)
    return TrainSession(exe, str(ckpt_dir), main_program=main, **kw)


# ---------------------------------------------------------------------------
# CheckpointManager
# ---------------------------------------------------------------------------

def test_manager_save_restore_roundtrip(tmp_path):
    main, startup, loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed=_feed_for(0), fetch_list=[loss])
    mgr = CheckpointManager(str(tmp_path), executor=exe, main_program=main)
    mgr.save(step=1)
    w_before = np.asarray(fluid.global_scope().get_value(
        main.global_block().all_parameters()[0].name))
    # clobber, then restore
    fluid.global_scope().set_value(
        main.global_block().all_parameters()[0].name,
        np.zeros_like(w_before))
    manifest = mgr.restore()
    assert manifest["step"] == 1 and manifest["serial"] == 1
    w_after = np.asarray(fluid.global_scope().get_value(
        main.global_block().all_parameters()[0].name))
    np.testing.assert_array_equal(w_before, w_after)
    # manifest carries digests + rng for every var file
    m = read_manifest(str(tmp_path / "checkpoint_1"))
    assert m["rng"]["run_counter"] == exe._run_counter
    assert all(v["sha256"] for v in m["vars"].values())
    assert verify_checkpoint_dir(str(tmp_path / "checkpoint_1")) == []


def test_manager_async_save_and_retention(tmp_path):
    main, startup, loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    mgr = CheckpointManager(str(tmp_path), executor=exe,
                            main_program=main, max_to_keep=2)
    for step in range(1, 6):
        exe.run(main, feed=_feed_for(step), fetch_list=[loss])
        mgr.save_async(step)
    mgr.wait()
    assert mgr.last_error is None
    assert complete_serials(str(tmp_path)) == [4, 5]


def test_restore_skips_and_quarantines_corrupt_latest(tmp_path):
    main, startup, loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    mgr = CheckpointManager(str(tmp_path), executor=exe, main_program=main)
    mgr.save(step=1)
    exe.run(main, feed=_feed_for(1), fetch_list=[loss])
    mgr.save(step=2)
    # corrupt the newest serial: flip bytes in one var file
    d2 = tmp_path / "checkpoint_2"
    victim = next(f for f in os.listdir(d2) if f.endswith(".npy"))
    with open(d2 / victim, "r+b") as f:
        f.seek(-4, os.SEEK_END)
        f.write(b"\xff\xff\xff\xff")
    manifest = mgr.restore()
    assert manifest["serial"] == 1  # fell back to previous complete
    assert 2 not in complete_serials(str(tmp_path))
    corrupt = [d for d in os.listdir(tmp_path) if ".corrupt-" in d]
    assert corrupt, "corrupt serial must be quarantined, not deleted"


def test_restore_ignores_partial_tmp_dir(tmp_path):
    main, startup, loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    mgr = CheckpointManager(str(tmp_path), executor=exe, main_program=main)
    mgr.save(step=3)
    # a writer killed mid-save leaves var files but no manifest, under
    # a temp name — restore must not even consider it
    fake = tmp_path / "checkpoint_9.tmp-12345"
    fake.mkdir()
    np.save(fake / "garbage.npy", np.zeros(3))
    manifest = mgr.restore()
    assert manifest["serial"] == 3
    assert complete_serials(str(tmp_path)) == [3]


def test_restore_skips_v1_marker_manifests(tmp_path):
    """A dir written by io.save_checkpoint (v1 manifest, no digests/vars)
    is complete but not the manager's dialect: restore must fall back to
    a manager serial instead of 'loading' zero vars and claiming ok."""
    main, startup, loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    mgr = CheckpointManager(str(tmp_path), executor=exe, main_program=main)
    mgr.save(step=2)
    fluid.io.save_checkpoint(exe, str(tmp_path), main_program=main,
                             serial=9)  # v1 dialect, newest serial
    manifest = mgr.restore()
    assert manifest["serial"] == 2  # v1 dir skipped, NOT quarantined
    assert os.path.isdir(tmp_path / "checkpoint_9")
    assert not [d for d in os.listdir(tmp_path) if ".corrupt-" in d]


def test_restore_empty_dir_returns_none(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "nope"))
    assert mgr.restore() is None
    assert mgr.latest_serial() is None


def test_checkpoint_failure_counted(tmp_path):
    from paddle_tpu.observability.metrics_registry import REGISTRY

    ctr = REGISTRY.counter("paddle_tpu_checkpoint_failures_total",
                           labels=["stage"])
    before = ctr.value(stage="save")
    main, startup, loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    mgr = CheckpointManager(str(tmp_path), executor=exe, main_program=main)
    chaos.configure("io@site=ckpt.write,p=1,n=1")
    try:
        with pytest.raises(IOError):
            mgr.save(step=1)
    finally:
        chaos.disable()
    assert ctr.value(stage="save") == before + 1
    assert complete_serials(str(tmp_path)) == []  # tmp dir cleaned up


# ---------------------------------------------------------------------------
# io.save_checkpoint atomicity (satellite)
# ---------------------------------------------------------------------------

def test_io_save_checkpoint_atomic_and_partial_skipped(tmp_path):
    main, startup, loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    ckpt = tmp_path / "ckpt"
    step_dir = fluid.io.save_checkpoint(exe, str(ckpt), main_program=main,
                                        serial=1)
    assert os.path.exists(os.path.join(step_dir, "__manifest__.json"))
    # a torn write: dir exists, manifest (and sharding marker) missing
    partial = ckpt / "checkpoint_7"
    partial.mkdir()
    np.save(partial / "w.npy", np.zeros(2))
    # and a stale temp dir from a killed writer
    (ckpt / "checkpoint_8.tmp-999").mkdir()
    assert fluid.io._checkpoint_serials(str(ckpt)) == [1]
    serial = fluid.io.load_checkpoint(exe, str(ckpt), main_program=main)
    assert serial == 1  # NOT 7: the partial dir is never "latest"


def test_io_load_checkpoint_reads_manager_dirs(tmp_path):
    """One on-disk dialect: io.load_checkpoint loads what the v2 manager
    wrote (plain npy layout + manifest)."""
    main, startup, loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    mgr = CheckpointManager(str(tmp_path), executor=exe, main_program=main)
    mgr.save(step=4)
    pname = main.global_block().all_parameters()[0].name
    w = np.asarray(fluid.global_scope().get_value(pname))
    fluid.global_scope().set_value(pname, np.zeros_like(w))
    assert fluid.io.load_checkpoint(exe, str(tmp_path),
                                    main_program=main) == 4
    np.testing.assert_array_equal(
        w, np.asarray(fluid.global_scope().get_value(pname)))


# ---------------------------------------------------------------------------
# retry policy
# ---------------------------------------------------------------------------

def test_retry_classification_table():
    assert retry.is_transient(IOError("disk glitch"))
    assert retry.is_transient(ConnectionError("reset"))
    assert retry.is_transient(EOFError())
    assert retry.is_transient(retry.TransientError("wrapped"))
    assert retry.is_transient(chaos.ChaosIOError("injected"))
    assert retry.is_transient(RuntimeError("UNAVAILABLE: backend"))
    assert not retry.is_transient(ValueError("bad shape"))
    assert not retry.is_transient(KeyError("var"))
    # deterministic OS failures: retrying replays them verbatim
    assert not retry.is_transient(FileNotFoundError("gone"))
    assert not retry.is_transient(PermissionError("denied"))
    assert not retry.is_transient(IsADirectoryError("dir"))
    assert not retry.is_transient(RuntimeError("NaN/Inf detected in x"))
    assert not retry.is_transient(RuntimeError("some other failure"))
    from paddle_tpu.analysis import ProgramVerifyError

    assert not retry.is_transient(ProgramVerifyError([]))


def test_retry_succeeds_after_transient_and_counts():
    from paddle_tpu.observability.metrics_registry import REGISTRY

    ctr = REGISTRY.counter("paddle_tpu_retries_total",
                           labels=["origin"])
    before = ctr.value(origin="test.flaky")
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise IOError("transient %d" % len(calls))
        return "ok"

    flags.set_flag("retry_backoff_s", 0.0)
    try:
        assert retry.call(flaky, origin="test.flaky", retries=5) == "ok"
    finally:
        flags.set_flag("retry_backoff_s", 0.05)
    assert len(calls) == 3
    assert ctr.value(origin="test.flaky") == before + 2


def test_retry_never_retries_user_errors():
    calls = []

    def broken():
        calls.append(1)
        raise ValueError("user bug")

    with pytest.raises(ValueError):
        retry.call(broken, origin="test.user", retries=5)
    assert len(calls) == 1


def test_retry_disabled_by_default_flag():
    calls = []

    def flaky():
        calls.append(1)
        raise IOError("transient")

    # FLAGS_dispatch_retries defaults to 0: straight through, no retry
    with pytest.raises(IOError):
        retry.call(flaky, origin="test.off")
    assert len(calls) == 1


def test_executor_dispatch_retries_injected_fault():
    from paddle_tpu.observability.metrics_registry import REGISTRY

    ctr = REGISTRY.counter("paddle_tpu_retries_total",
                           labels=["origin"])
    before = ctr.value(origin="Executor.dispatch")
    main, startup, loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    flags.set_flag("dispatch_retries", 3)
    flags.set_flag("retry_backoff_s", 0.0)
    chaos.configure("compile@site=exec.dispatch,n=2")
    try:
        out = exe.run(main, feed=_feed_for(0), fetch_list=[loss])
        assert np.isfinite(np.asarray(out[0])).all()
        fired = chaos.fires("exec.dispatch")
    finally:
        chaos.disable()
        flags.set_flag("dispatch_retries", 0)
        flags.set_flag("retry_backoff_s", 0.05)
    assert ctr.value(origin="Executor.dispatch") == before + 2
    assert fired == 2


def test_executor_fresh_compile_retries_injected_fault():
    main, startup, loss = _build_model(seed=23)
    exe = fluid.Executor(fluid.CPUPlace())
    flags.set_flag("dispatch_retries", 2)
    flags.set_flag("retry_backoff_s", 0.0)
    chaos.configure("compile@n=1")  # home site: exec.compile
    try:
        exe.run(startup)
        # use_program_cache=False forces a re-trace even when an earlier
        # test already published this structure to the shared registry —
        # the injected fault must hit a real fresh-compile path
        out = exe.run(main, feed=_feed_for(0), fetch_list=[loss],
                      use_program_cache=False)
        assert np.isfinite(np.asarray(out[0])).all()
        fired = chaos.fires("exec.compile")
    finally:
        chaos.disable()
        flags.set_flag("dispatch_retries", 0)
        flags.set_flag("retry_backoff_s", 0.05)
    assert fired == 1


# ---------------------------------------------------------------------------
# chaos harness
# ---------------------------------------------------------------------------

def test_chaos_spec_parse_and_defaults():
    cl = chaos.configure(
        "seed=9;kill@step=12;io@site=exec.dispatch,p=0.25,n=3;"
        "slow@site=master.call,secs=0.01")
    assert [c["kind"] for c in cl] == ["kill", "io", "slow"]
    assert cl[0]["site"] == "session.step" and cl[0]["n"] == 1
    assert cl[1]["p"] == 0.25 and cl[1]["n"] == 3
    assert cl[2]["secs"] == 0.01
    chaos.disable()
    assert not chaos.ENABLED


def test_chaos_bad_spec_rejected():
    with pytest.raises(ValueError):
        chaos.configure("explode@p=1")
    with pytest.raises(ValueError):
        chaos.configure("io@p=1")  # io has no default site
    chaos.disable()


def test_chaos_seeded_draws_are_deterministic():
    def fire_pattern():
        chaos.configure("seed=3;io@site=t.x,p=0.5,n=100")
        hits = []
        for i in range(20):
            try:
                chaos.fault("t.x")
                hits.append(0)
            except chaos.ChaosIOError:
                hits.append(1)
        chaos.disable()
        return hits

    a, b = fire_pattern(), fire_pattern()
    assert a == b and 0 < sum(a) < 20


def test_chaos_step_clause_fires_exactly_once():
    chaos.configure("kill@step=5,site=t.step")  # site override: no SIGKILL
    # kill clauses raise nothing at non-matching steps
    for step in (0, 1, 4, 6):
        chaos.fault("t.step", step=step)
    assert chaos.fires() == 0
    chaos.disable()


def test_chaos_counts_in_metrics():
    from paddle_tpu.observability.metrics_registry import REGISTRY

    ctr = REGISTRY.counter("paddle_tpu_chaos_faults_total",
                           labels=["site", "kind"])
    before = ctr.value(site="t.m", kind="io")
    chaos.configure("io@site=t.m,p=1,n=2")
    for _ in range(2):
        with pytest.raises(chaos.ChaosIOError):
            chaos.fault("t.m")
    chaos.fault("t.m")  # budget exhausted: no fire
    chaos.disable()
    assert ctr.value(site="t.m", kind="io") == before + 2


# ---------------------------------------------------------------------------
# TrainSession (in-process)
# ---------------------------------------------------------------------------

def test_session_periodic_checkpoint_and_resume(tmp_path):
    main, startup, loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    sess = _session(exe, tmp_path, main, interval_steps=2)
    for i in range(5):
        sess.run(feed=_feed_for(i), fetch_list=[loss])
    sess.close()  # final sync save at step 5
    assert 5 in complete_serials(str(tmp_path))

    # a "restarted process": fresh executor + scope, same program build
    from paddle_tpu.core.scope import Scope
    import paddle_tpu.executor as executor_mod

    executor_mod._global_scope = Scope()
    executor_mod._scope_stack = [executor_mod._global_scope]
    exe2 = fluid.Executor(fluid.CPUPlace())
    exe2.run(startup)
    sess2 = _session(exe2, tmp_path, main)
    assert sess2.step == 5 and sess2.resumed_serial == 5
    sess2.close(save=False)


def test_session_resume_is_bit_identical_with_dropout(tmp_path):
    """The loss-trajectory contract, in-process: save at step 5, restart
    into a fresh scope/executor, steps 5..9 match the uninterrupted
    run's bit for bit — including dropout masks (RNG stream restored)."""
    from paddle_tpu.core.scope import Scope
    import paddle_tpu.executor as executor_mod

    def fresh_world():
        from paddle_tpu import framework, unique_name

        framework.switch_main_program(framework.Program())
        framework.switch_startup_program(framework.Program())
        unique_name.switch({})
        executor_mod._global_scope = Scope()
        executor_mod._scope_stack = [executor_mod._global_scope]
        np.random.seed(42)

    def run_steps(sess, loss, start, n):
        return [float(np.asarray(
            sess.run(feed=_feed_for(start + i), fetch_list=[loss])[0]
        ).reshape(-1)[0]) for i in range(n)]

    fresh_world()
    main, startup, loss = _build_model(dropout=True)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    sA = _session(exe, tmp_path / "none", main)
    uninterrupted = run_steps(sA, loss, 0, 10)
    sA.close(save=False)

    fresh_world()
    main, startup, loss = _build_model(dropout=True)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    sB = _session(exe, tmp_path / "ck", main)
    resumed = run_steps(sB, loss, 0, 5)
    sB.close()  # checkpoint at step 5; "process dies" here

    fresh_world()
    main, startup, loss = _build_model(dropout=True)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    sB2 = _session(exe, tmp_path / "ck", main)
    assert sB2.step == 5
    resumed += run_steps(sB2, loss, 5, 5)
    sB2.close(save=False)

    assert resumed == uninterrupted  # bit-exact, not allclose


@pytest.mark.slow
def test_session_sigterm_checkpoints_then_dies_by_signal(tmp_path):
    """Subprocess: SIGTERM mid-training → the in-flight step finishes, a
    final checkpoint lands, and the process dies BY the signal (what a
    preemption supervisor keys on)."""
    child = _spawn_child(tmp_path, mode="sigterm", steps=50)
    assert child.returncode == -signal.SIGTERM, child.returncode
    serials = complete_serials(str(tmp_path / "ckpt"))
    assert serials, "SIGTERM must leave a final checkpoint"
    m = read_manifest(
        str(tmp_path / "ckpt" / ("checkpoint_%d" % serials[-1])))
    assert m["step"] >= 1


# ---------------------------------------------------------------------------
# subprocess crash/resume legs
# ---------------------------------------------------------------------------

_CHILD = os.path.join(REPO, "tools", "chaos_smoke.py")


def _spawn_child(tmp_path, mode, steps, chaos_spec="", extra_env=None,
                 timeout=120):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", FLAGS_chaos_spec=chaos_spec)
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, _CHILD, "child", "--mode", mode,
         "--ckpt-dir", str(tmp_path / "ckpt"), "--steps", str(steps),
         "--out", str(tmp_path / ("out_%s.json" % mode))],
        env=env, timeout=timeout)


def _child_losses(tmp_path, mode):
    with open(tmp_path / ("out_%s.json" % mode)) as f:
        return json.load(f)


@pytest.mark.slow
def test_sigkill_resume_bit_identical_subprocess(tmp_path):
    """THE acceptance test: child killed by SIGKILL at a seeded step
    (no cleanup possible), restarted child resumes from the newest
    complete serial, and the combined trajectory equals an
    uninterrupted run at the same total step count, bit for bit."""
    # uninterrupted reference
    ref = _spawn_child(tmp_path, mode="ref", steps=12)
    assert ref.returncode == 0, ref.returncode
    reference = _child_losses(tmp_path, "ref")
    assert len(reference["losses"]) == 12

    kill_dir = tmp_path / "k"
    kill_dir.mkdir()
    victim = _spawn_child(kill_dir, mode="train", steps=12,
                          chaos_spec="kill@step=7")
    assert victim.returncode == -signal.SIGKILL, victim.returncode
    survivor = _spawn_child(kill_dir, mode="train", steps=12)
    assert survivor.returncode == 0, survivor.returncode
    out = _child_losses(kill_dir, "train")
    assert out["resumed_step"] > 0, "child must resume, not restart at 0"
    assert out["losses"] == reference["losses"][out["resumed_step"]:]
    assert out["final_loss"] == reference["final_loss"]


@pytest.mark.slow
def test_sigkill_mid_checkpoint_write_leaves_only_tmp(tmp_path):
    """Kill the background writer mid-checkpoint: the next restart must
    see only complete serials (the torn write is a temp dir)."""
    victim = _spawn_child(
        tmp_path, mode="train", steps=12,
        chaos_spec="kill@site=ckpt.write,n=1")
    assert victim.returncode == -signal.SIGKILL, victim.returncode
    ckpt = tmp_path / "ckpt"
    leftovers = sorted(os.listdir(ckpt)) if ckpt.exists() else []
    assert any(".tmp-" in d for d in leftovers), leftovers
    # none of the complete serials is the torn one; a restart resumes
    survivor = _spawn_child(tmp_path, mode="train", steps=12)
    assert survivor.returncode == 0, survivor.returncode
    out = _child_losses(tmp_path, "train")
    assert np.isfinite(out["final_loss"])


# ---------------------------------------------------------------------------
# master-restart client survival (satellite)
# ---------------------------------------------------------------------------

def test_master_client_survives_master_restart(tmp_path):
    from paddle_tpu.distributed import MasterClient, MasterService

    snap = str(tmp_path / "master.json")
    s = MasterService(timeout_s=5.0, snapshot_path=snap)
    s.set_dataset(["a", "b", "c", "d"])
    host, port = s.serve()
    c = MasterClient((host, port))
    t = c.get_task()
    assert t is not None
    c.task_finished(t.task_id)
    # master dies and comes back on the SAME port with its snapshot
    s.close()
    s2 = MasterService(timeout_s=5.0, snapshot_path=snap)
    s2.serve(host=host, port=port)
    # the client's socket is dead; _call must reconnect-and-retry once
    # instead of surfacing a raw socket error to the training loop
    t2 = c.get_task()
    assert t2 is not None
    assert c.task_finished(t2.task_id)
    c.close()
    s2.close()


def test_ckpt_inspect_cli(tmp_path):
    """The operator CLI: exit 0 + digest report on a good checkpoint,
    exit 2 after a byte flip (the restore-gate contract)."""
    main, startup, loss = _build_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    mgr = CheckpointManager(str(tmp_path), executor=exe, main_program=main)
    mgr.save(step=2)
    cli = os.path.join(REPO, "tools", "ckpt_inspect.py")
    proc = subprocess.run(
        [sys.executable, cli, str(tmp_path), "--verify"],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "all digests match" in proc.stdout
    d = tmp_path / "checkpoint_2"
    victim = next(f for f in os.listdir(d) if f.endswith(".npy"))
    with open(d / victim, "r+b") as f:
        f.seek(-2, os.SEEK_END)
        tail = f.read(2)
        f.seek(-2, os.SEEK_END)
        # flip, not zero: a zero-initialized bias already ends in zeros
        # and "corrupting" it with zeros changes nothing
        f.write(bytes(b ^ 0xFF for b in tail))
    proc = subprocess.run(
        [sys.executable, cli, str(d), "--verify"],
        capture_output=True, text=True)
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "digest mismatch" in proc.stdout


def test_watchdog_on_hang_registry():
    from paddle_tpu.observability import watchdog

    seen = []
    cb = watchdog.register_on_hang(seen.append)
    try:
        with watchdog._lock:
            assert seen.append in watchdog._on_hang_extra
    finally:
        watchdog.unregister_on_hang(cb)
    with watchdog._lock:
        assert seen.append not in watchdog._on_hang_extra
