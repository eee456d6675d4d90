"""Chaos smoke (CI ``chaos`` stage): kill training the way production
does, then prove recovery is exact — not approximate.

Four legs, all asserted from the parent:

1. **Preemption leg** — a TrainSession child is SIGKILLed by a seeded
   chaos kill-point mid-run (no cleanup, like a real preemption). A
   restarted child must resume from the newest COMPLETE serial and its
   loss trajectory must equal an uninterrupted reference run at the same
   total step count **bit for bit** (RNG stream restored, dropout masks
   and all).
2. **Transient-fault leg** — a child runs with injected transient
   dispatch faults under ``FLAGS_dispatch_retries``: it must complete
   successfully, ``paddle_tpu_retries_total`` must be nonzero in the
   metrics scrape, and the black box must carry the ``retry`` and
   ``chaos_fault`` flight events (a run that silently survived faults is
   an incident report, not a clean run).
3. **Corruption leg** — the parent flips bytes in the newest checkpoint;
   the next child must quarantine it (``.corrupt-`` dir kept for
   autopsy) and resume from the previous complete serial.
4. **OOM leg** — a child with retries ENABLED hits an injected
   ``oom`` fault at ``exec.dispatch`` (a RESOURCE_EXHAUSTED allocator
   death, deterministic). It must die on the FIRST attempt — zero
   retries in the scrape, no budget burned replaying a deterministic
   failure — and leave a black box whose M001 diagnostic names the
   top-3 live-buffer holders; ``tools/blackbox_dump.py`` must surface
   it with its distinct exit code (4).

The ``child`` subcommand is the training worker (also driven directly by
``tests/test_resilience.py``): a deterministic 2-layer MLP + dropout
TrainSession loop whose per-step feeds are a pure function of the step
index, so any two runs at equal step counts are comparable bit-exactly.

Usage: python tools/chaos_smoke.py            # parent, runs all legs
       python tools/chaos_smoke.py child --mode {ref|train|sigterm} \
           --ckpt-dir D --steps N --out F     # worker (internal)
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

INTERVAL_STEPS = 4


# ---------------------------------------------------------------------------
# child: the deterministic training worker
# ---------------------------------------------------------------------------

def _feed_for(step):
    import numpy as np

    r = np.random.RandomState(1000 + step)
    return {"x": r.rand(8, 4).astype("float32"),
            "y": r.rand(8, 1).astype("float32")}


def _child(args):
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.resilience import TrainSession

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4], stop_gradient=False)
        y = fluid.layers.data("y", [1])
        h = fluid.layers.fc(x, 8, act="relu")
        h = fluid.layers.dropout(h, 0.3)  # RNG-dependent on purpose
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.05).minimize(loss)
    # BOTH programs get the fixed seed: the startup program's initializer
    # RNG must be process-independent too, or no two children ever agree
    main.random_seed = 17
    startup.random_seed = 17

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    sess = TrainSession(exe, args.ckpt_dir, main_program=main,
                        interval_steps=INTERVAL_STEPS)
    resumed_step = sess.step
    losses = []
    while sess.step < args.steps:
        if args.mode == "sigterm" and len(losses) == 3:
            # preemption notice to self: the session handler must finish
            # cleanly — final checkpoint, then death BY the signal
            os.kill(os.getpid(), signal.SIGTERM)
            raise SystemExit("unreachable: SIGTERM should have killed us")
        out = sess.run(feed=_feed_for(sess.step), fetch_list=[loss])
        losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
        # a realistic step is 100ms+ of device time; the toy CPU step is
        # sub-ms, which would give the async checkpoint writer no window
        # at all before a seeded kill lands a few steps later
        time.sleep(0.05)
    sess.close()
    with open(args.out, "w") as f:
        json.dump({
            "losses": losses,
            "final_loss": losses[-1] if losses else None,
            "resumed_step": resumed_step,
            "total_step": sess.step,
        }, f)


# ---------------------------------------------------------------------------
# parent: the three legs
# ---------------------------------------------------------------------------

def _env(chaos_spec="", **extra):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", FLAGS_chaos_spec=chaos_spec)
    env.update({k: str(v) for k, v in extra.items()})
    return env


def _run_child(tmp, name, mode, steps, env):
    out = os.path.join(tmp, "out_%s.json" % name)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "child",
         "--mode", mode, "--ckpt-dir", os.path.join(tmp, name, "ckpt"),
         "--steps", str(steps), "--out", out],
        env=env, timeout=300)
    return proc.returncode, out


def _load(out):
    with open(out) as f:
        return json.load(f)


def _preemption_leg(tmp):
    rc, ref_out = _run_child(tmp, "ref", "ref", 12, _env())
    assert rc == 0, "reference run failed rc=%d" % rc
    ref = _load(ref_out)

    rc, _ = _run_child(tmp, "kill", "train", 12,
                       _env(chaos_spec="kill@step=7"))
    assert rc == -signal.SIGKILL, (
        "victim should die BY SIGKILL (rc=-9), got rc=%d" % rc)
    rc, out = _run_child(tmp, "kill", "train", 12, _env())
    assert rc == 0, "resumed run failed rc=%d" % rc
    res = _load(out)
    assert res["resumed_step"] > 0, "must resume from a checkpoint"
    assert res["losses"] == ref["losses"][res["resumed_step"]:], (
        "resumed trajectory diverged from the uninterrupted run:\n"
        "ref tail: %s\nresumed:  %s"
        % (ref["losses"][res["resumed_step"]:], res["losses"]))
    print("chaos preemption leg OK: SIGKILL at step 7, resumed at %d, "
          "trajectory bit-identical" % res["resumed_step"])


def _retry_leg(tmp):
    prom = os.path.join(tmp, "retry.prom")
    box = os.path.join(tmp, "retry.box.json")
    rc, out = _run_child(
        tmp, "retry", "train", 8,
        _env(chaos_spec="seed=5;compile@site=exec.dispatch,n=2",
             FLAGS_dispatch_retries=3, FLAGS_retry_backoff_s=0.01,
             FLAGS_metrics_path=prom, FLAGS_blackbox_path=box))
    assert rc == 0, (
        "run with injected transient faults + retries should SUCCEED, "
        "got rc=%d" % rc)
    res = _load(out)
    assert res["total_step"] == 8
    with open(prom) as f:
        scrape = f.read()
    retr = [line for line in scrape.splitlines()
            if line.startswith("paddle_tpu_retries_total")]
    total = sum(float(line.rsplit(None, 1)[-1]) for line in retr)
    assert total > 0, "metrics must show retries, scrape had: %r" % retr
    with open(box) as f:
        kinds = [e["kind"] for e in json.load(f)["events"]]
    assert "retry" in kinds and "chaos_fault" in kinds, kinds
    print("chaos retry leg OK: %d retries recorded, run completed, "
          "black box carries retry + chaos_fault events" % int(total))


def _corruption_leg(tmp):
    rc, _ = _run_child(tmp, "corrupt", "train", 12, _env())
    assert rc == 0
    ckpt = os.path.join(tmp, "corrupt", "ckpt")
    serials = sorted(
        int(d[len("checkpoint_"):]) for d in os.listdir(ckpt)
        if d.startswith("checkpoint_")
        and d[len("checkpoint_"):].isdigit())
    latest = serials[-1]
    victim_dir = os.path.join(ckpt, "checkpoint_%d" % latest)
    victim = next(f for f in sorted(os.listdir(victim_dir))
                  if f.endswith(".npy"))
    with open(os.path.join(victim_dir, victim), "r+b") as f:
        f.seek(-4, os.SEEK_END)
        f.write(b"\xff\xff\xff\xff")
    rc, out = _run_child(tmp, "corrupt", "train", 16, _env())
    assert rc == 0
    res = _load(out)
    assert res["resumed_step"] < latest, (
        "corrupt serial %d must be skipped, resumed at %d"
        % (latest, res["resumed_step"]))
    assert res["resumed_step"] > 0, "older complete serial must load"
    quarantined = [d for d in os.listdir(ckpt) if ".corrupt-" in d]
    assert quarantined, "corrupt serial must be quarantined for autopsy"
    print("chaos corruption leg OK: serial %d quarantined (%s), resumed "
          "from step %d" % (latest, quarantined[0], res["resumed_step"]))


def _oom_leg(tmp):
    prom = os.path.join(tmp, "oom.prom")
    box = os.path.join(tmp, "oom.box.json")
    # skip=3: startup dispatch + two clean train steps pass (populating
    # the ledger: params, opt state, feeds), the third step's dispatch
    # dies RESOURCE_EXHAUSTED — deterministic, like a real allocator OOM
    rc, _out = _run_child(
        tmp, "oom", "train", 8,
        _env(chaos_spec="oom@site=exec.dispatch,skip=3,n=1",
             FLAGS_dispatch_retries=3, FLAGS_retry_backoff_s=0.01,
             FLAGS_telemetry=1, FLAGS_metrics_path=prom,
             FLAGS_blackbox_path=box))
    assert rc > 0, (
        "an injected OOM is deterministic and never retried: the run "
        "must die by the exception (got rc=%d)" % rc)
    with open(prom) as f:
        scrape = f.read()
    retr = [line for line in scrape.splitlines()
            if line.startswith("paddle_tpu_retries_total")]
    total = sum(float(line.rsplit(None, 1)[-1]) for line in retr)
    assert total == 0, (
        "OOM must be classified never-transient — %d retry(ies) burned "
        "their budget on a deterministic death: %r" % (int(total), retr))
    with open(box) as f:
        snap = json.load(f)
    diag = snap.get("oom_diagnostic")
    assert diag and diag.get("rule") == "M001", (
        "black box must carry the M001 diagnostic, got %r" % (diag,))
    holders = diag.get("top_holders") or []
    assert len(holders) >= 3, (
        "M001 must name the top-3 live-buffer holders, got %r" % holders)
    kinds = [e["kind"] for e in snap["events"]]
    assert "chaos_fault" in kinds and "oom_diagnostic" in kinds, kinds
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "blackbox_dump.py"),
         box], stdout=subprocess.DEVNULL)
    assert proc.returncode == 4, (
        "blackbox_dump must exit 4 on an M001 dump, got %d"
        % proc.returncode)
    print("chaos oom leg OK: died first attempt, 0 retries, M001 names "
          "%s" % ", ".join(h["name"] for h in holders[:3]))


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "child":
        p = argparse.ArgumentParser()
        p.add_argument("cmd")
        p.add_argument("--mode", choices=["ref", "train", "sigterm"],
                       required=True)
        p.add_argument("--ckpt-dir", required=True)
        p.add_argument("--steps", type=int, required=True)
        p.add_argument("--out", required=True)
        _child(p.parse_args())
        return
    import tempfile

    with tempfile.TemporaryDirectory(prefix="chaos_") as tmp:
        _preemption_leg(tmp)
        _retry_leg(tmp)
        _corruption_leg(tmp)
        _oom_leg(tmp)
    print("chaos smoke OK")


if __name__ == "__main__":
    main()
