"""Elastic smoke (CI ``elastic`` stage): kill a fleet the way production
does, then prove the reshape is exact — not approximate.

Two legs, all asserted from the parent (which hosts the coordinator):

1. **Churn leg** — two worker subprocesses register with a
   FleetCoordinator (min_workers=2) and train the same deterministic
   MLP over a per-worker ``ParallelExecutor`` whose planning mesh is
   sized to the fleet (fsdp=world — the repo's local-mesh stand-in for
   the global device mesh, same discipline as every multichip CPU
   test). The parent SIGKILLs worker 1 mid-epoch and asserts:

   * the coordinator **evicts it within the lease timeout** (measured
     from the kill) and bumps the membership generation;
   * the survivor reshards to world 1 and keeps training, and its
     world-1 loss segment is **bit-identical** to a fresh process
     restored from the same barrier checkpoint at world 1;
   * a **re-admitted** worker joins at the next generation, restores
     the chief's barrier serial, and both workers' world-2 segments are
     bit-identical to each other AND to a fresh restore at world 2;
   * the survivor's metrics scrape carries the fleet gauges
     (``paddle_tpu_fleet_generation``/``_size``) and
     ``paddle_tpu_reshard_seconds`` observations; the coordinator side
     counts the eviction; the final checkpoint passes
     ``tools/ckpt_inspect.py --verify`` and records the mesh.

2. **Coordinator-restart leg** — the coordinator is closed mid-run and
   restarted from its snapshot on the same port. The worker's retrying
   heartbeats (``paddle_tpu_retries_total{origin=FleetClient._call}``)
   ride out the restart, membership recovers at the SAME generation (no
   spurious reshape), and the run finishes every step.

Usage: python tools/elastic_smoke.py          # parent, runs both legs
       python tools/elastic_smoke.py child ...  # worker (internal)
"""

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# the churn leg's step budget must outlast: kill (~5s in) + lease expiry
# (2s) + the re-admitted worker's cold jax start (~5-10s), all while the
# survivor keeps stepping at ~sleep-speed — generous on purpose, the leg
# asserts segments, not totals
STEPS = 160
STEP_SLEEP = 0.15
LEASE_S = 2.0


# ---------------------------------------------------------------------------
# child: the elastic training worker
# ---------------------------------------------------------------------------


def _feed_for(step):
    import numpy as np

    r = np.random.RandomState(5000 + step)
    return {"x": r.rand(8, 16).astype("float32"),
            "y": r.rand(8, 1).astype("float32")}


def _make_build_fn(holder):
    """build_fn(world, rank): a fsdp=world planning-mesh PE over the
    first ``world`` local CPU devices. The first fc weight (16x64,
    numel 1024) clears the transpiler's shard threshold, so world>=2
    checkpoints actually exercise the shard-file dialect. The program is
    built ONCE and reused across rebuilds (unique-name discipline)."""
    import paddle_tpu as fluid
    from paddle_tpu.parallel_executor import BuildStrategy, ParallelExecutor

    def build_fn(world, rank):
        if "main" not in holder:
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                x = fluid.layers.data("x", [16], stop_gradient=False)
                y = fluid.layers.data("y", [1])
                h = fluid.layers.fc(x, 64, act="relu")
                h = fluid.layers.dropout(h, 0.3)  # RNG-dependent on purpose
                pred = fluid.layers.fc(h, 1)
                loss = fluid.layers.mean(
                    fluid.layers.square_error_cost(pred, y))
                fluid.optimizer.SGD(0.05).minimize(loss)
            main.random_seed = 23
            startup.random_seed = 23
            holder.update(main=main, startup=startup, loss=loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(holder["startup"])
        bs = BuildStrategy()
        bs.reduce_strategy = BuildStrategy.ReduceStrategy.Reduce
        pe = ParallelExecutor(
            loss_name=holder["loss"].name, main_program=holder["main"],
            build_strategy=bs, use_tpu=False, num_devices=world)
        return pe, holder["main"]

    return build_fn


def _child_elastic(args):
    import numpy as np

    from paddle_tpu.elastic.worker import ElasticTrainSession

    holder = {}
    sess = ElasticTrainSession(
        args.coordinator, args.ckpt_dir, _make_build_fn(holder),
        worker_id=args.worker_id, heartbeat_interval_s=0.3)
    losses = []
    while sess.step < args.steps:
        out = sess.run(feed=_feed_for(sess.step),
                       fetch_list=[holder["loss"]])
        # sess.step was bumped by run(): this loss belongs to step-1
        losses.append([sess.step - 1,
                       float(np.asarray(out[0]).reshape(-1)[0])])
        time.sleep(args.sleep)
    generation = sess.generation
    # leave=False: near-simultaneous finishers must not reshape each
    # other's tails — the fleet drains by lease expiry after exit
    sess.close(leave=False)
    with open(args.out, "w") as f:
        json.dump({
            "worker_id": sess.worker_id,
            "losses": losses,
            "reshapes": sess.reshapes,
            "generation": generation,
        }, f)


def _child_fixed(args):
    """Fresh-restore reference: restore ``--serial`` from a COPY of the
    checkpoint dir at a FIXED world size (no coordinator), run
    ``--steps`` more steps — the trajectory the post-reshape fleet must
    have matched bit-for-bit."""
    import numpy as np

    from paddle_tpu.elastic.reshard import ShardedCheckpointManager
    from paddle_tpu.elastic.worker import session_executor
    from paddle_tpu.resilience.session import TrainSession

    holder = {}
    pe, main = _make_build_fn(holder)(args.world, 0)
    exe = session_executor(pe)
    mgr = ShardedCheckpointManager(
        args.ckpt_dir, plan=pe.sharding_plan(), executor=exe,
        main_program=main)
    manifest = mgr.restore(serial=args.serial)
    assert manifest is not None, (
        "reference restore failed for serial %s" % args.serial)
    sess = TrainSession(exe, args.ckpt_dir, main_program=main,
                        manager=mgr, auto_resume=False,
                        interval_steps=0, interval_secs=0)
    sess.step = int(manifest["step"])
    losses = []
    for _ in range(args.steps):
        out = sess.run(feed=_feed_for(sess.step),
                       fetch_list=[holder["loss"]])
        losses.append([sess.step - 1,
                       float(np.asarray(out[0]).reshape(-1)[0])])
    sess.close(save=False)
    with open(args.out, "w") as f:
        json.dump({"losses": losses}, f)


# ---------------------------------------------------------------------------
# parent: the legs
# ---------------------------------------------------------------------------


def _env(**extra):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        FLAGS_checkpoint_max_to_keep="100",
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    env.update({k: str(v) for k, v in extra.items()})
    return env


def _spawn(mode, out, extra_args, env):
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "child",
         "--mode", mode, "--out", out] + extra_args, env=env)


def _wait_member_step(co, worker_id, step, timeout=60):
    deadline = time.time() + timeout
    while time.time() < deadline:
        m = co.status()["members"].get(worker_id)
        if m and (m["step"] or 0) >= step:
            return
        time.sleep(0.1)
    raise AssertionError("worker %s never reached step %d: %s"
                         % (worker_id, step, co.status()))


def _wait_world(co, world, timeout=30):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if co.status()["world"] == world:
            return time.time()
        time.sleep(0.05)
    raise AssertionError("fleet never reached world=%d: %s"
                         % (world, co.status()))


def _segment(losses, lo, hi):
    """losses: [[step, value]...] -> values for lo <= step < hi."""
    return [v for s, v in losses if lo <= s < (hi if hi is not None
                                               else 1 << 60)]


def _run_fixed_reference(tmp, tag, ckpt_src, world, serial, steps):
    copy = os.path.join(tmp, "ref_ckpt_%s" % tag)
    shutil.copytree(ckpt_src, copy)
    out = os.path.join(tmp, "ref_%s.json" % tag)
    proc = _spawn("fixed", out,
                  ["--ckpt-dir", copy, "--world", str(world),
                   "--serial", str(serial), "--steps", str(steps),
                   "--sleep", "0"], _env())
    assert proc.wait(timeout=300) == 0, "fixed reference %s failed" % tag
    with open(out) as f:
        return [v for _s, v in json.load(f)["losses"]]


def _churn_leg(tmp):
    from paddle_tpu.elastic.coordinator import FleetCoordinator
    from paddle_tpu.observability.metrics_registry import REGISTRY

    co = FleetCoordinator(lease_s=LEASE_S, min_workers=2)
    host, port = co.serve()
    addr = "%s:%d" % (host, port)
    ckpt = os.path.join(tmp, "ckpt")
    prom = os.path.join(tmp, "w0.prom")

    out0 = os.path.join(tmp, "w0.json")
    out1 = os.path.join(tmp, "w1.json")
    outr = os.path.join(tmp, "w1b.json")
    common = ["--coordinator", addr, "--ckpt-dir", ckpt,
              "--steps", str(STEPS), "--sleep", str(STEP_SLEEP)]
    w0 = _spawn("elastic", out0, common + ["--worker-id", "w0"],
                _env(FLAGS_metrics_path=prom))
    w1 = _spawn("elastic", out1, common + ["--worker-id", "w1"], _env())

    # both admitted, worker 1 demonstrably training -> SIGKILL it
    _wait_member_step(co, "w1", 4, timeout=120)
    os.kill(w1.pid, signal.SIGKILL)
    t_kill = time.time()
    assert w1.wait(timeout=30) == -signal.SIGKILL

    # eviction within the lease timeout (+ watcher period slack)
    t_evict = _wait_world(co, 1, timeout=LEASE_S * 4)
    detect_s = t_evict - t_kill
    assert detect_s <= LEASE_S + 1.0, (
        "eviction took %.1fs (lease %.1fs)" % (detect_s, LEASE_S))
    gen_evict = co.status()["generation"]

    # the survivor reshards to world 1 and KEEPS TRAINING
    surv_step = (co.status()["members"].get("w0") or {}).get("step") or 0
    _wait_member_step(co, "w0", surv_step + 3, timeout=120)

    # re-admission: a fresh worker joins at the next generation
    w1b = _spawn("elastic", outr, common + ["--worker-id", "w1b"], _env())
    _wait_world(co, 2, timeout=60)
    assert co.status()["generation"] > gen_evict

    assert w0.wait(timeout=300) == 0, "survivor failed"
    assert w1b.wait(timeout=300) == 0, "re-admitted worker failed"

    with open(out0) as f:
        r0 = json.load(f)
    with open(outr) as f:
        r1b = json.load(f)

    # reshape ledger: cold start at 2, eviction to 1, rejoin to 2
    worlds = [r["world"] for r in r0["reshapes"]]
    assert worlds == [2, 1, 2], r0["reshapes"]
    assert [r["generation"] for r in r0["reshapes"]] == sorted(
        r["generation"] for r in r0["reshapes"])
    evict_re, rejoin_re = r0["reshapes"][1], r0["reshapes"][2]
    assert evict_re["serial"] == evict_re["step"]

    # --- bit-tracked loss: world-1 segment vs a fresh restore at world 1
    seg1 = _segment(r0["losses"], evict_re["step"], rejoin_re["step"])
    assert len(seg1) >= 2, "world-1 segment too short: %s" % seg1
    ref1 = _run_fixed_reference(tmp, "w1", ckpt, 1, evict_re["serial"],
                                len(seg1))
    assert seg1 == ref1, (
        "world-1 segment diverged from fresh restore:\nfleet: %s\n"
        "fresh: %s" % (seg1, ref1))

    # --- world-2 segment vs fresh restore at world 2 AND vs the rejoiner
    seg2 = _segment(r0["losses"], rejoin_re["step"], None)
    assert len(seg2) >= 2, "world-2 segment too short"
    ref2 = _run_fixed_reference(tmp, "w2", ckpt, 2, rejoin_re["serial"],
                                len(seg2))
    assert seg2 == ref2, (
        "world-2 segment diverged from fresh restore:\nfleet: %s\n"
        "fresh: %s" % (seg2, ref2))
    seg2b = _segment(r1b["losses"], rejoin_re["step"], None)
    n = min(len(seg2), len(seg2b))
    assert n >= 2 and seg2[:n] == seg2b[:n], (
        "survivor and re-admitted worker diverged:\nw0:  %s\nw1b: %s"
        % (seg2[:n], seg2b[:n]))
    assert r1b["reshapes"][0]["serial"] == rejoin_re["serial"], (
        "rejoiner restored a different serial than the chief published")

    # --- fleet metrics: worker scrape + coordinator-side counters
    with open(prom) as f:
        scrape = f.read()
    gen_lines = [line for line in scrape.splitlines()
                 if line.startswith("paddle_tpu_fleet_generation")]
    assert gen_lines and float(gen_lines[0].rsplit(None, 1)[-1]) >= 4, (
        "worker scrape must carry the generation gauge: %r" % gen_lines)
    assert any(line.startswith("paddle_tpu_fleet_size")
               for line in scrape.splitlines())
    rs = [line for line in scrape.splitlines()
          if line.startswith("paddle_tpu_reshard_seconds_count")]
    assert rs and float(rs[0].rsplit(None, 1)[-1]) >= 3, (
        "reshard timings missing from the worker scrape: %r" % rs)
    parent_scrape = REGISTRY.to_prometheus()
    ev = [line for line in parent_scrape.splitlines()
          if line.startswith("paddle_tpu_fleet_evictions_total")]
    assert ev and float(ev[0].rsplit(None, 1)[-1]) >= 1

    # --- the final checkpoint verifies offline and names its mesh
    serials = sorted(int(d.split("_")[1]) for d in os.listdir(ckpt)
                     if d.startswith("checkpoint_")
                     and d.split("_")[1].isdigit())
    final_dir = os.path.join(ckpt, "checkpoint_%d" % serials[-1])
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ckpt_inspect.py"),
         final_dir, "--verify"], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "mesh:" in proc.stdout, proc.stdout

    co.close()
    print("elastic churn leg OK: evicted in %.1fs (lease %.1fs), "
          "reshapes %s, world-1 + world-2 segments bit-identical to "
          "fresh restores (%d + %d steps), rejoiner matched serial %d"
          % (detect_s, LEASE_S, worlds, len(seg1), len(seg2),
             rejoin_re["serial"]))


def _restart_leg(tmp):
    from paddle_tpu.elastic.coordinator import FleetCoordinator

    snap = os.path.join(tmp, "fleet.json")
    co = FleetCoordinator(lease_s=LEASE_S, min_workers=1,
                          snapshot_path=snap, snapshot_interval_s=0.0)
    host, port = co.serve()
    addr = "%s:%d" % (host, port)
    out = os.path.join(tmp, "cw.json")
    prom = os.path.join(tmp, "cw.prom")
    w = _spawn("elastic", out,
               ["--coordinator", addr, "--ckpt-dir",
                os.path.join(tmp, "ckpt_restart"), "--steps", "30",
                "--sleep", "0.08", "--worker-id", "cw"],
               _env(FLAGS_metrics_path=prom))
    _wait_member_step(co, "cw", 5, timeout=120)
    gen_before = co.status()["generation"]

    # kill -restart the coordinator: workers must ride it out
    co.close()
    time.sleep(0.6)  # downtime window: heartbeats fail and retry
    co2 = FleetCoordinator(lease_s=LEASE_S, min_workers=1,
                           snapshot_path=snap, snapshot_interval_s=0.0)
    co2.serve(host=host, port=port)
    assert co2.status()["generation"] == gen_before
    assert "cw" in co2.status()["members"]

    assert w.wait(timeout=300) == 0, "worker did not survive the restart"
    with open(out) as f:
        res = json.load(f)
    # ONE build (cold start), zero reshapes: recovery at the same
    # generation must not look like churn
    assert len(res["reshapes"]) == 1, res["reshapes"]
    assert res["generation"] == gen_before
    assert len(res["losses"]) == 30
    with open(prom) as f:
        scrape = f.read()
    retr = [line for line in scrape.splitlines()
            if line.startswith("paddle_tpu_retries_total")
            and "FleetClient" in line]
    assert retr and sum(float(line.rsplit(None, 1)[-1])
                        for line in retr) >= 1, (
        "the restart window must show classified FleetClient retries: %r"
        % retr)
    co2.close()
    print("elastic restart leg OK: coordinator restarted from snapshot "
          "at generation %d, %d retries absorbed, zero spurious reshapes"
          % (gen_before, int(sum(float(line.rsplit(None, 1)[-1])
                                 for line in retr))))


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "child":
        p = argparse.ArgumentParser()
        p.add_argument("cmd")
        p.add_argument("--mode", choices=["elastic", "fixed"],
                       required=True)
        p.add_argument("--coordinator")
        p.add_argument("--ckpt-dir", required=True)
        p.add_argument("--steps", type=int, required=True)
        p.add_argument("--out", required=True)
        p.add_argument("--worker-id")
        p.add_argument("--world", type=int, default=1)
        p.add_argument("--serial", type=int, default=None)
        p.add_argument("--sleep", type=float, default=0.05)
        args = p.parse_args()
        if args.mode == "elastic":
            _child_elastic(args)
        else:
            _child_fixed(args)
        return
    import tempfile

    with tempfile.TemporaryDirectory(prefix="elastic_") as tmp:
        _churn_leg(tmp)
        _restart_leg(tmp)
    print("elastic smoke OK")


if __name__ == "__main__":
    main()
