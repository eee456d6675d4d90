"""Serving-chaos smoke: prove the decode fleet survives the machine.

    python tools/serve_chaos_smoke.py $DIR    # writes $DIR/servechaos.json

Two legs, both asserted hard (the CI ``servechaos`` stage):

* **SIGKILL-mid-decode restore.** Three subprocesses share one
  ``FLAGS_exec_cache_dir`` and build the SAME seeded model + paged
  ``SlotDecodeSession``. The *oracle* decodes a 10-request backlog
  uninterrupted (and warms the executable cache). The *victim* runs
  with a ``DecodeSnapshotManager`` (periodic async snapshots) under
  ``kill@site=serve.dispatch,step=N`` — SIGKILLed entering a seeded
  step dispatch, no cleanup, the real preemption. The *restored*
  process constructs a fresh session, restores the newest VERIFIED
  snapshot (mid-write victims quarantine/skip), pumps the remaining
  backlog to completion and must emit token streams **bit-identical**
  to the oracle's — the ``(seed, slot, position)`` PRNG contract — with
  **0 fresh compiles** scraped from its metrics registry (every
  executable, init through the multi-step scan, comes from the warm
  persistent cache). It then times one synchronous snapshot
  (``snapshot_seconds``, budget-gated).
* **Overload brownout/recovery.** An in-process ``BatchingServer``
  with the degradation machine armed is flooded past its shed
  threshold: every refusal must be a TYPED retriable ``DegradedError``
  (retry-after hint), every admitted future must complete (no wedged
  requests), and after the drain the health gauge must read healthy
  again with the brownout->shed->...->healthy transitions counted in
  the registry.

The capture lands in ``$DIR/servechaos.json`` and the stage gates it
via ``tools/perf_diff.py --budgets benchmark/budgets.json --models
servechaos`` (``fresh_compiles`` max 0 deterministic,
``snapshot_seconds`` banded).
"""

import json
import os
import re
import signal
import subprocess
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

VOCAB, SEQ, D, S = 40, 16, 32, 4
N_REQUESTS = 10
KILL_STEP = 6
CFG = dict(src_vocab_size=VOCAB, trg_vocab_size=VOCAB, n_layer=1,
           n_head=2, d_inner=64)


def _scrape_fresh_compiles():
    from paddle_tpu.observability import REGISTRY

    text = REGISTRY.to_prometheus()
    m = re.search(r"^paddle_tpu_fresh_compiles_total (\d+)", text,
                  re.MULTILINE)
    return int(m.group(1)) if m else 0


def _build_session():
    """The one seeded model + session every child builds identically
    (cross-process determinism: BOTH programs carry the seed, so the
    startup init and the decode sampler replay bit-for-bit)."""
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer
    from paddle_tpu.serving.generation import Sampler, SlotDecodeSession

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 13
    startup.random_seed = 13
    with fluid.program_guard(main, startup):
        transformer.build(dropout=0.0, label_smooth_eps=0.0,
                          max_length=SEQ, d_model=D, **CFG)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    sess = SlotDecodeSession(
        exe, num_slots=S, max_length=SEQ, d_model=D, paged=True,
        page_size=4, steps=2, num_groups=2, prefix_cache_pages=8,
        sampler=Sampler(strategy="top_k", top_k=4, temperature=0.9,
                        seed=3), **CFG)
    return sess


def _requests():
    rng = np.random.RandomState(17)
    src = rng.randint(3, VOCAB, (N_REQUESTS, SEQ)).astype("int64")
    lens = [SEQ, 2, SEQ - 1, 5, SEQ, 3, SEQ - 2, SEQ, 4, SEQ]
    return src, lens


def child_oracle(workdir):
    sess = _build_session()
    src, lens = _requests()
    rids = [sess.enqueue(src[i], lens[i]) for i in range(N_REQUESTS)]
    done = {}
    while len(done) < N_REQUESTS:
        done.update(sess.pump())
    with open(os.path.join(workdir, "oracle.json"), "w") as f:
        json.dump({str(r): [int(t) for t in done[r]] for r in rids}, f)
    print("oracle: decoded %d requests" % N_REQUESTS)
    return 0


def child_victim(workdir):
    from paddle_tpu.serving.snapshot import DecodeSnapshotManager

    sess = _build_session()
    mgr = DecodeSnapshotManager(  # noqa: F841 - armed via the session hook
        sess, os.path.join(workdir, "snap"), interval_steps=2)
    src, lens = _requests()
    for i in range(N_REQUESTS):
        sess.enqueue(src[i], lens[i])
    while sess._pending or sess._live:
        sess.pump()  # chaos SIGKILLs entering step dispatch KILL_STEP
    print("victim: drained WITHOUT dying — chaos never fired",
          file=sys.stderr)
    return 1


def child_restored(workdir):
    from paddle_tpu.core import exec_cache
    from paddle_tpu.serving.snapshot import DecodeSnapshotManager

    sess = _build_session()
    mgr = DecodeSnapshotManager(sess, os.path.join(workdir, "snap"))
    manifest = mgr.restore()
    assert manifest is not None, "no restorable snapshot after SIGKILL"
    done = {}
    while sess._pending or sess._live:
        done.update(sess.pump())
    # requests that FINISHED before the snapshot ride it in the result
    # bank — the restored process serves those too, so every stream of
    # the whole backlog is re-emittable after the kill
    for rid in range(N_REQUESTS):
        if rid not in done:
            tokens = sess.take_result(rid)
            if tokens is not None:
                done[rid] = tokens
    # THE acceptance numbers: the whole process — startup init, session
    # init, restore scatter, the continuation's admits and multi-step
    # scans — compiled NOTHING; every executable was an AOT cache hit
    fresh = _scrape_fresh_compiles()
    stats = exec_cache.stats()
    assert fresh == 0, (
        "restored process paid %d fresh compiles (exec_cache: %r)"
        % (fresh, stats))
    t0 = time.perf_counter()
    mgr.save()
    snap_s = time.perf_counter() - t0
    with open(os.path.join(workdir, "restored.json"), "w") as f:
        json.dump({
            "restored_serial": mgr.restored_serial,
            "fresh_compiles": fresh,
            "snapshot_seconds": snap_s,
            "tokens": {str(r): [int(t) for t in v]
                       for r, v in done.items()},
        }, f)
    print("restored: serial %s, %d requests completed post-restore, "
          "0 fresh compiles, snapshot %.3fs"
          % (mgr.restored_serial, len(done), snap_s))
    return 0


def _spawn(mode, workdir, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", mode,
         workdir],
        env=env, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def leg_sigkill_restore(workdir):
    cache = os.path.join(workdir, "cache")
    # an empty compile cache shared by every child, said explicitly
    env = {"FLAGS_exec_cache_dir": cache,
           "JAX_COMPILATION_CACHE_DIR": os.path.join(cache, "xla")}
    assert _spawn("oracle", workdir, env).returncode == 0
    victim = _spawn("victim", workdir, dict(
        env, FLAGS_chaos_spec="seed=5;kill@site=serve.dispatch,step=%d"
        % KILL_STEP))
    assert victim.returncode == -signal.SIGKILL, (
        "victim exited %r, expected death by SIGKILL" % victim.returncode)
    snap_root = os.path.join(workdir, "snap")
    assert os.path.isdir(snap_root) and any(
        d.startswith("checkpoint_") for d in os.listdir(snap_root)), \
        "victim left no snapshot behind"
    assert _spawn("restored", workdir, env).returncode == 0

    with open(os.path.join(workdir, "oracle.json")) as f:
        oracle = json.load(f)
    with open(os.path.join(workdir, "restored.json")) as f:
        restored = json.load(f)
    toks = restored["tokens"]
    assert toks, "restored process completed nothing"
    for rid, stream in toks.items():
        assert stream == oracle[rid], (
            "request %s: restored tokens diverge from the oracle\n"
            "  oracle:   %r\n  restored: %r"
            % (rid, oracle[rid], stream))
    # full coverage: live/pending work re-decodes, and requests that
    # finished BEFORE the snapshot ride its result bank — the restored
    # process re-emits the ENTIRE backlog bit-identical
    missing = [r for r in range(N_REQUESTS) if str(r) not in toks]
    assert not missing, "streams missing after restore: %s" % missing
    print("servechaos: SIGKILL leg OK — %d/%d token streams re-emitted "
          "bit-identical after restore (serial %s), 0 fresh compiles"
          % (len(toks), N_REQUESTS, restored["restored_serial"]))
    return restored


def leg_overload_brownout(workdir):
    from paddle_tpu.inference import NativeConfig, create_paddle_predictor
    from paddle_tpu.observability import REGISTRY
    from paddle_tpu.serving import loadgen
    from paddle_tpu.serving.degradation import DegradedError
    from paddle_tpu.serving.server import BatchingServer

    model_dir = os.path.join(workdir, "demo_model")
    loadgen.build_demo_model(model_dir, train_steps=5)
    predictor = create_paddle_predictor(
        NativeConfig(model_dir=model_dir, use_tpu=False))
    server = BatchingServer(
        predictor, max_batch=8, workers=1, max_queue_depth=8,
        batch_linger_s=0.05,
        degradation=dict(brownout_at=0.5, shed_at=0.75,
                         recover_at=0.25, retry_after_s=0.1))
    futures, rejects = [], 0
    with server:
        for req in loadgen.demo_requests(24):
            try:
                futures.append(server.submit(req))
            except Exception as exc:  # noqa: BLE001 - asserted typed below
                assert isinstance(exc, DegradedError), (
                    "overload produced a non-typed reject: %r" % exc)
                assert exc.retry_after_s > 0
                rejects += 1
        assert rejects > 0, "the flood never tripped shed"
        for fut in futures:  # no wedged requests: everything resolves
            fut.result(timeout=60.0)
        for req in loadgen.demo_requests(4):  # post-drain: serving again
            server.run(req)
        stats = server.stats()
    assert stats["health"] == "healthy", stats["health"]
    assert stats["degraded"] == rejects
    text = REGISTRY.to_prometheus()
    assert 'paddle_tpu_serving_health{component="server"} 0' in text
    transitions = sum(
        int(float(line.split()[-1])) for line in text.splitlines()
        if line.startswith("paddle_tpu_serving_health_transitions_total"))
    assert transitions >= 2, "no brownout round trip in the scrape"
    print("servechaos: overload leg OK — %d typed retriable rejects, "
          "%d admitted futures all resolved, %d health transitions, "
          "back to healthy" % (rejects, len(futures), transitions))


def main():
    if len(sys.argv) >= 2 and sys.argv[1] == "--child":
        return {"oracle": child_oracle, "victim": child_victim,
                "restored": child_restored}[sys.argv[2]](sys.argv[3])
    if len(sys.argv) != 2:
        sys.exit("usage: serve_chaos_smoke.py OUTPUT_DIR")
    workdir = sys.argv[1]
    restored = leg_sigkill_restore(workdir)
    leg_overload_brownout(workdir)
    capture = {"models": {"servechaos": {
        "fresh_compiles": restored["fresh_compiles"],
        "snapshot_seconds": restored["snapshot_seconds"],
    }}}
    path = os.path.join(workdir, "servechaos.json")
    with open(path, "w") as f:
        json.dump(capture, f)
    print("servechaos: capture -> %s (fresh_compiles=%d, "
          "snapshot_seconds=%.3f)" % (
              path, restored["fresh_compiles"],
              restored["snapshot_seconds"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
