"""Router-fleet smoke: prove the router tier survives a dead frontend.

    python tools/router_smoke.py $DIR    # writes $DIR/router.json

One leg, asserted hard (the CI ``route`` stage):

* **SIGKILL-a-frontend failover.** An *oracle* subprocess decodes the
  whole request set uninterrupted (and warms the one shared
  ``FLAGS_exec_cache_dir``). Then the parent runs a ``ServingRouter``
  and spawns TWO frontend subprocesses — each builds the SAME seeded
  model + paged ``SlotDecodeSession`` (greedy sampler: tokens are
  slot-assignment-independent, so concurrent routing stays
  oracle-comparable; SAMPLED bit-exactness across migration is pinned
  by ``tests/test_router.py``), arms a periodic
  ``DecodeSnapshotManager``, and registers as a ``RouterMember``.
  Phase 1 drives a warm set through the router including duplicate
  ``(src, prefix)`` pairs: prefix-affinity consistent hashing must pin
  each pair to ONE member so the second request HITS the prefix cache
  (``prefix_hit_rate`` surviving scale-out is the point of affinity
  routing). Phase 2 starts concurrent token streams and SIGKILLs one
  frontend mid-stream (asserted: death by SIGKILL with live slots on
  board). Every stream must still complete through the router —
  severed relays fail over, the victim's banked snapshot restores on
  the survivor, and the spliced streams are **bit-identical** to the
  oracle with **zero** lost or duplicated tokens. The survivor ends
  with **0 fresh compiles** (failover restore included — every
  executable from the warm cache).

The capture lands in ``$DIR/router.json`` and the stage gates it via
``tools/perf_diff.py --budgets benchmark/budgets.json --models
router`` (``fresh_compiles`` max 0 deterministic, ``lost_streams``
max 0 deterministic, ``migration_seconds`` banded).
"""

import json
import os
import re
import signal
import subprocess
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

VOCAB, SEQ, D, S = 40, 16, 32, 4
CFG = dict(src_vocab_size=VOCAB, trg_vocab_size=VOCAB, n_layer=1,
           n_head=2, d_inner=64)
PREFIX_A = [5, 9, 7, 11, 6, 8]      # > page_size: a cacheable full page
PREFIX_B = [4, 6, 10, 12, 5]
# per-dispatch chaos slowdown inside the frontends: widens the
# mid-stream window so the SIGKILL provably lands on live slots
CHILD_CHAOS = "seed=5;slow@site=serve.dispatch,p=1.0,secs=0.1"


def _requests():
    """The one deterministic request set every process derives.
    Returns (warm_wave_a, warm_wave_b, streams) as lists of
    ``(oracle_index, src_row, src_len, prefix)``."""
    rng = np.random.RandomState(23)
    src = rng.randint(3, VOCAB, (10, SEQ)).astype("int64")
    warm_a = [
        (0, src[0], SEQ, PREFIX_A),
        (1, src[1], 5, None),
        (2, src[2], SEQ - 1, None),
        (3, src[3], SEQ, PREFIX_B),
    ]
    # wave B re-sends two (src, prefix) pairs VERBATIM: affinity must
    # route each to the member that already cached its prefix pages
    warm_b = [
        (4, src[0], SEQ, PREFIX_A),
        (5, src[3], SEQ, PREFIX_B),
    ]
    streams = [(6 + i, src[4 + i], SEQ, None) for i in range(6)]
    return warm_a, warm_b, streams


def _build_session():
    """The seeded model + session every child builds identically —
    GREEDY sampler (``sampler=None``): greedy tokens depend only on
    the model and the request, never on which slot/member a
    concurrently-routed request landed in."""
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer
    from paddle_tpu.serving.generation import SlotDecodeSession

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 13
    startup.random_seed = 13
    with fluid.program_guard(main, startup):
        transformer.build(dropout=0.0, label_smooth_eps=0.0,
                          max_length=SEQ, d_model=D, **CFG)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    return SlotDecodeSession(
        exe, num_slots=S, max_length=SEQ, d_model=D, paged=True,
        page_size=4, steps=2, num_groups=2, prefix_cache_pages=8,
        **CFG)


def child_oracle(workdir):
    sess = _build_session()
    warm_a, warm_b, streams = _requests()
    specs = warm_a + warm_b + streams
    rids = {}
    for idx, src, length, prefix in specs:
        rids[sess.enqueue(src, length, prefix_tokens=prefix)] = idx
    done = {}
    while len(done) < len(specs):
        done.update(sess.pump())
    with open(os.path.join(workdir, "oracle.json"), "w") as f:
        json.dump({str(rids[r]): [int(t) for t in row]
                   for r, row in done.items()}, f)
    print("oracle: decoded %d requests" % len(specs))
    return 0


def child_frontend(workdir, name):
    from paddle_tpu.serving.frontend import ServingFrontend
    from paddle_tpu.serving.router import RouterMember
    from paddle_tpu.serving.snapshot import DecodeSnapshotManager

    sess = _build_session()
    mgr = DecodeSnapshotManager(
        sess, os.path.join(workdir, "snap_%s" % name), interval_steps=2)
    fe = ServingFrontend(session=sess, snapshot_manager=mgr)
    with open(os.path.join(workdir, "router.addr")) as f:
        host, port = f.read().strip().rsplit(":", 1)
    member = RouterMember(  # noqa: F841 - keeps the lease beating
        fe, (host, int(port)), worker_id="fe-%s" % name)
    ready = os.path.join(workdir, "%s.ready" % name)
    with open(ready + ".tmp", "w") as f:
        f.write("%s:%d" % (fe.address[0], fe.address[1]))
    os.rename(ready + ".tmp", ready)
    print("frontend %s: serving on %s:%d" % (name, fe.address[0],
                                             fe.address[1]))
    while True:  # parked until the parent SIGKILLs / SIGTERMs us
        time.sleep(0.2)


def _spawn_child(args, workdir, extra_env=None, wait=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(extra_env or {})
    cmd = [sys.executable, os.path.abspath(__file__), "--child"] + args
    cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if wait:
        return subprocess.run(cmd, env=env, timeout=600, cwd=cwd)
    return subprocess.Popen(cmd, env=env, cwd=cwd)


def _wait_file(path, timeout_s=120.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if os.path.exists(path):
            with open(path) as f:
                return f.read().strip()
        time.sleep(0.1)
    raise AssertionError("timed out waiting for %s" % path)


def _addr(text):
    host, port = text.rsplit(":", 1)
    return host, int(port)


def _scrape_fresh_compiles(text):
    m = re.search(r"^paddle_tpu_fresh_compiles_total (\d+)", text,
                  re.MULTILINE)
    return int(m.group(1)) if m else 0


def leg_fleet_failover(workdir):
    from paddle_tpu.serving.client import ServingClient
    from paddle_tpu.serving.router import ServingRouter

    cache = os.path.join(workdir, "cache")
    # an empty compile cache shared by every child, said explicitly
    env = {"FLAGS_exec_cache_dir": cache,
           "JAX_COMPILATION_CACHE_DIR": os.path.join(cache, "xla")}
    assert _spawn_child(["oracle", workdir], workdir, env).returncode == 0
    with open(os.path.join(workdir, "oracle.json")) as f:
        oracle = json.load(f)

    router = ServingRouter(lease_s=1.0, health_poll_s=0.25)
    procs = []
    try:
        with open(os.path.join(workdir, "router.addr"), "w") as f:
            f.write("%s:%d" % (router.address[0], router.port))
        child_env = dict(env, FLAGS_chaos_spec=CHILD_CHAOS)
        procs = [
            _spawn_child(["frontend", workdir, n], workdir, child_env,
                         wait=False)
            for n in ("a", "b")]
        fe_addr = {n: _addr(_wait_file(
            os.path.join(workdir, "%s.ready" % n))) for n in ("a", "b")}
        cl = ServingClient(router.address)
        deadline = time.monotonic() + 60.0
        while len(cl.stats()["frontends"]) < 2:
            assert time.monotonic() < deadline, "members never registered"
            time.sleep(0.1)

        # -- phase 1: warm set + prefix-affinity pinning ------------------
        warm_a, warm_b, streams = _requests()
        t0 = time.perf_counter()
        for wave in (warm_a, warm_b):
            rows, threads = {}, []
            for idx, src, length, prefix in wave:
                def run(idx=idx, src=src, length=length, prefix=prefix):
                    c = ServingClient(router.address)
                    try:
                        rows[idx] = c.generate_full(
                            src, length, prefix_tokens=prefix)[0]
                    finally:
                        c.close()
                threads.append(threading.Thread(target=run))
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120.0)
            for idx, _, _, _ in wave:
                assert idx in rows, "warm request %d never completed" % idx
                assert [int(t) for t in rows[idx]] == oracle[str(idx)], (
                    "warm request %d diverges from the oracle" % idx)
        warm_s = time.perf_counter() - t0
        lookups = hits = 0
        for n in ("a", "b"):
            c = ServingClient(fe_addr[n])
            try:
                p = c.stats()["decode"]["prefix"]
            finally:
                c.close()
            lookups += int(p["lookups"])
            hits += int(p["hits"])
        assert hits >= len(warm_b), (
            "affinity failed to pin the duplicate (src, prefix) pairs: "
            "%d hits across the fleet (lookups=%d), expected >= %d"
            % (hits, lookups, len(warm_b)))
        hit_rate = hits / float(lookups) if lookups else 0.0
        print("router: phase 1 OK — %d warm requests in %.2fs, prefix "
              "hits %d/%d (hit_rate %.2f) across 2 members"
              % (len(warm_a) + len(warm_b), warm_s, hits, lookups,
                 hit_rate))

        # -- phase 2: concurrent streams, SIGKILL one frontend ------------
        results, errors, first_tok = {}, {}, {}
        threads = []
        for idx, src, length, prefix in streams:
            first_tok[idx] = threading.Event()

            def run(idx=idx, src=src, length=length):
                c = ServingClient(router.address)

                def saw(ev):
                    if ev.get("event") == "tokens":
                        first_tok[idx].set()

                try:
                    results[idx] = c.generate_full(src, length,
                                                   on_event=saw)[0]
                except Exception as exc:  # noqa: BLE001 - asserted below
                    errors[idx] = exc
                finally:
                    c.close()
            threads.append(threading.Thread(target=run))
        for t in threads:
            t.start()
        for idx in first_tok:
            assert first_tok[idx].wait(timeout=120.0), (
                "stream %d produced no tokens" % idx)
        stats_cl = ServingClient(fe_addr["a"])
        try:
            live_on_victim = stats_cl.stats()["decode"]["active_slots"]
        finally:
            stats_cl.close()
        assert live_on_victim >= 1, (
            "victim had no live slots at the kill point — the failover "
            "would not exercise live-stream migration")
        procs[0].kill()
        assert procs[0].wait(timeout=30.0) == -signal.SIGKILL
        print("router: SIGKILLed frontend a with %d live slot(s) "
              "mid-stream" % live_on_victim)
        for t in threads:
            t.join(timeout=180.0)
            assert not t.is_alive(), "a stream never completed"
        assert not errors, (
            "streams failed after the kill: %s\n(router stats: %r)"
            % ({i: repr(e) for i, e in errors.items()}, router.stats()))
        for idx, _, _, _ in streams:
            assert idx in results, "stream %d never completed" % idx
            assert [int(t) for t in results[idx]] == oracle[str(idx)], (
                "stream %d diverges from the oracle after failover\n"
                "  oracle: %r\n  got:    %r"
                % (idx, oracle[str(idx)], [int(t) for t in results[idx]]))

        rstats = router.stats()
        assert rstats["failovers"] >= 1, "no failover ran"
        assert rstats["migrations"] >= 1, "no migration landed"
        assert rstats["lost_streams"] == 0, rstats
        assert rstats["migration_seconds"], "no migration was timed"
        migration_s = float(rstats["migration_seconds"][0])

        # the survivor — failover restore included — compiled NOTHING:
        # every executable came from the oracle-warmed persistent cache
        surv = ServingClient(fe_addr["b"])
        try:
            fresh = _scrape_fresh_compiles(surv.metrics())
            conserved = surv.stats()["decode"]["pool_conserved"]
        finally:
            surv.close()
        assert fresh == 0, (
            "survivor paid %d fresh compiles after the failover restore"
            % fresh)
        assert conserved, "survivor page pool leaked after migration"
        cl.close()
        print("router: failover leg OK — %d/%d streams bit-identical "
              "after SIGKILL (migration %.2fs), 0 lost, 0 fresh "
              "compiles on the survivor"
              % (len(results), len(streams), migration_s))
        return {"fresh_compiles": fresh, "migration_seconds": migration_s,
                "lost_streams": int(rstats["lost_streams"]),
                "prefix_hit_rate": hit_rate}
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
                try:
                    p.wait(timeout=15.0)
                except subprocess.TimeoutExpired:
                    p.kill()
        router.close()


def main():
    if len(sys.argv) >= 2 and sys.argv[1] == "--child":
        if sys.argv[2] == "oracle":
            return child_oracle(sys.argv[3])
        return child_frontend(sys.argv[3], sys.argv[4])
    if len(sys.argv) != 2:
        sys.exit("usage: router_smoke.py OUTPUT_DIR")
    workdir = sys.argv[1]
    os.makedirs(workdir, exist_ok=True)
    numbers = leg_fleet_failover(workdir)
    capture = {"models": {"router": numbers}}
    path = os.path.join(workdir, "router.json")
    with open(path, "w") as f:
        json.dump(capture, f)
    print("router: capture -> %s (%s)" % (
        path, ", ".join("%s=%s" % (k, v)
                        for k, v in sorted(numbers.items()))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
