"""Serving resilience (PR 13): preemption-safe decode snapshots,
graceful degradation, chaos-hardened serving dispatch.

* a mid-flight ``SlotDecodeSession`` (live fork groups, shared prefix
  pages, a pending request backlog) snapshots atomically and restores
  into a FRESH session whose remaining tokens are BIT-identical to the
  uninterrupted run's — the (seed, slot, position) PRNG contract;
* corrupt snapshots quarantine and fall back; geometry drift raises a
  typed ``SnapshotMismatchError`` (operator error, not corruption);
* ``tools/ckpt_inspect.py`` prints the decode dialect and ``--verify``
  re-checks page conservation + refcount accounting offline (exit 2);
* the healthy -> brownout -> shed machine sheds load with typed
  retriable ``DegradedError``\\ s (retry-after hints) in BOTH the
  batching server (queue depth) and the decode session (page/slot
  occupancy: brownout evicts the prefix cache and refuses forks, shed
  refuses admissions while in-flight work drains) — and recovers;
* a chaos fault at ``serve.admit`` rolls the whole group back and,
  under classified retry, re-admits bit-identically; a fault at
  ``snapshot.write`` fails the save without touching the session;
* a Pallas ``paged_attention`` failure trips the once-per-process
  reference fallback (counter + flag) instead of killing the request;
* SIGTERM mid-decode finishes the in-flight dispatch, banks a final
  snapshot and dies BY the signal (subprocess leg);
* SIGKILL entering a seeded step dispatch (``kill@site=serve.dispatch``):
  another process restores the newest verified periodic snapshot and
  re-emits the whole backlog bit-identical (subprocess leg).
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
from paddle_tpu.core import exec_cache
from paddle_tpu.executor import global_scope
from paddle_tpu.observability import REGISTRY
from paddle_tpu.resilience import chaos
from paddle_tpu.serving.degradation import (
    BROWNOUT,
    HEALTHY,
    SHED,
    DegradedError,
    HealthMonitor,
)
from paddle_tpu.serving.generation import Sampler, SlotDecodeSession
from paddle_tpu.serving.snapshot import (
    DecodeSnapshotManager,
    SnapshotMismatchError,
)

VOCAB, SEQ, D, S = 24, 8, 32, 4
CFG = dict(src_vocab_size=VOCAB, trg_vocab_size=VOCAB, n_layer=2,
           n_head=2, d_inner=64)


@pytest.fixture(scope="module")
def trained(request):
    """One tiny trained 2-layer transformer (2 layers so cross/self
    pools past layer 0 are in every snapshot) shared by the module."""
    from paddle_tpu.models import transformer

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 41
    startup.random_seed = 41
    scope = global_scope()
    with fluid.program_guard(main, startup):
        transformer.build(dropout=0.0, label_smooth_eps=0.0,
                          max_length=SEQ, d_model=D, **CFG)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(7)
    src = rng.randint(3, VOCAB, (8, SEQ)).astype("int64")
    src_len = np.asarray([SEQ, 3, SEQ - 1, 5, SEQ, 4, SEQ - 2, SEQ],
                         "int64")
    return {"exe": exe, "scope": scope, "src": src, "src_len": src_len}


def _paged(trained, **kw):
    # every session gets its OWN child of the trained scope: params
    # resolve through the parent chain, pgd_* state shadows per child,
    # so two live sessions (oracle / victim / restored) never collide
    args = dict(num_slots=S, max_length=SEQ, d_model=D, paged=True,
                page_size=4, steps=2, num_groups=2,
                prefix_cache_pages=8,
                sampler=Sampler(strategy="top_k", top_k=4,
                                temperature=0.9, seed=11),
                scope=trained["scope"].new_scope())
    args.update(CFG)
    args.update(kw)
    return SlotDecodeSession(trained["exe"], **args)


@pytest.fixture(autouse=True)
def _clean_chaos_and_flags():
    yield
    chaos.disable()
    flags.set_flag("dispatch_retries", 0)


# -- snapshot / restore ------------------------------------------------------

def test_snapshot_restore_is_bit_exact_mid_flight(trained, tmp_path):
    src, src_len = trained["src"], trained["src_len"]
    pfx = [int(x) for x in src[0][:5]]

    def drive(sess):
        """Deterministic load: fork group with prefix, then a backlog
        pumped through the 4-slot pool."""
        gslots = sess.admit_group(src[0], n=2, src_len=int(src_len[0]),
                                  prefix_tokens=pfx)
        rids = [sess.enqueue(src[i], int(src_len[i]))
                for i in range(1, 6)]
        return gslots, rids

    # oracle: the uninterrupted run
    oracle = _paged(trained)
    og, orids = drive(oracle)
    odone = {}
    for _ in range(40):
        odone.update(oracle.pump())
        if len(odone) >= len(orids):
            break

    # victim: same drive, snapshot after 2 pump rounds (live slots,
    # shared pages, prefix cache and backlog all nonempty)
    victim = _paged(trained)
    vg, vrids = drive(victim)
    vdone = {}
    for _ in range(2):
        vdone.update(victim.pump())
    assert victim._live and victim._pending, "snapshot point too late"
    assert victim.shared_pages > 0 or victim.cached_pages > 0
    mgr = DecodeSnapshotManager(victim, str(tmp_path / "snap"))
    mgr.save()
    mgr.close(save=False)

    # restored: a FRESH session + restore, then the same continuation,
    # every executable of it one the oracle's run already compiled
    compiled = exec_cache.stats()["fresh_compiles"]
    restored = _paged(trained)
    mgr2 = DecodeSnapshotManager(restored, str(tmp_path / "snap"))
    manifest = mgr2.restore()
    assert manifest is not None
    assert restored.steps_done == victim.steps_done
    assert restored.pending_requests == victim.pending_requests
    assert restored._pool.state_dict() == victim._pool.state_dict()

    rdone = dict(vdone)
    vdone2 = dict(vdone)
    for _ in range(40):
        vdone2.update(victim.pump())
        rdone.update(restored.pump())
        if len(rdone) >= len(vrids):
            break
    # every request's tokens: victim continuation == restored
    # continuation == oracle (same seeds, same slots, same positions)
    for rid in vrids:
        np.testing.assert_array_equal(rdone[rid], vdone2[rid])
    for o_rid, rid in zip(orids, vrids):
        np.testing.assert_array_equal(odone[o_rid], rdone[rid])
    assert exec_cache.stats()["fresh_compiles"] == compiled, (
        "the restore and its continuation paid fresh compiles")
    mgr2.close(save=False)


def test_snapshot_quarantines_corruption_and_falls_back(trained,
                                                        tmp_path):
    sess = _paged(trained)
    sess.admit(trained["src"][0], int(trained["src_len"][0]))
    snap = str(tmp_path / "snap")
    mgr = DecodeSnapshotManager(sess, snap)
    mgr.save(serial=1)
    sess.step()
    mgr.save(serial=2)
    # flip one byte of a var file in the NEWEST serial
    newest = os.path.join(snap, "checkpoint_2")
    victim_file = os.path.join(newest, "pgd_pos.npy")
    blob = bytearray(open(victim_file, "rb").read())
    blob[-1] ^= 0xFF
    open(victim_file, "wb").write(bytes(blob))

    fresh = _paged(trained)
    mgr2 = DecodeSnapshotManager(fresh, snap)
    manifest = mgr2.restore()
    assert manifest is not None and int(manifest["serial"]) == 1
    assert not os.path.exists(newest), "corrupt serial not quarantined"
    assert any(".corrupt-" in d for d in os.listdir(snap))
    mgr.close(save=False)
    mgr2.close(save=False)


def test_snapshot_geometry_mismatch_is_typed_not_quarantined(
        trained, tmp_path):
    sess = _paged(trained)
    sess.admit(trained["src"][0], int(trained["src_len"][0]))
    snap = str(tmp_path / "snap")
    DecodeSnapshotManager(sess, snap).save()
    other = _paged(trained,
                   num_groups=3)  # different geometry
    with pytest.raises(SnapshotMismatchError):
        DecodeSnapshotManager(other, snap).restore()
    # the serial is still there — operator error, not corruption
    assert os.path.isdir(os.path.join(snap, "checkpoint_0"))


def test_dense_session_is_refused_with_guidance(trained):
    dense = SlotDecodeSession(trained["exe"], num_slots=S,
                              max_length=SEQ, d_model=D,
                              scope=global_scope().new_scope(), **CFG)
    with pytest.raises(ValueError, match="paged"):
        DecodeSnapshotManager(dense, "/tmp/unused")


def test_ckpt_inspect_knows_the_decode_dialect(trained, tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    os.pardir, "tools"))
    try:
        import ckpt_inspect
    finally:
        sys.path.pop(0)
    sess = _paged(trained)
    sess.admit_group(trained["src"][0], n=2,
                     src_len=int(trained["src_len"][0]),
                     prefix_tokens=[int(x) for x in trained["src"][0][:5]])
    snap = str(tmp_path / "snap")
    DecodeSnapshotManager(sess, snap).save(serial=7)
    step_dir = os.path.join(snap, "checkpoint_7")
    assert ckpt_inspect.main([step_dir, "--verify"]) == 0

    # break refcount conservation INSIDE the dialect block (digests
    # cover var files, not the manifest) — --verify must exit 2
    mpath = os.path.join(step_dir, "__manifest__.json")
    manifest = json.load(open(mpath))
    ds = manifest["extra"]["decode_snapshot"]
    page = next(iter(ds["pool"]["ref"]))
    ds["pool"]["ref"][page] = int(ds["pool"]["ref"][page]) + 1
    json.dump(manifest, open(mpath, "w"))
    assert ckpt_inspect.main([step_dir, "--verify"]) == 2
    assert ckpt_inspect.main([step_dir]) == 0  # print-only still reads


@pytest.mark.parametrize("drafter", ["ngram", "model"])
def test_snapshot_restores_mid_speculation_bit_exact(trained, tmp_path,
                                                     drafter):
    """A snapshot taken BETWEEN speculative dispatches restores into a
    fresh session that finishes every request bit-identically to the
    uninterrupted victim: counters, drafter state and (for the model
    drafter) the draft K/V pool rows all travel in the dialect."""
    src, src_len = trained["src"], trained["src_len"]

    def spec_sess():
        return _paged(trained, steps=1,
                      speculative={"k": 2, "drafter": drafter})

    victim = spec_sess()
    vrids = [victim.enqueue(src[i], int(src_len[i])) for i in range(5)]
    vdone = {}
    for _ in range(2):
        vdone.update(victim.pump())
    assert victim._live and victim.spec_dispatches > 0, \
        "snapshot point is not mid-speculation"
    snap = str(tmp_path / "snap")
    mgr = DecodeSnapshotManager(victim, snap)
    mgr.save()
    mgr.close(save=False)

    restored = spec_sess()
    mgr2 = DecodeSnapshotManager(restored, snap)
    assert mgr2.restore() is not None
    assert restored.spec_proposed == victim.spec_proposed
    assert restored.spec_accepted == victim.spec_accepted
    assert restored.spec_dispatches == victim.spec_dispatches
    assert (restored._spec_drafter.state_dict()
            == victim._spec_drafter.state_dict())

    if drafter == "model":
        # the draft params must travel: victim and restored drafters
        # are independently RANDOMLY initialised, and a weight delta
        # shifts acceptance TIMING — which slot a backlog request
        # lands in after restore — which keys the sampler stream.
        # Without the snapshot carrying them this test only fails
        # when the two random inits happen to disagree early enough.
        vp = victim._spec_drafter.param_arrays()
        rp = restored._spec_drafter.param_arrays()
        assert sorted(vp) == sorted(rp) and vp
        for n in vp:
            np.testing.assert_array_equal(rp[n], vp[n], err_msg=n)

    rdone, vdone2 = dict(vdone), dict(vdone)
    for _ in range(40):
        vdone2.update(victim.pump())
        rdone.update(restored.pump())
        if len(rdone) >= len(vrids) and len(vdone2) >= len(vrids):
            break
    for rid in vrids:
        np.testing.assert_array_equal(rdone[rid], vdone2[rid])
    mgr2.close(save=False)

    # speculative config is part of the snapshot contract: a session
    # without the drafter cannot re-own the watermark/draft rows
    plain = _paged(trained, steps=1)
    with pytest.raises(SnapshotMismatchError):
        DecodeSnapshotManager(plain, snap).restore()


@pytest.mark.parametrize("speculative", [
    None, {"k": 2, "drafter": "model"}], ids=["plain", "model_drafter"])
def test_per_head_snapshot_restores_transposed(trained, tmp_path,
                                               monkeypatch, speculative):
    """A snapshot written when a page was per-head ``[H, page_size, dh]``
    (its meta names no layout) restores into today's session of whole
    token rows: the live pages are transposed on load, the draft pools'
    too, and the restored session decodes token for token as the one
    that wrote it. Today's meta names its layout."""
    import re

    from paddle_tpu.serving import snapshot as snapshot_mod

    src, src_len = trained["src"], trained["src_len"]
    H = CFG["n_head"]

    def sess():
        return _paged(trained, steps=1, speculative=speculative)

    victim = sess()
    vrids = [victim.enqueue(src[i], int(src_len[i])) for i in range(5)]
    vdone = {}
    for _ in range(2):
        vdone.update(victim.pump())
    assert victim._live, "snapshot point too late"
    capture = DecodeSnapshotManager._capture
    seen = []

    def capture_per_head(self):
        snap, meta = capture(self)
        assert meta.pop("pool_layout") == snapshot_mod.POOL_LAYOUT
        for name, pages in snap.items():
            if re.fullmatch(r"pgd_(draft_)?[kv]pool_\d+__live", name):
                n, ps, width = pages.shape
                snap[name] = np.ascontiguousarray(pages.reshape(
                    n, ps, H, width // H).transpose(0, 2, 1, 3))
                seen.append(name)
        return snap, meta

    monkeypatch.setattr(DecodeSnapshotManager, "_capture", capture_per_head)
    snap_dir = str(tmp_path / "snap")
    mgr = DecodeSnapshotManager(victim, snap_dir)
    mgr.save()
    mgr.close(save=False)
    monkeypatch.undo()
    assert len(seen) == (2 * CFG["n_layer"] + (2 if speculative else 0))

    restored = sess()
    mgr2 = DecodeSnapshotManager(restored, snap_dir)
    assert mgr2.restore() is not None
    for name in ("pgd_kpool_0", "pgd_vpool_%d" % (CFG["n_layer"] - 1)):
        np.testing.assert_array_equal(
            np.asarray(restored._scope.get_value(name))[
                sorted(victim._pool._ref)],
            np.asarray(victim._scope.get_value(name))[
                sorted(victim._pool._ref)], err_msg=name)
    rdone, vdone2 = dict(vdone), dict(vdone)
    for _ in range(40):
        vdone2.update(victim.pump())
        rdone.update(restored.pump())
        if len(rdone) >= len(vrids) and len(vdone2) >= len(vrids):
            break
    for rid in vrids:
        np.testing.assert_array_equal(rdone[rid], vdone2[rid])
    mgr2.close(save=False)


def test_ckpt_inspect_crosschecks_speculative_bindings(trained,
                                                       tmp_path,
                                                       capsys):
    """``--verify`` on a speculative snapshot cross-checks tree-page
    bindings: a page laundered out of a slot's list (ref moved to the
    free list so conservation and refcount accounting both still
    balance) is exactly the tamper only the resident-coverage check
    catches — exit 2."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    os.pardir, "tools"))
    try:
        import ckpt_inspect
    finally:
        sys.path.pop(0)
    sess = _paged(trained, steps=1,
                  speculative={"k": 2, "drafter": "ngram"})
    sess.admit(trained["src"][0], SEQ)
    while sess._live and all(
            int(st["pos"]) < 5 for st in sess._live.values()):
        sess.step()
    assert sess._live, "request finished before spanning two pages"
    snap = str(tmp_path / "snap")
    DecodeSnapshotManager(sess, snap).save(serial=3)
    step_dir = os.path.join(snap, "checkpoint_3")
    assert ckpt_inspect.main([step_dir, "--verify"]) == 0
    out = capsys.readouterr().out
    assert "speculative: k=2 drafter=ngram" in out

    mpath = os.path.join(step_dir, "__manifest__.json")
    manifest = json.load(open(mpath))
    ds = manifest["extra"]["decode_snapshot"]
    slot = next(iter(ds["slot_pages"]))
    page = int(ds["slot_pages"][slot].pop())
    del ds["pool"]["ref"][str(page)]
    ds["pool"]["free"].append(page)
    ds["live_pages"] = [p for p in ds["live_pages"] if int(p) != page]
    json.dump(manifest, open(mpath, "w"))
    assert ckpt_inspect.main([step_dir, "--verify"]) == 2
    out = capsys.readouterr().out
    assert "speculative slot" in out


# -- degradation -------------------------------------------------------------

def test_health_monitor_hysteresis_and_metrics():
    mon = HealthMonitor("unit", brownout_at=0.5, shed_at=0.9,
                        recover_at=0.3)
    assert mon.observe(0.2) == HEALTHY
    assert mon.observe(0.6) == BROWNOUT
    assert mon.observe(0.4) == BROWNOUT  # hysteresis band: hold
    assert mon.observe(0.95) == SHED
    assert mon.observe(0.6) == SHED      # brownout band can't relax shed
    assert mon.observe(0.1) == BROWNOUT  # one level per crossing
    assert mon.observe(0.1) == HEALTHY
    assert mon.transitions == 4
    err = mon.reject("unit test")
    assert isinstance(err, DegradedError)
    assert err.retry_after_s > 0
    from paddle_tpu.resilience.retry import is_transient

    assert is_transient(err), "DegradedError must classify retriable"
    text = REGISTRY.to_prometheus()
    assert 'paddle_tpu_serving_health{component="unit"} 0' in text
    assert "paddle_tpu_serving_health_transitions_total" in text


def test_decode_brownout_evicts_cache_refuses_forks_then_recovers(
        trained):
    src, src_len = trained["src"], trained["src_len"]
    sess = _paged(trained, num_groups=S,
                  degradation=dict(brownout_at=0.5, shed_at=0.95,
                                   recover_at=0.3))
    pfx = [int(x) for x in src[0][:5]]
    # populate the prefix cache while healthy
    sess.admit(src[0], int(src_len[0]), prefix_tokens=pfx)
    assert sess.cached_pages > 0 and sess.health == HEALTHY
    # second admission crosses 0.5 occupancy at the NEXT gate check
    sess.admit(src[1], int(src_len[1]))
    sess.admit(src[2], int(src_len[2]))
    assert sess.health == BROWNOUT
    # brownout evicted the prefix cache on transition...
    assert sess.cached_pages == 0
    # ...and refuses forks (n=1 only) with a typed retriable error
    with pytest.raises(DegradedError) as exc_info:
        sess.admit_group(src[3], n=2, src_len=int(src_len[3]))
    assert exc_info.value.state == BROWNOUT
    assert exc_info.value.retry_after_s > 0
    sess.admit(src[3], int(src_len[3]))  # solo admission still served
    # full pool: shed refuses EVERYTHING while in-flight work drains
    with pytest.raises(DegradedError) as exc_info:
        sess.admit(src[4], int(src_len[4]))
    assert exc_info.value.state == SHED
    for _ in range(30):  # drain: each step observes the falling load
        if not sess._live:
            break
        sess.step()
    # recovery relaxes ONE level per observation below recover_at, so
    # a couple more public ops land it: the admission gate observes
    # (shed -> brownout at worst, then the solo admit serves), and the
    # drain steps observe again (-> healthy)
    sess.admit(src[4], int(src_len[4]))
    for _ in range(30):
        if not sess._live:
            break
        sess.step()
    assert sess.health == HEALTHY


def test_generate_survives_degradation_by_deferring(trained):
    """pump() treats a DegradedError like a pool reject: defer to the
    queue front and drain — generate() completes every request."""
    src, src_len = trained["src"], trained["src_len"]
    sess = _paged(trained,
                  degradation=dict(brownout_at=0.5, shed_at=0.75,
                                   recover_at=0.5))
    clean = _paged(trained)
    got = sess.generate(src, src_len)
    want = clean.generate(src, src_len)
    # degradation defers ADMISSION ORDER only; tokens are a per-slot
    # function of (seed, slot, position), and requests are admitted in
    # row order either way, so the outputs still match wherever the
    # slot assignment sequence matches. At minimum: every row decoded
    # to a complete, bos-led stream and nothing wedged.
    assert got.shape == want.shape
    assert (got[:, 0] == 1).all()
    assert sess.free_slots == S and sess.pages_in_use == sess.cached_pages


def test_server_shed_types_rejects_and_recovers(trained, tmp_path):
    from paddle_tpu.inference import NativeConfig, create_paddle_predictor
    from paddle_tpu.serving import loadgen
    from paddle_tpu.serving.server import BatchingServer

    model_dir = str(tmp_path / "demo")
    loadgen.build_demo_model(model_dir, train_steps=5)
    predictor = create_paddle_predictor(
        NativeConfig(model_dir=model_dir, use_tpu=False))
    server = BatchingServer(
        predictor, max_batch=8, workers=1, max_queue_depth=8,
        batch_linger_s=0.05,
        degradation=dict(brownout_at=0.5, shed_at=0.75, recover_at=0.25,
                         retry_after_s=0.1))
    reqs = loadgen.demo_requests(16)
    futures, rejects = [], []
    with server:
        for req in reqs:
            try:
                futures.append(server.submit(req))
            except DegradedError as exc:
                assert exc.state == SHED
                assert exc.retry_after_s == 0.1
                rejects.append(exc)
        assert rejects, "the flood never tripped shed"
        # nothing wedges: every admitted future completes
        for fut in futures:
            fut.result(timeout=30.0)
        # drained: the monitor recovered (observe runs at dispatch)
        for req in reqs:  # resubmit the rejected volume — serving again
            server.run(req)
        stats = server.stats()
    assert stats["health"] == HEALTHY
    assert stats["degraded"] == len(rejects)
    text = REGISTRY.to_prometheus()
    assert 'paddle_tpu_serving_health{component="server"} 0' in text


# -- chaos + retry on serving paths ------------------------------------------

def test_admit_chaos_fault_rolls_back_and_retries_bit_exact(trained):
    src, src_len = trained["src"], trained["src_len"]
    clean = _paged(trained)
    want = clean.generate_best_of(src[0], 2, src_len=int(src_len[0]),
                                  prefix_tokens=[int(x)
                                                 for x in src[0][:5]])
    before = REGISTRY.counter(
        "paddle_tpu_retries_total",
        "transient-failure retries by origin",
        ["origin"]).value(origin="serve.admit")
    chaos.configure("seed=3;io@site=serve.admit,n=1")
    flags.set_flag("dispatch_retries", 2)
    sess = _paged(trained)
    got = sess.generate_best_of(src[0], 2, src_len=int(src_len[0]),
                                prefix_tokens=[int(x)
                                               for x in src[0][:5]])
    assert chaos.fires("serve.admit") == 1, "the fault never fired"
    np.testing.assert_array_equal(got, want)
    after = REGISTRY.counter(
        "paddle_tpu_retries_total",
        "transient-failure retries by origin",
        ["origin"]).value(origin="serve.admit")
    assert after == before + 1
    # rollback left the books clean for the retry: nothing leaked
    assert sess._leaked_pages == 0


def test_admit_chaos_fault_without_retries_is_clean_rollback(trained):
    src, src_len = trained["src"], trained["src_len"]
    sess = _paged(trained)
    free_pages = sess.free_pages
    chaos.configure("io@site=serve.admit,n=1")
    with pytest.raises(IOError):
        sess.admit_group(src[0], n=2, src_len=int(src_len[0]))
    chaos.disable()
    assert sess.free_slots == S and sess.free_groups == 2
    assert sess.free_pages == free_pages and sess._reserved_pages == 0
    slots = sess.admit_group(src[0], n=2, src_len=int(src_len[0]))
    assert slots == [0, 1], "rollback changed the slot pop order"


def test_snapshot_write_chaos_fails_save_not_session(trained, tmp_path):
    sess = _paged(trained)
    sess.admit(trained["src"][0], int(trained["src_len"][0]))
    mgr = DecodeSnapshotManager(sess, str(tmp_path / "snap"))
    chaos.configure("io@site=snapshot.write,n=1")
    with pytest.raises(IOError):
        mgr.save(serial=1)
    chaos.disable()
    assert mgr.latest_serial() is None  # nothing half-written visible
    sess.step()  # the session was never touched: still serving
    mgr.save(serial=2)
    assert mgr.latest_serial() == 2
    mgr.close(save=False)


def test_pool_acquire_is_a_chaos_site():
    from paddle_tpu.serving.kv_pool import PagePool

    pool = PagePool(4)
    chaos.configure("io@site=pool.acquire,n=1")
    with pytest.raises(IOError):
        pool.acquire()
    chaos.disable()
    assert pool.free_count == 3  # the faulted acquire allocated nothing
    assert pool.acquire() in (1, 2, 3)


# -- kernel failure is an error, not a degradation ------------------------------------------------------

def test_paged_attention_kernel_failure_propagates_typed(monkeypatch):
    """No trip to the reference any more: a kernel the toolchain
    refuses propagates, typed, naming the kernel and its operand
    shapes — on every call, and the fallback counter never appears."""
    from paddle_tpu.kernels import paged_attention as pa

    rng = np.random.RandomState(5)
    q = rng.randn(2, 2, 8).astype("float32")
    kp = rng.randn(3, 4, 16).astype("float32")
    vp = rng.randn(3, 4, 16).astype("float32")
    table = np.asarray([[1, 1], [2, 2]], "int32")
    lengths = np.asarray([3, 4], "int32")

    calls = {"n": 0}

    def boom(*a, **k):
        calls["n"] += 1
        raise RuntimeError("pallas toolchain exploded")

    monkeypatch.setattr(pa, "_paged_pallas", boom)
    for attempt in (1, 2):
        with pytest.raises(pa.KernelCompileError) as err:
            pa.paged_attention(q, kp, vp, table, lengths,
                               force_pallas=True)
        assert calls["n"] == attempt  # nothing remembers the failure
        assert err.value.kernel == pa.PAGED_KERNEL_NAME
        assert ((3, 4, 16), "float32") in err.value.shapes
        assert "pallas toolchain exploded" in str(err.value)
        assert isinstance(err.value.__cause__, RuntimeError)
    # the explicit oracle is untouched by a broken kernel
    np.asarray(pa.paged_attention(q, kp, vp, table, lengths,
                                  force_reference=True))
    assert calls["n"] == 2
    assert "paddle_tpu_kernel_fallbacks_total" not in \
        REGISTRY.to_prometheus()


# -- watchdog over serving dispatch ------------------------------------------

def test_server_dispatch_arms_watchdog(trained, tmp_path, monkeypatch):
    from paddle_tpu.inference import NativeConfig, create_paddle_predictor
    from paddle_tpu.serving import loadgen
    from paddle_tpu.serving import server as server_mod

    model_dir = str(tmp_path / "demo")
    loadgen.build_demo_model(model_dir, train_steps=5)
    predictor = create_paddle_predictor(
        NativeConfig(model_dir=model_dir, use_tpu=False))
    events = []

    class SpyWatchdog(object):
        ENABLED = True

        @staticmethod
        def arm(tag="work", scale=1):
            events.append(("arm", tag))
            return 99

        @staticmethod
        def disarm(token):
            events.append(("disarm", token))

    monkeypatch.setattr(server_mod, "_watchdog", SpyWatchdog)
    with server_mod.BatchingServer(predictor, max_batch=2,
                                   workers=1) as server:
        server.run(loadgen.demo_requests(1)[0])
    assert ("arm", "serve.dispatch") in events
    assert ("disarm", 99) in events
    assert (len([e for e in events if e[0] == "arm"])
            == len([e for e in events if e[0] == "disarm"]))


# -- a signal mid-decode (subprocess) ----------------------------------------

def _drill(n_requests):
    """A seeded 1-layer model, a factory of paged sessions over it and
    request rows, built the same in ANY process (fresh name counters, both
    programs seeded): the subprocess legs' victim, and the oracle and the
    restored session of the test that outlives it."""
    from paddle_tpu import unique_name
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.models import transformer

    cfg = dict(CFG, n_layer=1)
    with unique_name.guard({}):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 41
        with fluid.program_guard(main, startup):
            transformer.build(dropout=0.0, label_smooth_eps=0.0,
                              max_length=SEQ, d_model=D, **cfg)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)

    def session():
        return SlotDecodeSession(
            exe, num_slots=S, max_length=SEQ, d_model=D, paged=True,
            page_size=4, steps=2, sampler=Sampler(seed=3),
            scope=scope.new_scope(), **cfg)

    src = np.random.RandomState(7).randint(
        3, VOCAB, (n_requests, SEQ)).astype("int64")
    return session, src


# argv: snapshot dir, snapshot interval in steps, number of requests
_CHILD = r"""
import os, sys, time
sys.path.insert(0, os.path.join(os.getcwd(), "tests"))
import test_serving_resilience as t

session, src = t._drill(int(sys.argv[3]))
sess = session()
mgr = t.DecodeSnapshotManager(sess, sys.argv[1],
                              interval_steps=int(sys.argv[2]),
                              install_signal_handlers=True)
for row in src:
    sess.enqueue(row)
print("READY", flush=True)
while sess._pending or sess._live:
    sess.pump()
    time.sleep(0.01)
print("DRAINED", flush=True)  # only reached if no signal ever lands
"""

_REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)


def test_sigkill_mid_decode_is_resumed_bit_exact_by_another_process(
        tmp_path):
    """The real preemption: a decoding process with periodic snapshots is
    SIGKILLed entering a seeded step dispatch (no handler, no cleanup);
    THIS process restores the newest verified snapshot into a fresh
    session and re-emits every stream of the backlog, those finished
    before the snapshot out of its result bank, bit-identical to an
    uninterrupted run. Ten full-length requests over four slots finish in
    dispatches 4, 8 and 12; the snapshots fall after dispatches 3 and 6
    and the kill on entering the 8th, so whichever of the two was written
    out in time is restored. (Not after dispatch 4 or 8: a snapshot taken
    at the quiesce point of the dispatch that FINISHES a request holds
    it neither live nor banked, ``pump`` banks after ``step`` returns:
    ROADMAP D23.)"""
    snap_dir = str(tmp_path / "snap")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               FLAGS_chaos_spec="seed=5;kill@site=serve.dispatch,step=7")
    victim = subprocess.run(
        [sys.executable, "-c", _CHILD, snap_dir, "3", "10"], env=env,
        cwd=_REPO, capture_output=True, text=True, timeout=180)
    assert victim.returncode == -signal.SIGKILL, (
        victim.returncode, victim.stderr[-2000:])
    assert "READY" in victim.stdout and "DRAINED" not in victim.stdout

    session, src = _drill(10)
    oracle = session()
    rids = [oracle.enqueue(row) for row in src]
    want = {}
    while len(want) < len(rids):
        want.update(oracle.pump())
    restored = session()
    mgr = DecodeSnapshotManager(restored, snap_dir)
    assert mgr.restore() is not None, "no restorable snapshot after SIGKILL"
    assert restored._pending or restored._live, "nothing was cut off"
    got = {}
    while restored._pending or restored._live:
        got.update(restored.pump())
    for rid in rids:
        tokens = got[rid] if rid in got else restored.take_result(rid)
        np.testing.assert_array_equal(tokens, want[rid])
    mgr.close(save=False)


@pytest.mark.slow
def test_sigterm_banks_final_snapshot_and_dies_by_signal(tmp_path):
    snap_dir = str(tmp_path / "snap")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("FLAGS_chaos_spec", None)
    proc = subprocess.Popen(
        [sys.executable, "-c", _CHILD, snap_dir, "0", "64"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=_REPO)
    try:
        line = proc.stdout.readline().strip()
        assert line == "READY", (line, proc.stderr.read())
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=120)
    finally:
        proc.kill()
    # died BY the signal (handler chain re-delivered it), after the
    # in-flight dispatch finished and a final sync snapshot landed
    assert proc.returncode == -signal.SIGTERM, (proc.returncode, err)
    assert "DRAINED" not in out
    from paddle_tpu.resilience.checkpoint import (
        complete_serials,
        read_manifest,
    )

    serials = complete_serials(snap_dir)
    assert serials, "no final snapshot banked on SIGTERM"
    manifest = read_manifest(
        os.path.join(snap_dir, "checkpoint_%d" % serials[-1]))
    meta = manifest["extra"]["decode_snapshot"]
    assert meta["live"] or meta["pending"], \
        "snapshot carries no in-flight state"
