"""Batched beam search over the paged slot pool (PR 15), pinned at the
BIT level:

* the in-graph ``slot_beam_search`` selection is bit-exact against the
  dense ``beam_step`` lattice replayed OFFLINE over the step's own
  fetched logits (same reshapes, same parent gather, same float32
  log-softmax);
* the zero-copy rebind reorder decodes bit-identical tokens AND scores
  to the ``FLAGS_beam_reorder=reference`` copy-reorder oracle at
  staggered admissions — while physically moving ZERO pages (the
  oracle moves O(resident) per reorder);
* COW pairs coalesce into ONE bucket-laddered dispatch per step window
  (the dispatch count is pinned — beam reorders multiply pairs, not
  dispatches);
* ``cancel`` of any hypothesis releases the WHOLE beam with the pool
  conserved (the PR 14 disconnect path);
* a mid-beam ``DecodeSnapshotManager`` snapshot restores scores,
  parent maps and hypothesis->slot bindings bit-exactly (geometry
  drift raises the typed ``SnapshotMismatchError``), and
  ``tools/ckpt_inspect.py --verify`` cross-checks the beam bindings
  against the refcounts (exit 2 on a tampered binding);
* warm beam churn adds 0 fresh compiles.
"""

import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags as _flags
from paddle_tpu.core import exec_cache
from paddle_tpu.executor import global_scope
from paddle_tpu.serving.generation import (
    NoFreeSlotError,
    Sampler,
    SlotDecodeSession,
)

VOCAB, SEQ, D, S, BW = 26, 12, 32, 8, 4
CFG = dict(src_vocab_size=VOCAB, trg_vocab_size=VOCAB, n_layer=2,
           n_head=2, d_inner=64)
# both reorder modes share ONE geometry (and one content-addressed
# program set); the copy oracle's transient full-list copies need the
# free-page headroom
PAGES = 1 + 2 * S * (SEQ // 4 + 1)


@pytest.fixture(scope="module")
def trained():
    """One tiny trained 2-layer transformer (per-layer pools, COW and
    reorder all exercised past layer 0)."""
    from paddle_tpu.models import transformer

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 43
    startup.random_seed = 43
    scope = global_scope()
    with fluid.program_guard(main, startup):
        loss, feeds, extras = transformer.build(
            dropout=0.0, label_smooth_eps=0.0, max_length=SEQ,
            d_model=D, **CFG)
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(44)
    # a handful of steps is enough: the suite pins BIT-equalities
    # between decode modes, not model quality — it only needs
    # deterministic, non-degenerate logits (enough spread that beams
    # actually diverge and COW fires; asserted downstream)
    for _ in range(6):
        src = rng.randint(3, VOCAB, (8, SEQ)).astype("int64")
        trg = np.full_like(src, 1)
        trg[:, 1:] = src[:, :-1]
        exe.run(main, feed={
            "src_word": src,
            "src_len": np.full((8, 1), SEQ, "int64"),
            "trg_word": trg,
            "trg_len": np.full((8, 1), SEQ, "int64"),
            "label": src,
        }, fetch_list=[loss])
    src = rng.randint(3, VOCAB, (4, SEQ)).astype("int64")
    return {"exe": exe, "scope": scope, "src": src}


def _beam(trained, **kw):
    args = dict(num_slots=S, max_length=SEQ, d_model=D, paged=True,
                page_size=4, beam_width=BW, num_pages=PAGES,
                scope=trained["scope"].new_scope())
    args.update(CFG)
    args.update(kw)
    return SlotDecodeSession(trained["exe"], **args)


def _staggered(sess, src, keep_going=True):
    """Two beams admitted 3 dispatches apart, a third back-to-back —
    the reorder/COW/release paths at mixed lane ages."""
    a = sess.admit_beam(src[0], SEQ)
    ra = sess.register_beam_owner(a)
    for _ in range(3):
        sess.step()
    b = sess.admit_beam(src[1], SEQ - 2)
    rb = sess.register_beam_owner(b)
    while sess.active_beams:
        sess.step()
    out = [sess.take_beam_result(ra), sess.take_beam_result(rb)]
    if keep_going:
        out.append(dict(zip(("tokens", "scores"),
                            sess.generate_beam(src[2], SEQ))))
    return out


# ---------------------------------------------------------------------------
# the lattice itself: in-graph selection == dense beam_step offline
# ---------------------------------------------------------------------------

def test_in_graph_selection_matches_offline_dense_lattice(trained):
    """Per step, the fetched (token, parent, score) must be bit-equal
    to ``ops.beam_search_ops.beam_step`` run OFFLINE on the step's own
    fetched logits with the session's pre-step lattice state — the
    proof that the slot-pool beam is the dense lattice, reshaped."""
    import jax

    from paddle_tpu.ops.beam_search_ops import beam_step

    sess = _beam(trained)
    scope = sess._scope
    lane = sess.admit_beam(trained["src"][0], SEQ)
    slots = sess.beam_slots(lane)
    # ride the step dispatch with a logits fetch (the builder exports
    # the name for exactly this test)
    sess._extra_step_fetches = [sess._beam_fetches["logits"]]
    checked = 0
    for _ in range(SEQ):
        if lane not in sess._beam_live:
            break
        pre_tok = np.asarray(scope.get_value("pgd_tok")).reshape(-1)
        pre_done = np.asarray(scope.get_value("pgd_done")).reshape(-1)
        pre_score = np.asarray(
            scope.get_value("pgd_score")).reshape(-1)
        sess.step()
        logits = sess.last_extra_fetches[0][:, 0, :].astype(np.float32)
        # offline replay of the op's lattice, lane slice only (lanes
        # are independent rows of the [B, K, V] lattice)
        forced = np.where(pre_done > 0, sess._eos, pre_tok)
        logp = np.asarray(jax.nn.log_softmax(logits[slots], axis=-1))
        tok, sel, parent = beam_step(
            forced[slots].reshape(1, BW).astype(np.int32),
            pre_score[slots].reshape(1, BW).astype(np.float32),
            logp.reshape(1, BW, -1), sess._eos, is_accumulated=False)
        ev = sess.last_beam_events.get(lane)
        if ev is None:  # the finishing step: compare the final chunk
            fin = sess.last_finished_beams[lane]
            got = (fin["step_tokens"], fin["parents"],
                   fin["step_scores"])
        else:
            got = (ev["tokens"], ev["parents"], ev["scores"])
        np.testing.assert_array_equal(np.asarray(tok).reshape(-1),
                                      got[0])
        np.testing.assert_array_equal(np.asarray(parent).reshape(-1),
                                      got[1])
        np.testing.assert_array_equal(
            np.asarray(sel, np.float32).reshape(-1),
            np.asarray(got[2], np.float32))
        checked += 1
    assert checked >= 3, "lattice never compared across a real decode"


# ---------------------------------------------------------------------------
# the tentpole: zero-copy rebind == copy oracle, staggered
# ---------------------------------------------------------------------------

def test_rebind_matches_copy_oracle_and_moves_zero_pages(trained):
    src = trained["src"]
    swap = _beam(trained)
    got = _staggered(swap, src, keep_going=False)
    # THE zero-copy law: every reorder this decode performed was pure
    # table-row rebinds + refcount moves — no KV page was copied to
    # execute a permutation (COW write-page splits are counted apart)
    assert swap.beam_reorder_pages == 0
    assert swap.pool_conserved and swap.pages_in_use == 0

    _flags.set_flag("beam_reorder", "reference")
    try:
        copy_sess = _beam(trained)
        ref = _staggered(copy_sess, src, keep_going=False)
    finally:
        _flags.set_flag("beam_reorder", "rebind")
    assert copy_sess.beam_reorder_pages > 0, \
        "the copy oracle never copied a page"
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(g["tokens"], r["tokens"])
        np.testing.assert_array_equal(g["scores"], r["scores"])
    assert copy_sess.pool_conserved and copy_sess.pages_in_use == 0
    from paddle_tpu.observability import REGISTRY

    text = REGISTRY.to_prometheus()
    assert "paddle_tpu_serving_active_beams 0" in text
    assert "paddle_tpu_serving_beam_reorder_bytes_total" in text
    assert "paddle_tpu_serving_beam_cow_copies_total" in text


def test_warm_beam_rerun_adds_zero_fresh_compiles(trained):
    sess = _beam(trained)
    # warmup compiles the beam set (incl. generate_beam's path)
    first = _staggered(sess, trained["src"])
    before = exec_cache.stats()["fresh_compiles"]
    again = _staggered(sess, trained["src"])
    assert exec_cache.stats()["fresh_compiles"] == before, \
        "staggered beam churn recompiled at warm steady state"
    # and the re-run is deterministic (greedy lattice, same pages)
    for g, r in zip(again, first):
        np.testing.assert_array_equal(g["tokens"], r["tokens"])


def test_cow_dispatches_coalesce_per_step_window(trained):
    """The satellite pin: COW pairs multiply per beam step (duplicated
    parents x layers of pages), but dispatches must NOT — one
    bucket-laddered executable per step window."""
    sess = _beam(trained)
    sess.generate_beam(trained["src"][0], SEQ)
    assert sess.cow_pairs > sess.cow_dispatches, (
        "coalescing never happened: %d pairs took %d dispatches"
        % (sess.cow_pairs, sess.cow_dispatches))
    # at most ONE coalesced dispatch per step window (+1 for the
    # admission-time provisioning none of these shapes need)
    assert sess.cow_dispatches <= sess.steps_done, (
        "%d COW dispatches over %d step windows — the window split"
        % (sess.cow_dispatches, sess.steps_done))


def test_cancel_releases_whole_beam_and_conserves(trained):
    sess = _beam(trained)
    lane = sess.admit_beam(trained["src"][0], SEQ)
    for _ in range(3):
        sess.step()
    slots = sess.beam_slots(lane)
    assert sess.cancel(slots[2])  # ANY member tears the whole beam down
    assert not sess.active_beams and sess.free_beams == S // BW
    assert sess.free_slots == S and not sess.active_slots
    assert sess.pool_conserved and sess.pages_in_use == 0
    # the lane is immediately reusable, bit-identically
    t1, s1 = sess.generate_beam(trained["src"][0], SEQ)
    t2, s2 = sess.generate_beam(trained["src"][0], SEQ)
    np.testing.assert_array_equal(t1, t2)


def test_beam_admission_rejects_are_typed(trained):
    sess = _beam(trained)
    lanes = [sess.admit_beam(trained["src"][i % 4], SEQ)
             for i in range(S // BW)]
    with pytest.raises(NoFreeSlotError):
        sess.admit_beam(trained["src"][0], SEQ)
    for lane in lanes:
        sess.cancel(sess.beam_slots(lane)[0])
    # beam sessions are admit-or-reject: the solo backlog is refused
    with pytest.raises(ValueError):
        sess.enqueue(trained["src"][0], SEQ)
    with pytest.raises(ValueError):
        sess.admit_group(trained["src"][0], n=2)
    # and a beam session cannot be mis-built
    with pytest.raises(ValueError):
        _beam(trained, steps=2)
    with pytest.raises(ValueError):
        _beam(trained, beam_width=3)  # 8 % 3 != 0
    with pytest.raises(ValueError):
        _beam(trained, sampler=Sampler(strategy="temperature",
                                       temperature=0.8))


def test_beam_shared_prefix_pages_count_once(trained):
    """A beam over a forced prefix references the prefix pages from
    every hypothesis — physically stored ONCE (the LONG_CONTEXT row)."""
    sess = _beam(trained)
    pfx = [int(t) for t in trained["src"][0][:7]]
    lane = sess.admit_beam(trained["src"][0], SEQ, prefix_tokens=pfx)
    # 7 forced tokens + bos at page_size 4 => 1 FULL shared prefix page
    # (+ the partial tail each hypothesis may COW later); 4 hypotheses
    # referencing it physically allocate 1, not 4
    assert sess.shared_pages >= 1
    full_prefix_pages = (len(pfx) + 1 - 1) // 4  # positions 0..6
    assert sess.pages_in_use < BW * (full_prefix_pages + 1) + 2
    sess.cancel(sess.beam_slots(lane)[0])
    assert sess.pool_conserved


# ---------------------------------------------------------------------------
# snapshot + inspector coverage
# ---------------------------------------------------------------------------

def test_snapshot_restores_mid_beam_bit_exact(trained, tmp_path):
    from paddle_tpu.serving.snapshot import (
        DecodeSnapshotManager,
        SnapshotMismatchError,
    )

    src = trained["src"]
    oracle = _beam(trained)
    want = _staggered(oracle, src, keep_going=False)

    victim = _beam(trained)
    a = victim.admit_beam(src[0], SEQ)
    ra = victim.register_beam_owner(a)
    for _ in range(3):
        victim.step()
    b = victim.admit_beam(src[1], SEQ - 2)
    rb = victim.register_beam_owner(b)
    mgr = DecodeSnapshotManager(victim, str(tmp_path))
    mgr.save()
    mgr.close(save=False)

    restored = _beam(trained)
    mgr2 = DecodeSnapshotManager(restored, str(tmp_path))
    assert mgr2.restore() is not None
    while restored.active_beams:
        restored.step()
    got = [restored.take_beam_result(ra),
           restored.take_beam_result(rb)]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g["tokens"], w["tokens"])
        np.testing.assert_array_equal(g["scores"], w["scores"])
    mgr2.close(save=False)

    # geometry drift (a different beam tiling between the snapshot and
    # the session) is the TYPED error — drift the recorded width so the
    # bw=4 session we already have plays the mismatched restorer
    step_dir = sorted(glob.glob(str(tmp_path / "checkpoint_*")))[-1]
    mpath = os.path.join(step_dir, "__manifest__.json")
    with open(mpath) as f:
        manifest = json.load(f)
    manifest["extra"]["decode_snapshot"]["config"]["beam_width"] = 2
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(SnapshotMismatchError):
        DecodeSnapshotManager(restored, str(tmp_path)).restore()


def test_ckpt_inspect_prints_and_verifies_beam_state(trained,
                                                     tmp_path):
    from paddle_tpu.serving.snapshot import DecodeSnapshotManager

    sess = _beam(trained)
    sess.admit_beam(trained["src"][0], SEQ)
    for _ in range(2):
        sess.step()
    mgr = DecodeSnapshotManager(sess, str(tmp_path))
    mgr.save()
    mgr.close(save=False)
    step_dir = sorted(glob.glob(str(tmp_path / "checkpoint_*")))[-1]
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "ckpt_inspect.py")

    r = subprocess.run([sys.executable, tool, step_dir, "--verify"],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "beam: width=4" in r.stdout and "lane 0:" in r.stdout

    # tamper the beam binding (a lane claiming a non-live slot): the
    # refcount/binding cross-check must fail OFFLINE with exit 2
    mpath = os.path.join(step_dir, "__manifest__.json")
    with open(mpath) as f:
        manifest = json.load(f)
    ds = manifest["extra"]["decode_snapshot"]
    lane0 = sorted(ds["beam"]["lanes"])[0]
    ds["beam"]["lanes"][lane0]["slots"][-1] = S - 1  # a free slot
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    r = subprocess.run([sys.executable, tool, step_dir, "--verify"],
                       capture_output=True, text=True)
    assert r.returncode == 2, r.stdout + r.stderr
    assert "beam" in r.stdout
