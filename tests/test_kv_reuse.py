"""Cross-request KV reuse tests (the PR 12 serving layer): fork groups
over the refcounted page pool, group-pooled cross-attention K/V, the
prefix cache + chunked prefill, and copy-on-write — all pinned at the
BIT level:

* an ``admit_group(n=N)`` greedy member's tokens are bit-identical to
  a solo ``admit()`` of the same source;
* sampled members match a per-member seeded UNSHARED replay (same
  slots => same ``(seed, slot, position)`` PRNG streams);
* a prefix-cache hit decodes bit-identical to a cold suffix prefill;
* a post-dispatch admission fault rolls back with the table row
  repointed at the trash page FIRST, so a recycled page can never
  receive the stale row's writes (the chaos regression for the PR 11
  rollback bug);
* ``generate()``'s deferred-request ordering is pinned (deque
  semantics);
* cross K/V pool bytes scale with ``num_groups``, not ``num_slots``.
"""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import exec_cache
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.resilience.chaos import ChaosTransientError
from paddle_tpu.serving.generation import (
    NoFreeGroupError,
    NoFreePageError,
    Sampler,
    SlotDecodeSession,
)

VOCAB, SEQ, D = 24, 8, 32
CFG = dict(src_vocab_size=VOCAB, trg_vocab_size=VOCAB, n_layer=2,
           n_head=2, d_inner=64)


@pytest.fixture(scope="module")
def trained(request):
    """One tiny trained 2-layer transformer (2 layers so per-layer
    pools, prefill writes and COW copies are all exercised past layer
    0) + the dense-decoder greedy oracle."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 31
    startup.random_seed = 31
    from paddle_tpu.executor import global_scope
    from paddle_tpu.models import transformer

    scope = global_scope()
    with fluid.program_guard(main, startup):
        loss, feeds, extras = transformer.build(
            dropout=0.0, label_smooth_eps=0.0, max_length=SEQ,
            d_model=D, **CFG)
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(32)
    for _ in range(25):
        src = rng.randint(3, VOCAB, (16, SEQ)).astype("int64")
        trg = np.full_like(src, 1)
        trg[:, 1:] = src[:, :-1]
        exe.run(main, feed={
            "src_word": src,
            "src_len": np.full((16, 1), SEQ, "int64"),
            "trg_word": trg,
            "trg_len": np.full((16, 1), SEQ, "int64"),
            "label": src,
        }, fetch_list=[loss])
    src = rng.randint(3, VOCAB, (4, SEQ)).astype("int64")
    src_len = np.asarray([[SEQ], [SEQ - 2], [SEQ], [3]], "int64")
    dense = SlotDecodeSession(exe, num_slots=4, max_length=SEQ,
                              d_model=D, scope=scope, **CFG)
    want = dense.generate(src, src_len)
    return {"exe": exe, "scope": scope, "src": src, "src_len": src_len,
            "want": want}


def _paged(trained, **kw):
    args = dict(num_slots=4, max_length=SEQ, d_model=D, paged=True,
                page_size=4, steps=2, scope=trained["scope"])
    args.update(CFG)
    args.update(kw)
    return SlotDecodeSession(trained["exe"], **args)


def test_group_greedy_member_bit_identical_to_solo_admit(trained):
    """Acceptance: one encoder forward + a shared cross K/V row + a
    shared (then COW'd) page set changes NOTHING about a greedy
    member's tokens vs a solo admission of the same source — and the
    solo path itself still equals the dense oracle."""
    sess = _paged(trained)
    solo = sess.generate(trained["src"][:1], trained["src_len"][:1])
    np.testing.assert_array_equal(solo, trained["want"][:1])
    group = sess.generate_best_of(trained["src"][0], 3,
                                  src_len=trained["src_len"][0])
    for row in group:
        np.testing.assert_array_equal(row, solo[0])
    assert sess.pages_in_use == 0 and sess.free_groups == 4


def test_group_sampled_members_match_unshared_replay(trained):
    """Sampled members share encoder/cross/pages yet reproduce a
    per-member UNSHARED replay bit-for-bit: group members land in the
    same slots consecutive solo admissions would, so the
    (seed, slot, position) streams line up; sharing must not perturb a
    single sampled bit."""
    smp = Sampler(strategy="top_k", top_k=4, temperature=0.9, seed=7)
    shared = _paged(trained, sampler=smp)
    got = shared.generate_best_of(trained["src"][0], 3,
                                  src_len=trained["src_len"][0])
    # members DO diverge (the sampler is per-slot), else the test is
    # vacuous
    assert not (np.array_equal(got[0], got[1])
                and np.array_equal(got[1], got[2]))
    unshared = _paged(trained, sampler=smp)
    s = [unshared.admit(trained["src"][0], trained["src_len"][0])
         for _ in range(3)]
    outs = {}
    while len(outs) < 3:
        outs.update(unshared.step())
    np.testing.assert_array_equal(
        got, np.stack([outs[i] for i in s]))


def test_prefix_cache_hit_bit_identical_and_skips_prefill(trained):
    """A prefix-cache hit provisions full pages by REFERENCE and
    decodes bit-identical to the cold suffix prefill that created
    them; stats/gauges record the reuse, cached pages outlive the
    slots, and clear_prefix_cache() drains the pool to zero."""
    sess = _paged(trained, prefix_cache_pages=8)
    pfx = [int(t) for t in trained["src"][0][:5]]  # 5 forced + bos = 6
    cold = sess.generate_best_of(trained["src"][0], 1, src_len=SEQ,
                                 prefix_tokens=pfx)
    st = sess.prefix_cache_stats()
    assert st["lookups"] == 1 and st["hits"] == 0
    assert sess.cached_pages == 1  # one FULL page (4 of 5 positions)
    hit = sess.generate_best_of(trained["src"][0], 1, src_len=SEQ,
                                prefix_tokens=pfx)
    np.testing.assert_array_equal(hit, cold)
    st = sess.prefix_cache_stats()
    assert st["hits"] == 1 and st["hit_rate"] == 0.5
    assert st["tokens_saved"] == 4  # one full page provisioned by ref
    # forced rows actually lead the output
    assert (cold[0][:6] == [1] + pfx).all()
    # a LONGER prefix extending the cached one reuses its full page
    pfx2 = pfx + [int(trained["src"][0][5])]
    sess.generate_best_of(trained["src"][0], 1, src_len=SEQ,
                          prefix_tokens=pfx2)
    st = sess.prefix_cache_stats()
    assert st["hits"] == 2 and st["tokens_saved"] == 8
    # a different SOURCE must miss (prefix K/V depends on cross attn)
    sess.generate_best_of(trained["src"][2], 1, src_len=SEQ,
                          prefix_tokens=pfx)
    assert sess.prefix_cache_stats()["hits"] == 2
    # cached pages persist after every slot drained; clear() frees them
    assert sess.free_slots == 4 and sess.pages_in_use > 0
    assert sess.pages_in_use == sess.cached_pages
    sess.clear_prefix_cache()
    assert sess.pages_in_use == 0
    from paddle_tpu.observability import REGISTRY

    text = REGISTRY.to_prometheus()
    assert "paddle_tpu_serving_prefix_hit_rate" in text
    assert "paddle_tpu_serving_prefill_tokens_saved_total" in text


def test_prefix_fork_shares_pages_until_cow_and_conserves(trained):
    """A best-of-N fork over a forced prefix: members share the prefix
    pages (kv_pages_shared / dedup gauges go live), each member's
    first write copy-on-writes the partial tail, tokens equal the
    unshared replay, and the drained pool conserves every page. A
    second wave through the warm session adds ZERO fresh compiles
    (join/prefill/copy are fixed-shape executables)."""
    smp = Sampler(strategy="temperature", temperature=0.8, seed=11)
    sess = _paged(trained, sampler=smp, prefix_cache_pages=8)
    pfx = [int(t) for t in trained["src"][0][:5]]
    shared_seen = []
    orig_run = sess._exe.run

    def spy(prog, **kw):
        shared_seen.append(sess.shared_pages)
        return orig_run(prog, **kw)

    sess._exe = type("E", (), {
        "run": staticmethod(spy),
        "run_multi_step": staticmethod(sess._exe.run_multi_step)})()
    got = sess.generate_best_of(trained["src"][0], 3, src_len=SEQ,
                                prefix_tokens=pfx)
    assert max(shared_seen) > 0, "fork never actually shared a page"
    # unshared replay (cache off => three cold prefills)
    solo = _paged(trained, sampler=smp)
    s = [solo.admit(trained["src"][0], SEQ, prefix_tokens=pfx)
         for _ in range(3)]
    outs = {}
    while len(outs) < 3:
        outs.update(solo.step())
    np.testing.assert_array_equal(got, np.stack([outs[i] for i in s]))
    # conservation at drain: only cache refs remain, then none
    assert sess.pages_in_use == sess.cached_pages
    assert sess.shared_pages == 0
    before = exec_cache.stats()["fresh_compiles"]
    # wave 2 members land in whatever slots the free stack now leads
    # with (slot-keyed PRNG => legitimately different samples); the
    # invariant is the EXECUTABLE SET: zero fresh compiles warm
    sess.generate_best_of(trained["src"][0], 3, src_len=SEQ,
                          prefix_tokens=pfx)
    assert exec_cache.stats()["fresh_compiles"] == before, \
        "warm fork/prefix wave paid fresh compiles"
    sess.clear_prefix_cache()
    assert sess.pages_in_use == 0 and sess.free_pages == sess._P - 1
    from paddle_tpu.observability import REGISTRY

    assert "paddle_tpu_serving_kv_pages_shared 0" in REGISTRY.to_prometheus()


def test_admit_failure_rollback_repoints_before_freeing(trained):
    """Chaos regression for the admission rollback: a fault raised
    AFTER the admit dispatch committed device-side (the worst case —
    the device row points at the rolled-back pages and the slot's
    done flag is 0) must repoint the table row at the trash page
    BEFORE the pages return to the free list. Otherwise the next
    admission recycles those pages while the stale, still-stepping
    row keeps writing into them — and the re-admitted sequence's
    tokens silently corrupt."""
    sess = _paged(trained, num_pages=1 + 2 * pa.pages_for(SEQ, 4))
    orig_exe = sess._exe
    state = {"armed": True}

    class _PostDispatchFault(object):
        def run(self, prog, **kw):
            out = orig_exe.run(prog, **kw)
            if state["armed"] and prog is sess._admit_prog:
                state["armed"] = False
                raise ChaosTransientError(
                    "chaos: post-dispatch admit fault")
            return out

        def run_multi_step(self, *a, **kw):
            return orig_exe.run_multi_step(*a, **kw)

    sess._exe = _PostDispatchFault()
    free_pages = sess.free_pages
    with pytest.raises(ChaosTransientError):
        sess.admit(trained["src"][0], trained["src_len"][0])
    # rollback left every count unchanged
    assert sess.free_slots == 4 and sess.free_pages == free_pages
    assert sess.free_groups == 4 and sess._reserved_pages == 0
    # the poisoned slot's device row now points at the trash page, so
    # admissions that RECYCLE its pages decode clean while the stale
    # row keeps stepping on device
    out = sess.generate(trained["src"][1:3], trained["src_len"][1:3])
    np.testing.assert_array_equal(out, trained["want"][1:3])
    assert sess.pages_in_use == 0


def test_cow_failure_leaks_destination_instead_of_freeing(trained):
    """A copy_prog dispatch that fails AFTER possibly committing must
    LEAK the destination page, not free it: if the dispatch committed,
    the device row points at it, and recycling it would corrupt the
    next owner. The leak also shrinks the admission capacity bound so
    provisioning still can never fail mid-flight."""
    smp = Sampler(strategy="temperature", temperature=0.8, seed=19)
    # prefix of 3 forced tokens: the first write (pos 3) lands inside
    # the shared tail page => one COW per non-final member
    sess = _paged(trained, sampler=smp)
    pfx = [int(t) for t in trained["src"][0][:3]]
    slots = sess.admit_group(trained["src"][0], 2, src_len=SEQ,
                             prefix_tokens=pfx)
    orig_exe = sess._exe
    state = {"armed": True}

    class _PostDispatchCopyFault(object):
        def run(self, prog, **kw):
            out = orig_exe.run(prog, **kw)
            # COW rides the coalesced bucket-ladder programs now (one
            # dispatch per step window), not the per-pair copy_prog
            if state["armed"] and prog in sess._cow_progs.values():
                state["armed"] = False
                raise ChaosTransientError(
                    "chaos: post-dispatch copy fault")
            return out

        def run_multi_step(self, *a, **kw):
            return orig_exe.run_multi_step(*a, **kw)

    sess._exe = _PostDispatchCopyFault()
    in_use = sess.pages_in_use
    with pytest.raises(ChaosTransientError):
        sess.step()
    # the destination page stays allocated (leaked), the host row
    # restored the shared source, and capacity shrank by the leak
    assert sess._leaked_pages == 1
    assert sess.pages_in_use == in_use + 1
    assert sess.shared_pages > 0  # src_pg still shared in the row
    sess._exe = orig_exe
    # the session still decodes: the retried dispatch COWs afresh and
    # both members finish with uncorrupted streams (== unshared replay)
    outs = {}
    while len(outs) < 2:
        outs.update(sess.step())
    solo = _paged(trained, sampler=smp)
    s = [solo.admit(trained["src"][0], SEQ, prefix_tokens=pfx)
         for _ in range(2)]
    want = {}
    while len(want) < 2:
        want.update(solo.step())
    for got_slot, want_slot in zip(slots, s):
        np.testing.assert_array_equal(outs[got_slot], want[want_slot])
    # drain leaves exactly the leaked page allocated, and the shrunk
    # reservation bound still admits and drains cleanly (the leaked
    # page is never handed out again)
    assert sess.pages_in_use == 1 and sess._reserved_pages == 0
    worst = pa.pages_for(SEQ, 4)
    assert (sess._P - 1 - sess._leaked_pages) // worst >= 1
    sess.generate(trained["src"][:1], trained["src_len"][:1])
    assert sess.pages_in_use == 1 and sess.free_slots == 4


def test_generate_deferred_request_ordering_pinned(trained):
    """generate() serves requests strictly in row order even when the
    pool defers admissions (deque popleft/appendleft — the O(B^2)
    list shuffle is gone, the ordering contract stays)."""
    # pool covers ONE sequence at a time: every admission but the
    # in-flight one defers
    sess = _paged(trained, num_pages=1 + pa.pages_for(SEQ, 4))
    order = []
    orig_admit_pending = sess.admit_pending

    def spy_admit_pending():
        admitted = orig_admit_pending()  # request ids are row numbers
        assert len(admitted) <= 1, "the pool covers one sequence"
        order.extend(admitted.values())
        return admitted

    sess.admit_pending = spy_admit_pending
    out = sess.generate(trained["src"], trained["src_len"])
    np.testing.assert_array_equal(out, trained["want"])
    assert order == [0, 1, 2, 3], \
        "deferred requests were reordered: %r" % order


def test_cross_kv_pool_scales_with_groups_not_slots(trained):
    """The cross-attention K/V pool is [G, H, T, dh]: sizing groups
    below slots shrinks the live scope buffers (the HBM ledger counts
    them once, at group size), and grid_accounting models the same
    contract. Group exhaustion is a typed reject and generate()
    defers through it."""
    sess = _paged(trained, num_groups=2)
    kc = np.asarray(trained["scope"].get_value("pgd_kcross_0"))
    assert kc.shape == (2, 2, SEQ, D // 2)  # [G, H, T, dh], G=2 < S=4
    acc = pa.grid_accounting([SEQ] * 4, 4, 2, D // 2, SEQ,
                             num_groups=2, n_layer=2)
    assert acc["cross_hbm_bytes"] == 2 * 2 * 2 * 2 * SEQ * (D // 2) * 4
    assert acc["cross_hbm_bytes"] * 2 == acc["cross_dense_hbm_bytes"]
    # one fork pair + one solo fill both groups (3 of 4 slots)...
    a = sess.admit_group(trained["src"][0], 2,
                         src_len=trained["src_len"][0])
    b = sess.admit(trained["src"][2], trained["src_len"][2])
    assert sess.free_groups == 0 and sess.free_slots == 1
    # ...and a third SOURCE is a typed reject (a slot is still free —
    # it's the group pool that's exhausted) until a group drains
    with pytest.raises(NoFreeGroupError):
        sess.admit(trained["src"][1], trained["src_len"][1])
    outs = {}
    while len(outs) < 3:
        outs.update(sess.step())
    for slot in a:
        np.testing.assert_array_equal(outs[slot], trained["want"][0])
    np.testing.assert_array_equal(outs[b], trained["want"][2])
    assert sess.free_groups == 2
    # generate() defers through group exhaustion and stays ordered
    out = sess.generate(trained["src"], trained["src_len"])
    np.testing.assert_array_equal(out, trained["want"])


def test_pool_reservation_respects_group_size(trained):
    """admit_group reserves n x worst-case pages up front: a pool
    sized for one sequence rejects a fork pair atomically (no partial
    group ever lands), and counts are untouched by the reject."""
    sess = _paged(trained, num_pages=1 + pa.pages_for(SEQ, 4))
    with pytest.raises(NoFreePageError):
        sess.admit_group(trained["src"][0], 2)
    assert sess.free_slots == 4 and sess.free_groups == 4
    assert sess._reserved_pages == 0 and sess.pages_in_use == 0
    # a solo admission still fits and decodes clean
    out = sess.generate(trained["src"][:1], trained["src_len"][:1])
    np.testing.assert_array_equal(out, trained["want"][:1])
