"""Speculative decoding over the paged slot pool (PR 16): the tree
verify kernel, the drafters, and the end-to-end session.

* interpret-mode Pallas ``paged_tree_attention`` == composed reference
  at ragged/non-page-multiple base lengths, branched ancestor masks,
  empty and dead slots, and max-length clipping — and a Pallas failure
  trips the once-per-process reference fallback;
* the ``FLAGS_speculative`` on/off ORACLE: the same session streams
  BIT-identical tokens with speculation on and off, greedy AND seeded
  top-k (the drafter only ever moves throughput, never content), and
  the speculative path matches the dense slot decoder;
* a second batch through the warm speculative session adds ZERO fresh
  compiles — drafting/accept churn stays on the two cached executables;
* ``NgramDrafter`` is deterministic in the history and a state_dict
  round-trip re-proposes identically (the snapshot contract);
* ``chain_tree`` / ``tree_from_parents`` build the visibility masks the
  kernel contract requires (and reject malformed trees loudly).
"""

import re

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags
from paddle_tpu.core import exec_cache
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.observability import REGISTRY
from paddle_tpu.serving.generation import Sampler, SlotDecodeSession
from paddle_tpu.serving.speculative import (
    NgramDrafter,
    chain_tree,
    tree_from_parents,
)

VOCAB, SEQ, D = 24, 8, 32
CFG = dict(src_vocab_size=VOCAB, trg_vocab_size=VOCAB, n_layer=1,
           n_head=2, d_inner=64)


# -- tree masks --------------------------------------------------------------

def test_chain_tree_and_tree_from_parents():
    parent, anc = chain_tree(3)
    np.testing.assert_array_equal(parent, [-1, 0, 1, 2])
    np.testing.assert_array_equal(anc, np.tril(np.ones((4, 4))))
    # a branched tree: node's root path only, diagonal included
    anc = tree_from_parents([-1, 0, 0, 1])
    np.testing.assert_array_equal(anc, [[1, 0, 0, 0],
                                        [1, 1, 0, 0],
                                        [1, 0, 1, 0],
                                        [1, 1, 0, 1]])
    with pytest.raises(ValueError, match="anchor"):
        tree_from_parents([0, 0])
    with pytest.raises(ValueError, match="precede"):
        tree_from_parents([-1, 2, 1])


# -- kernel ------------------------------------------------------------------

def _pools(rng, S, H, dh, ps, npp, lengths):
    """Random pools + ragged table, page 0 reserved as trash (mirrors
    test_paged_attention)."""
    P = 1 + S * npp
    kp = rng.randn(P, ps, H * dh).astype("float32")
    vp = rng.randn(P, ps, H * dh).astype("float32")
    table = np.zeros((S, npp), np.int32)
    nxt = 1
    for s in range(S):
        n = pa.pages_for(max(int(lengths[s]), 1), ps)
        for p in range(n):
            table[s, p] = nxt
            nxt += 1
        for p in range(n, npp):
            table[s, p] = table[s, max(n - 1, 0)]
    return kp, vp, table


def _tree_case(seed=9):
    """S=5 ragged verify batch: off-grid base, empty slot, a base whose
    tree straddles max_length (tail rows trash-routed), and a DEAD slot
    (base -1); chain and branched ancestor masks mixed."""
    import jax.numpy as jnp

    S, H, dh, ps, npp, N = 5, 2, 16, 4, 8, 4
    base = np.array([7, 0, 25, 30, -1], np.int32)
    rng = np.random.RandomState(seed)
    q = rng.randn(S, H, N, dh).astype("float32")
    kp, vp, table = _pools(rng, S, H, dh, ps, npp,
                           np.minimum(np.maximum(base, 0) + N,
                                      npp * ps))
    anc = np.stack([
        chain_tree(N - 1)[1],
        tree_from_parents([-1, 0, 0, 1]),
        tree_from_parents([-1, 0, 1, 1]),
        chain_tree(N - 1)[1],
        tree_from_parents([-1, 0, 0, 0]),
    ]).astype("int64")
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table), jnp.asarray(base), jnp.asarray(anc))
    return args, dict(max_length=npp * ps)


def test_tree_kernel_parity_ragged_lengths():
    args, kw = _tree_case()
    ref = np.asarray(pa.paged_tree_attention_reference(*args, **kw))
    ker = np.asarray(pa.paged_tree_attention(*args, force_pallas=True,
                                             **kw))
    assert np.isfinite(ker).all()
    np.testing.assert_allclose(ker, ref, rtol=2e-6, atol=2e-6)
    # the dead slot is exactly zero from both paths, never NaN bait
    assert np.abs(ker[4]).max() == 0.0 and np.abs(ref[4]).max() == 0.0
    # the empty slot's anchor row sees only itself -> its own V row
    assert np.abs(ker[1]).max() > 0.0


def test_tree_kernel_branch_isolation():
    """Two sibling branches never see each other: zeroing a sibling's
    K/V rows must not change a node's output (only its root path is
    visible), while zeroing an ANCESTOR row must."""
    import jax.numpy as jnp

    args, kw = _tree_case(seed=11)
    q, kp, vp, table, base, anc = args
    out = np.asarray(pa.paged_tree_attention_reference(*args, **kw))
    # slot 1 (base 0, tree [-1,0,0,1]): node 2's sibling branch is
    # nodes 1 and 3; its rows live at storage 1 and 3 of page
    # table[1, 0]
    pg = int(np.asarray(table)[1, 0])
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    for row in (1, 3):
        kp2[pg, row] = 0.0
        vp2[pg, row] = 0.0
    out2 = np.asarray(pa.paged_tree_attention_reference(
        q, jnp.asarray(kp2), jnp.asarray(vp2), table, base, anc, **kw))
    np.testing.assert_allclose(out2[1, :, 2], out[1, :, 2],
                               rtol=1e-6, atol=1e-6)
    # zeroing its ANCHOR (ancestor, row 0) does move node 2
    kp3, vp3 = np.asarray(kp).copy(), np.asarray(vp).copy()
    kp3[pg, 0] = 0.0
    vp3[pg, 0] = 0.0
    out3 = np.asarray(pa.paged_tree_attention_reference(
        q, jnp.asarray(kp3), jnp.asarray(vp3), table, base, anc, **kw))
    assert np.abs(out3[1, :, 2] - out[1, :, 2]).max() > 1e-4


def _per_head(pool, H):
    """A whole-token-row pool as the old per-head ``[P, H, ps, dh]``."""
    P, ps, width = pool.shape
    return np.ascontiguousarray(
        pool.reshape(P, ps, H, width // H).transpose(0, 2, 1, 3))


def _tree_oracle_per_head(q, kp_old, vp_old, table, base, anc, max_length):
    """Plain numpy tree verify over per-head pools: node ``n`` of a slot
    sees its committed rows and the in-budget tree rows on its own root
    path; a node that sees nothing is 0."""
    S, H, N, dh = q.shape
    out = np.zeros((S, H, N, dh), "float64")
    for s in range(S):
        b = int(base[s])
        if b < 0:
            continue
        for h in range(H):
            k = np.concatenate([kp_old[p, h] for p in table[s]])
            v = np.concatenate([vp_old[p, h] for p in table[s]])
            for n in range(N):
                rows = list(range(b)) + [
                    b + j for j in range(N)
                    if anc[s, n, j] and b + j < max_length]
                if not rows:
                    continue
                sc = (k[rows].astype("float64")
                      @ q[s, h, n].astype("float64")) * dh ** -0.5
                w = np.exp(sc - sc.max())
                out[s, h, n] = (w / w.sum()) @ v[rows].astype("float64")
    return out


@pytest.mark.parametrize("H,dh,ps,npp,N,base", [
    (2, 16, 4, 8, 4, [7, 0, 25, 30, -1]),
    (8, 64, 16, 3, 5, [0, 15, 16, 44, -1, 47]),
    (4, 128, 8, 2, 8, [8, -1, 3]),
    (16, 8, 8, 2, 3, [13, 15]),
], ids=["2x16", "served_8x64", "dh_128", "16_heads"])
def test_tree_kernel_reference_and_per_head_oracle_agree(H, dh, ps, npp, N,
                                                         base):
    """The interpret-mode tree kernel and the composed reference on
    whole token rows against a numpy oracle on the same pool transposed
    to per-head pages: bases off the page grid and on a page boundary, a
    tree that straddles ``max_length``, a dead slot (exactly 0)."""
    import jax.numpy as jnp

    S = len(base)
    base = np.asarray(base, np.int32)
    L = npp * ps
    rng = np.random.RandomState(N * 17 + H)
    q = rng.randn(S, H, N, dh).astype("float32")
    kp, vp, table = _pools(rng, S, H, dh, ps, npp,
                           np.minimum(np.maximum(base, 0) + N, L))
    parents = [-1] + [int(rng.randint(0, j)) for j in range(1, N)]
    anc = np.stack([tree_from_parents(parents) if s % 2 else
                    chain_tree(N - 1)[1] for s in range(S)]).astype("int64")
    want = _tree_oracle_per_head(q, _per_head(kp, H), _per_head(vp, H),
                                 table, base, anc, L)
    args = [jnp.asarray(x) for x in (q, kp, vp, table, base, anc)]
    ref = np.asarray(pa.paged_tree_attention(
        *args, max_length=L, force_reference=True))
    ker = np.asarray(pa.paged_tree_attention(
        *args, max_length=L, force_pallas=True))
    np.testing.assert_allclose(ref, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(ker, want, rtol=2e-5, atol=2e-6)
    for s in np.flatnonzero(base < 0):
        assert np.abs(ref[s]).max() == 0.0 and np.abs(ker[s]).max() == 0.0


@pytest.mark.parametrize("pos", [0, 3, 5, 13], ids=lambda p: "pos_%d" % p)
def test_tree_write_and_compact_match_per_head(pos):
    """``paged_kv_write_block`` lands N rows a slot where the per-head
    write put them (a block that crosses a page boundary, one that runs
    past the table's coverage onto the trash page), and
    ``paged_kv_compact`` moves the accepted path's rows to their
    canonical positions reading the pre-compaction pool."""
    import jax.numpy as jnp

    S, H, dh, ps, npp, N = 2, 2, 4, 4, 4, 4
    L = npp * ps
    rng = np.random.RandomState(pos)
    kp, vp, table = _pools(rng, S, H, dh, ps, npp, [L, L])
    knew = rng.randn(S, H, N, dh).astype("float32")
    vnew = rng.randn(S, H, N, dh).astype("float32")
    base = np.array([pos, 1], np.int32)
    positions = base[:, None] + np.arange(N)[None, :]
    k2, v2 = pa.paged_kv_write_block(
        *[jnp.asarray(x) for x in (kp, vp, knew, vnew, table, positions)])
    want_k, want_v = _per_head(kp, H), _per_head(vp, H)
    for s in range(S):
        for i in range(N):
            t = positions[s, i]
            page, off = (table[s, t // ps], t % ps) if t < L else (0, 0)
            want_k[page, :, off, :] = knew[s, :, i]
            want_v[page, :, off, :] = vnew[s, :, i]
    np.testing.assert_array_equal(_per_head(np.asarray(k2), H)[1:],
                                  want_k[1:])
    np.testing.assert_array_equal(_per_head(np.asarray(v2), H)[1:],
                                  want_v[1:])
    # accept 3 tokens a slot along the path anchor -> node 2 -> node 3
    path = np.array([[0, 2, 3, 3], [0, 2, 3, 3]], np.int32)
    k3, v3 = pa.paged_kv_compact(
        k2, v2, jnp.asarray(table), jnp.asarray(base), jnp.asarray(path),
        jnp.asarray([3, 3], np.int32))
    want_k3, want_v3 = want_k.copy(), want_v.copy()
    for s in range(S):
        for j in (1, 2):
            src, dst = base[s] + path[s, j], base[s] + j
            if src >= L or dst >= L:
                continue
            sp, dp = table[s, src // ps], table[s, dst // ps]
            want_k3[dp, :, dst % ps, :] = want_k[sp, :, src % ps, :]
            want_v3[dp, :, dst % ps, :] = want_v[sp, :, src % ps, :]
    np.testing.assert_array_equal(_per_head(np.asarray(k3), H)[1:],
                                  want_k3[1:])
    np.testing.assert_array_equal(_per_head(np.asarray(v3), H)[1:],
                                  want_v3[1:])


def test_tree_kernel_failure_propagates_typed(monkeypatch):
    """No trip to the reference any more: a refused tree kernel
    propagates, typed, naming the kernel and its operand shapes."""
    args, kw = _tree_case()
    calls = {"n": 0}

    def boom(*a, **k):
        calls["n"] += 1
        raise RuntimeError("pallas toolchain exploded")

    monkeypatch.setattr(pa, "_tree_pallas", boom)
    for attempt in (1, 2):
        with pytest.raises(pa.KernelCompileError) as err:
            pa.paged_tree_attention(*args, force_pallas=True, **kw)
        assert calls["n"] == attempt  # nothing remembers the failure
        assert err.value.kernel == pa.TREE_KERNEL_NAME
        assert (tuple(args[0].shape), str(args[0].dtype)) \
            in err.value.shapes
        assert "pallas toolchain exploded" in str(err.value)
    np.asarray(pa.paged_tree_attention(*args, force_reference=True,
                                       **kw))
    assert calls["n"] == 2
    assert "paddle_tpu_kernel_fallbacks_total" not in \
        REGISTRY.to_prometheus()


# -- drafters ----------------------------------------------------------------

def test_ngram_drafter_is_deterministic_and_restores():
    d = NgramDrafter(num_slots=4, k=3, eos_id=2, order=3)
    states = {
        0: {"trg": np.array([1, 5, 6, 5, 6, 0, 0, 0]), "pos": 4},
        2: {"trg": np.array([1, 3, 3, 3, 3, 0, 0, 0]), "pos": 4},
    }
    a = d.propose(states)
    np.testing.assert_array_equal(a, d.propose(states))  # pure lookup
    # slot 0: suffix (5, 6) recurs at position 1 -> continuation (5, 6)
    np.testing.assert_array_equal(a[0], [5, 6, 2])
    # slot 2: suffix (3, 3, 3) recurs -> continuation (3,), eos-padded
    np.testing.assert_array_equal(a[2], [3, 2, 2])
    # slots not live propose pure eos (a free reject)
    assert (a[1] == 2).all() and (a[3] == 2).all()
    # the snapshot contract: a fresh drafter with the restored state
    # re-proposes identically (the lookup state IS the history)
    d2 = NgramDrafter(num_slots=4, k=3, eos_id=2, order=1)
    d2.load_state_dict(d.state_dict())
    np.testing.assert_array_equal(d2.propose(states), a)
    d.forget(0)  # stateless no-op, must not disturb proposals
    np.testing.assert_array_equal(d.propose(states), a)


# -- session: the on/off oracle ----------------------------------------------

@pytest.fixture(scope="module")
def trained(request):
    """One tiny trained transformer (copy task, so the n-gram drafter
    actually gets acceptances) + the dense slot decoder's greedy tokens
    as the cross-architecture oracle."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 21
    startup.random_seed = 21
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
    rng = np.random.RandomState(22)
    for _ in range(30):
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
    src = rng.randint(3, VOCAB, (3, SEQ)).astype("int64")
    src_len = np.asarray([[SEQ], [SEQ - 3], [SEQ - 1]], "int64")
    dense = SlotDecodeSession(exe, num_slots=3, max_length=SEQ,
                              d_model=D, scope=scope, **CFG)
    want = dense.generate(src, src_len)
    return {"exe": exe, "scope": scope, "src": src, "src_len": src_len,
            "want": want}


def _spec_session(trained, **kw):
    args = dict(num_slots=3, max_length=SEQ, d_model=D, paged=True,
                page_size=4, steps=1,
                speculative={"k": 3, "drafter": "ngram"},
                scope=trained["scope"])
    args.update(CFG)
    args.update(kw)
    return SlotDecodeSession(trained["exe"], **args)


@pytest.fixture(autouse=True)
def _speculative_flag_restored():
    old = flags.get("speculative")
    yield
    flags.set_flag("speculative", old)


def test_greedy_stream_is_bit_identical_to_off_oracle(trained):
    """THE tentpole contract: the same session decodes the same batch
    with speculation on and off and the streams are BIT-identical —
    and both equal the dense slot decoder (a third architecture)."""
    sess = _spec_session(trained)
    flags.set_flag("speculative", "on")
    on = sess.generate(trained["src"], trained["src_len"])
    assert sess.spec_dispatches > 0 and sess.spec_proposed > 0
    assert sess.spec_accepted > 0, \
        "drafter never landed a token on a trained copy task"
    assert sess.pages_in_use == 0  # spec churn recycled everything
    # the acceptance telemetry is published, and counts what was proposed
    text = REGISTRY.to_prometheus()
    m = re.search(r"^paddle_tpu_serving_speculative_proposed_tokens_total "
                  r"(\d+)", text, re.MULTILINE)
    assert m and int(m.group(1)) >= sess.spec_proposed
    assert "paddle_tpu_serving_speculative_accepted_tokens_total" in text
    assert "paddle_tpu_serving_speculative_acceptance_rate" in text
    flags.set_flag("speculative", "off")
    off = sess.generate(trained["src"], trained["src_len"])
    np.testing.assert_array_equal(on, off)
    np.testing.assert_array_equal(on, trained["want"])


def test_sampled_stream_is_bit_identical_to_off_oracle(trained):
    """Seeded top-k sampling under speculation: accepted tokens are
    re-sampled from TARGET logits with (seed, slot, position) keys, so
    the stream is bit-identical to the sequential path's."""
    sess = _spec_session(
        trained, sampler=Sampler(strategy="top_k", top_k=4,
                                 temperature=0.8, seed=11))
    flags.set_flag("speculative", "on")
    on = sess.generate(trained["src"], trained["src_len"])
    flags.set_flag("speculative", "off")
    off = sess.generate(trained["src"], trained["src_len"])
    np.testing.assert_array_equal(on, off)
    assert (on[:, 0] == 1).all()  # bos leads every row


def test_warm_speculative_rerun_compiles_nothing_fresh(trained):
    """A second batch through the warm speculative session — drafting,
    accepts, rejects, admissions, releases — adds ZERO fresh compiles:
    the decode hot path is the ONE cached verify executable (plus the
    warm admit/table programs)."""
    flags.set_flag("speculative", "on")
    sess = _spec_session(trained)
    first = sess.generate(trained["src"], trained["src_len"])
    before = exec_cache.stats()["fresh_compiles"]
    again = sess.generate(trained["src"], trained["src_len"])
    np.testing.assert_array_equal(again, first)
    assert exec_cache.stats()["fresh_compiles"] == before, (
        "warm speculative decode paid fresh compiles")
    assert sess.spec_dispatches > 0


def test_speculative_composes_with_fork_groups(trained):
    """COW isolation under speculation: two forked continuations of one
    admitted prefix decode to the SAME tokens as two independent
    admissions (greedy), with all pages recycled after."""
    sess = _spec_session(trained, num_groups=2)
    flags.set_flag("speculative", "on")
    src = trained["src"][0]
    slots = sess.admit_group(src, n=2, src_len=SEQ)
    done = {}
    for _ in range(40):
        done.update(sess.step())
        if len(done) >= len(slots):
            break
    flags.set_flag("speculative", "off")
    want = sess.generate(trained["src"][:1], trained["src_len"][:1])
    for slot in slots:
        np.testing.assert_array_equal(done[slot], want[0])
    assert sess.pages_in_use == 0
