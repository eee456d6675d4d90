"""The latent-attention decoder under LEARNED SPARSE attention, holding a
shard of its experts, tiny on the CPU (``index_topk`` 8 so that selection
bites within a few dozen positions; hidden 64, 4 heads of 12+4 / 16, an
indexer of 2 heads of 8, 4 of 16 experts held, top 4): the kernels against
their references, prefill then decode through both pools of
``DecoderOnlySession`` against the plain reference's full forward
(logits), a slot crossing ``index_topk`` while it decodes, a ``shared``
layer reading the ``full`` layer's choice, the parts that all the shards
give adding up to the uncut layer, the descriptions the builder refuses,
and the step program compiled for a described v5e."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.kernels import sparse_latent_attention as sla  # noqa: E402
from paddle_tpu.models import latent_moe_decoder as lmd  # noqa: E402
from paddle_tpu.ops import decoder_ops, moe_ops  # noqa: E402
from paddle_tpu.serving.decoder_session import (  # noqa: E402
    DecoderOnlySession,
    builder_for,
)
from perfbench import weights_glm52  # noqa: E402
from perfbench.reference import sparse_latent_moe_decoder as ref  # noqa: E402

DESC = dict(hidden_size=64, num_attention_heads=4, qk_nope_head_dim=12,
            qk_rope_head_dim=4, v_head_dim=16, q_lora_rank=24,
            kv_lora_rank=16, intermediate_size=320,
            moe_intermediate_size=48, n_routed_experts=4,
            expert_shard={"of": 16, "first": 0}, num_experts_per_tok=4,
            n_shared_experts=1, first_k_dense_replace=1,
            num_hidden_layers=4, vocab_size=512, rms_norm_eps=1e-5,
            rope_parameters={"rope_theta": 1e6},
            routed_scaling_factor=2.5, norm_topk_prob=True, n_group=1,
            topk_group=1, rope_interleave=True,
            indexer_rope_interleave=True, index_topk=8, index_n_heads=2,
            index_head_dim=8, index_topk_pattern=None,
            indexer_types=["full", "shared", "shared", "full"],
            mlp_layer_types=["dense", "sparse", "sparse", "sparse"])
P = 12      # decoded positions compared


class Tap(object):
    """An executor that also fetches every dispatch's logits and the
    positions its indexers chose."""

    def __init__(self, exe, fetches):
        self._exe, self._f = exe, fetches
        self.prefill, self.steps = [], []

    def __getattr__(self, name):
        return getattr(self._exe, name)

    def run(self, program, feed=None, fetch_list=None, scope=None, **kw):
        if not feed or "prompt_ids" not in feed:
            return self._exe.run(program, feed=feed, fetch_list=fetch_list,
                                 scope=scope, **kw)
        out = self._exe.run(
            program, feed=feed, scope=scope,
            fetch_list=list(fetch_list) + [self._f["first_logits"]], **kw)
        self.prefill.append((feed, np.asarray(out[-1])))
        return out[:-1]

    def run_multi_step(self, program, steps, feed=None, fetch_list=None,
                       scope=None, **kw):
        out = self._exe.run_multi_step(
            program, steps, feed=feed, scope=scope,
            fetch_list=list(fetch_list) + [self._f["logits"],
                                           self._f["selected"]], **kw)
        self.steps.append((np.asarray(out[-2]), np.asarray(out[-1])))
        return out[:-2]


def make_session(desc=DESC, seed=3, **kw):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    params = lmd.random_parameters(desc, seed, "float32")
    lmd.load_parameters(scope, params, desc, "float32")
    args = dict(num_slots=4, max_prompt=32, max_new_tokens=16, page_size=8,
                tokens_per_dispatch=2, prefill_token_budget=64, scope=scope,
                dtype="float32")
    args.update(kw)
    sess = DecoderOnlySession(exe, desc, **args)
    sess._exe = Tap(exe, sess._fetch)
    return sess, weights_glm52.tree({k: jnp.asarray(v)
                                     for k, v in params.items()}, desc)


def rel(got, want):
    got, want = np.asarray(got, "float64"), np.asarray(want, "float64")
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def served(sess, lengths, seed=0):
    """Prompts of ``lengths`` admitted together and ``P`` positions
    decoded: {request: (slot, tokens fed, logits [P + 1, V])}."""
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(3, DESC["vocab_size"], n) for n in lengths]
    for p in prompts:
        sess.enqueue(p)
    admitted = sess.admit_pending()
    slots = {rid: slot for slot, rid in admitted.items()}
    for _ in range(P // 2):
        assert sess.step() == {}
    steps = np.concatenate([s[0] for s in sess._exe.steps])  # [P, S, 1, V]
    out = {}
    for rid, prompt in enumerate(prompts):
        slot = slots[rid]
        feed, first = next(p for p in sess._exe.prefill
                           if slot in list(p[0]["slot_idx"]))
        row = list(feed["slot_idx"]).index(slot)
        toks = sess.tokens_of(slot)
        out[rid] = (slot, np.concatenate([prompt, toks[:-1]]),
                    np.concatenate([first[row], steps[:, slot, 0]]), toks)
    return out


# -- the kernels against their references -------------------------------------

def _pools(rng, S=3, npp=6, ps=8, dI=16, W=128):
    n_pages = 1 + S * npp
    table = jnp.asarray(
        rng.permutation(n_pages - 1)[:S * npp].reshape(S, npp) + 1,
        jnp.int32)
    return (jnp.asarray(rng.standard_normal((n_pages, ps, dI)), jnp.float32),
            jnp.asarray(rng.standard_normal((n_pages, ps, W)), jnp.float32),
            table, jnp.asarray([5, 48, 17], jnp.int32))


def test_index_score_kernel_matches_its_reference():
    rng = np.random.RandomState(0)
    keys, _rows, table, lengths = _pools(rng)
    q = jnp.asarray(rng.standard_normal((3, 4, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 4)), jnp.float32)
    got = np.asarray(sla.index_score_decode(q, w, keys, table, lengths,
                                            force_pallas=True))
    want = np.asarray(sla.index_score_decode_reference(q, w, keys, table,
                                                       lengths))
    live = np.arange(48)[None, :] < np.asarray(lengths)[:, None]
    assert (np.isfinite(got) == live).all()
    assert (np.isfinite(want) == live).all()
    assert rel(got[live], want[live]) < 1e-6
    # the choice: the largest first, nothing past a slot's length
    chosen = np.asarray(sla.index_select(jnp.asarray(want), 8))
    assert (chosen[0, :5] >= 0).all() and (chosen[0, 5:] == -1).all()
    assert sorted(chosen[0, :5]) == [0, 1, 2, 3, 4]
    for s in (1, 2):
        assert (chosen[s] == np.argsort(-want[s], kind="stable")[:8]).all()


def test_sparse_decode_attention_reads_the_chosen_rows_alone():
    rng = np.random.RandomState(1)
    _keys, rows, table, _lengths = _pools(rng)
    rows = rows.at[..., 20:].set(0.0)          # a 16 + 4 wide row
    chosen = jnp.asarray([[3, 0, 4, -1, -1, -1, -1, -1],
                          [47, 43, 36, 38, 44, 35, 17, 3],
                          [-1] * 8], jnp.int32)
    q_lat = jnp.asarray(rng.standard_normal((3, 4, 16)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((3, 4, 4)), jnp.float32)
    kernel = sla.sparse_latent_decode_attention(
        q_lat, q_rope, rows, table, chosen, 0.25, force_pallas=True)
    composed = sla.sparse_latent_decode_attention(
        q_lat, q_rope, rows, table, chosen, 0.25, force_reference=True)
    assert rel(kernel, composed) < 1e-6
    flat = np.asarray(rows[table]).reshape(3, 48, -1)
    for s in (0, 1):
        at = np.asarray(chosen[s])
        r = flat[s][at[at >= 0]]
        sc = (np.asarray(q_lat[s]) @ r[:, :16].T
              + np.asarray(q_rope[s]) @ r[:, 16:20].T) * 0.25
        p = np.exp(sc - sc.max(-1, keepdims=True))
        assert rel(composed[s], p / p.sum(-1, keepdims=True) @ r[:, :16]) \
            < 1e-6
    assert not np.asarray(kernel[2]).any()     # nothing chosen: exactly 0


def test_prefill_choice_is_the_exact_top_k_of_every_row():
    rng = np.random.RandomState(2)
    B, T, J, dI, k = 2, 32, 4, 16, 8
    q = jnp.asarray(rng.standard_normal((B, T, J, dI)), jnp.float32)
    keys = jnp.asarray(rng.standard_normal((B, T, dI)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((B, T, J)), jnp.float32)
    mask = np.asarray(sla.index_select_prefill(q, w, keys, k, block=8))
    for b in range(B):
        scores = np.asarray(sla.index_scores_block(q[b], w[b], keys[b]))
        for t in range(T):
            want = np.zeros(T, bool)
            want[sorted(range(t + 1),
                        key=lambda s: (-scores[t, s], s))[:k]] = True
            assert (mask[b, t].astype(bool) == want).all(), (b, t)
    # a block of queries past its prompt's length chooses nothing
    cut = np.asarray(sla.index_select_prefill(
        q, w, keys, k, lengths=jnp.asarray([11, 32]), block=8))
    assert (cut[0, :16] == mask[0, :16]).all() and not cut[0, 16:].any()
    assert (cut[1] == mask[1]).all()
    # ties go to the lower position, and exactly k are taken
    tied = jnp.asarray([[1.0, 1, 1, 1, 0, 2, 1, 1]])
    assert np.asarray(sla.top_k_mask(tied, 3, jnp.ones((1, 8), bool))) \
        .tolist() == [[True, True, False, False, False, True, False, False]]


@pytest.mark.parametrize("masked", [True, False], ids=["masked", "causal"])
def test_prefill_attention_kernel_matches_its_reference(masked):
    rng = np.random.RandomState(3)
    B, T, H, d = 2, 32, 2, 16
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H * d)), jnp.float32)
               for _ in range(3))
    mask = None
    if masked:
        keep = np.tril(rng.rand(B, T, T) < 0.4)
        keep |= np.eye(T, dtype=bool)[None]
        mask = jnp.asarray(keep, jnp.int8)
    got = sla.sparse_latent_prefill_attention(q, k, v, mask, 0.25, H,
                                              block=8, force_pallas=True)
    want = sla.sparse_prefill_attention_reference(q, k, v, mask, 0.25, H)
    assert rel(got, want) < 1e-6
    # told the prompts' lengths it skips the tiles of the bucket's padding:
    # every row of a tile that holds a real one is as before, the rest 0
    cut = sla.sparse_latent_prefill_attention(
        q, k, v, mask, 0.25, H, lengths=jnp.asarray([11, 32]), block=8,
        force_pallas=True)
    assert rel(cut[0, :16], want[0, :16]) < 1e-6
    assert rel(cut[1], want[1]) < 1e-6
    assert not np.asarray(cut[0, 16:]).any()


def test_interleaved_rope_is_the_references():
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.standard_normal((7, 3, 8)), jnp.float32)
    pos = jnp.asarray([0, 1, 5, 17, 300, 4000, 16000])
    assert rel(decoder_ops.rope_interleaved(x, pos, 8e6),
               ref.rope(x, pos, 8e6)) < 1e-6
    # another pairing than the split halves, same norms
    assert rel(decoder_ops.rope_interleaved(x, pos, 8e6),
               decoder_ops.rope_rotate_half(x, pos, 8e6)) > 0.1
    assert np.allclose(
        np.linalg.norm(decoder_ops.rope_interleaved(x, pos, 8e6), axis=-1),
        np.linalg.norm(x, axis=-1), rtol=1e-5)


# -- the session ----------------------------------------------------------------

def test_prefill_then_decode_through_both_pools_match_the_reference():
    """4 prompts of different lengths (two in one bucket; one under
    ``index_topk``, one that crosses it while it decodes, two beyond it),
    then 12 decoded positions through the latent pools and the indexers'
    narrow pools: logits against the reference's full forward, and far
    from the reference WITHOUT the selection."""
    sess, tree = make_session()
    lengths = [3, 6, 11, 27]
    assert [sess.bucket_of(n) for n in lengths] == [8, 8, 16, 32]
    out = served(sess, lengths)
    assert sess.prefill_dispatches == 3
    for rid, n in enumerate(lengths):
        _slot, seq, got, toks = out[rid]
        at = list(range(n - 1, n + P))
        want = ref.forward(tree, jnp.asarray(seq), DESC, logits_at=at)
        assert rel(got, want["logits"]) < 2e-5
        assert (got.argmax(-1) == toks).all()
        dense = ref.forward(tree, jnp.asarray(seq), DESC, logits_at=at,
                            select=False)
        if n + P > DESC["index_topk"] + 1:
            assert rel(got, dense["logits"]) > 0.05
    geo = sess.geometry
    assert geo["index_topk"] == 8 and geo["index_layers"] == [0, 3]
    assert [p["shape"][-1] for p in geo["state"]["page_pools"].values()] \
        == [128] * 4 + [8] * 2
    assert sess.pool_conserved
    assert sess.pages_in_use == sum(-(-(n + P) // 8) for n in lengths)
    counters = sess.last_counters
    rows = sum(n + P - 1 for n in lengths)
    assert counters["latent_rows_resident"] == rows
    assert counters["latent_rows_selected"] == sum(
        min(n + P - 1, 8) for n in lengths)
    assert counters["index_pages_in_use"] == sess.pages_in_use
    assert counters["experts_routed_tokens"] == 2 * 3 * 4 * 4
    assert 0 < counters["experts_held_tokens"] \
        < counters["experts_routed_tokens"]
    assert 0 < counters["experts_held_hit"] <= 4


def test_a_dispatch_runs_the_least_rung_of_rows_that_holds_its_prompts():
    """With ``prefill_rungs`` a bucket has a program a power of two of
    prompt rows under its most (budget 64: 8, 4 and 2 prompts of 8, 16 and
    32): one prompt is walked as one row, three as four, and the logits
    are those of the session that walks the whole budget."""
    plain, tree = make_session()
    sess, _tree = make_session(prefill_rungs=True)
    assert plain.geometry["prefill_rungs"] == {8: [8], 16: [4], 32: [2]}
    assert sess.geometry["prefill_rungs"] == {
        8: [1, 2, 4, 8], 16: [1, 2, 4], 32: [1, 2]}
    assert sess.geometry["prompts_per_dispatch"] == {8: 8, 16: 4, 32: 2}
    lengths = [27, 5, 6, 7]
    want, got = served(plain, lengths), served(sess, lengths)
    places = lambda s: sorted(len(feed["prompt_ids"])  # noqa: E731
                              for feed, _first in s._exe.prefill)
    assert places(plain) == [64, 64] and places(sess) == [32, 32]
    assert sess.last_prefills == [(8, [5, 6, 7]), (32, [27])]
    for rid in range(len(lengths)):
        assert (got[rid][3] == want[rid][3]).all()
        assert rel(got[rid][2], want[rid][2]) < 2e-5
    n = lengths[0]
    ref_logits = ref.forward(tree, jnp.asarray(got[0][1]), DESC,
                             logits_at=list(range(n - 1, n + P)))["logits"]
    assert rel(got[0][2], ref_logits) < 2e-5
    from paddle_tpu.observability import tracing

    tracing.enable()
    try:
        rnd = tracing.round_begin()
        sess.cancel(got[0][0])
        sess.enqueue(np.arange(3, 12))
        sess.admit_pending()
        tracing.round_end(rnd, keep=False)
        assert rnd.spans[0]["prefill_pad_tokens"] == 16 - 9
    finally:
        tracing.enable(False)


def test_a_slot_crosses_index_topk_while_it_decodes():
    """A prompt of 5 tokens attends everything until its 9th position:
    the choice a step fetches holds all the slot's positions, then 8 of
    them, and the logits sit on the reference on both sides."""
    sess, tree = make_session()
    (_slot, seq, got, _toks), = served(sess, [5]).values()
    want = ref.forward(tree, jnp.asarray(seq), DESC,
                       logits_at=list(range(4, 5 + P)))
    assert rel(got, want["logits"]) < 2e-5
    slot = sess.active_slots[0]
    chosen = np.concatenate([s[1] for s in sess._exe.steps])[:, :, slot]
    for j in range(P):                      # step j: positions 0 .. 5 + j
        held = 5 + j + 1
        for layer in range(2):
            mine = chosen[j, layer]
            assert (mine >= 0).sum() == min(held, 8)
            if held <= 8:
                assert sorted(mine[mine >= 0]) == list(range(held))
            assert mine.max() < held


def test_a_shared_layer_reads_the_full_layers_choice():
    """With the ``shared`` layers given indexers of their own (every
    layer ``full``, the same parameters otherwise) the logits move: the
    layers behind a ``full`` one do use ITS choice. And the programs hold
    one indexer a ``full`` layer, none for a ``shared`` one."""
    sess, tree = make_session()
    (_slot, seq, got, _toks), = served(sess, [27]).values()
    names = lmd.parameter_shapes(DESC, "float32")
    assert [i for i in range(4) if "lmd_%d_idx_q" % i in names] == [0, 3]
    ops = [op.type for op in sess.step_program.global_block().ops]
    assert ops.count("index_select_decode") == 2
    assert ops.count("sparse_latent_paged_attention") == 4
    assert "latent_paged_attention" not in ops
    # the reference with layer 1 and 2 made ``full`` (they borrow layer
    # 3's indexer): another choice in them, other logits
    other = dict(tree, layers=[dict(p) for p in tree["layers"]])
    for i in (1, 2):
        other["layers"][i]["indexer"] = tree["layers"][3]["indexer"]
    at = list(range(26, 27 + P))
    moved = ref.forward(other, jnp.asarray(seq), DESC, logits_at=at)
    same = ref.forward(tree, jnp.asarray(seq), DESC, logits_at=at)
    assert rel(got, same["logits"]) < 2e-5
    assert rel(got, moved["logits"]) > 1e-3


def test_the_reference_follows_a_given_choice_of_positions():
    """``positions`` replaces the compared rows' own choice: given its own
    choice back the reference does not move; given another it does."""
    sess, tree = make_session()
    (_slot, seq, _got, _toks), = served(sess, [27]).values()
    at = np.arange(26, 27 + P)
    own = ref.forward(tree, jnp.asarray(seq), DESC, logits_at=at)
    sets = []
    for scores in own["index_scores"]:
        order = np.argsort(-np.asarray(scores), axis=-1, kind="stable")
        sets.append(order[:, :8])
    back = ref.forward(tree, jnp.asarray(seq), DESC, logits_at=at,
                       positions=sets)
    assert rel(back["logits"], own["logits"]) < 1e-6
    first = [np.tile(np.arange(8), (len(at), 1)) for _ in sets]
    moved = ref.forward(tree, jnp.asarray(seq), DESC, logits_at=at,
                        positions=first)
    assert rel(moved["logits"], own["logits"]) > 1e-3


# -- a shard of the experts -------------------------------------------------------

def test_the_shards_parts_add_up_to_the_uncut_layer():
    """The guide's share test: over all 4 shards of 4 experts, each
    shard's part of the routed sum (the op told which experts it holds)
    and the shared expert counted ONCE add up to the reference's uncut
    expert layer; a shard's counts are its own experts' tokens."""
    rng = np.random.RandomState(5)
    D, F, E, k, N = 64, 48, 16, 4, 21
    x = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    p = {"router": jnp.asarray(rng.standard_normal((D, E)) / 8, jnp.float32),
         "router_bias": jnp.asarray(rng.uniform(-0.01, 0.01, E),
                                    jnp.float32)}
    for name, shape in (("gate", (E, D, F)), ("up", (E, D, F)),
                        ("down", (E, F, D))):
        p[name] = jnp.asarray(
            rng.standard_normal(shape) * shape[1] ** -0.5, jnp.float32)
    for name, shape in (("shared_gate", (D, F)), ("shared_up", (D, F)),
                        ("shared_down", (F, D))):
        p[name] = jnp.asarray(
            rng.standard_normal(shape) * shape[0] ** -0.5, jnp.float32)
    d = dict(k=k, scale=2.5, norm_topk=True, first=0)
    uncut, _biased, own = ref.routed_part(p, x, d)
    uncut = uncut + ref.shared_part(p, x)

    chosen, weights = moe_ops.route_top_k(
        x, p["router"], p["router_bias"], k, True, 2.5)
    assert (np.sort(np.asarray(chosen), -1)
            == np.sort(np.asarray(own), -1)).all()
    total = np.asarray(ref.shared_part(p, x))
    tokens = []
    for first in range(0, E, 4):
        part, counts = moe_ops.dropless_experts(
            x, chosen, weights, p["gate"][first:first + 4],
            p["up"][first:first + 4], p["down"][first:first + 4],
            first=first)
        total = total + np.asarray(part)
        tokens += list(np.asarray(counts))
        # the reference's part of the same shard
        held = dict(p, gate=p["gate"][first:first + 4],
                    up=p["up"][first:first + 4],
                    down=p["down"][first:first + 4])
        want, _b, _o = ref.routed_part(held, x, dict(d, first=first))
        assert rel(part, want) < 1e-5
    assert rel(total, uncut) < 1e-5
    assert tokens == list(np.bincount(np.asarray(chosen).reshape(-1),
                                      minlength=E))
    assert sum(tokens) == N * k


def test_a_large_dispatch_goes_through_the_held_experts_in_blocks():
    """A held shard's dispatch of more than a block of tokens goes through
    in blocks: nothing changes but the size of the sorted copies; tokens
    that do not exist are still not counted."""
    rng = np.random.RandomState(6)
    N, D, F = 2 * moe_ops._HELD_TOKEN_BLOCK, 16, 8
    x = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    ins = {"X": [x],
           "RouterW": [jnp.asarray(rng.standard_normal((D, 16)) / 4,
                                   jnp.float32)],
           "RouterBias": [jnp.zeros((16,), jnp.float32)],
           "ExpertWGate": [jnp.asarray(rng.standard_normal((4, D, F)) / 4,
                                       jnp.float32)],
           "ExpertWUp": [jnp.asarray(rng.standard_normal((4, D, F)) / 4,
                                     jnp.float32)],
           "ExpertWDown": [jnp.asarray(rng.standard_normal((4, F, D)) / 3,
                                       jnp.float32)],
           "Valid": [jnp.asarray(np.arange(N) % 5 != 0, jnp.int32)]}
    attrs = dict(top_k=4, norm_topk=True, scale=2.5, held_first=4)
    blocks = moe_ops._lower_dropless_moe_ffn(None, ins, attrs)
    chosen, weights = moe_ops.route_top_k(
        x, ins["RouterW"][0], ins["RouterBias"][0], 4, True, 2.5)
    whole, counts = moe_ops.dropless_experts(
        x, chosen, weights, ins["ExpertWGate"][0], ins["ExpertWUp"][0],
        ins["ExpertWDown"][0], valid=ins["Valid"][0] > 0, first=4)
    assert rel(blocks["Out"], whole) < 1e-6
    assert (np.asarray(blocks["ExpertTokens"]) == np.asarray(counts)).all()
    live = np.asarray(blocks["Chosen"])[np.arange(N) % 5 != 0]
    assert list(np.asarray(counts)) == [
        int((live == e).sum()) for e in range(4, 8)]


# -- what the builder refuses -------------------------------------------------------

@pytest.mark.parametrize("key,value", [
    ("n_group", 2), ("index_topk_pattern", [2048, 1024]),
    ("indexer_types", ["shared", "full", "shared", "full"]),
    ("indexer_types", ["full", "shared"]),
    ("mlp_layer_types", ["dense", "sparse", "dense", "sparse"]),
    ("expert_shard", {"of": 16, "first": 14})],
    ids=["n_group", "index_topk_pattern", "starts_shared", "too_few",
         "mlp_layer_types", "expert_shard"])
def test_a_description_the_builder_does_not_serve_is_refused_by_its_key(
        key, value):
    assert builder_for(DESC) is lmd.build_latent_moe_decoder
    bad = dict(DESC, **{key: value})
    # the session's choice of a builder refuses what the keys alone show;
    # the sizes (a list's length, a shard's place) are the builder's
    early = key in ("n_group", "index_topk_pattern") or (
        key == "indexer_types" and value[0] == "shared")
    for refuse in (lmd.decoder_dims,) + ((builder_for,) if early else ()):
        with pytest.raises((NotImplementedError, ValueError)) as err:
            refuse(bad)
        assert key in str(err.value)


def test_the_dense_description_builds_what_it_built():
    """No ``index_topk``: no indexer parameter, no narrow pool, the dense
    kernel's op in the step, every expert held."""
    desc = {k: v for k, v in DESC.items()
            if not k.startswith("index") and k != "expert_shard"}
    assert not [n for n in lmd.parameter_shapes(desc) if "idx" in n]
    built = lmd.build_latent_moe_decoder(
        desc, 4, 48, 8, [8, 16, 32], prefill_token_budget=64,
        dtype="float32")
    ops = [op.type for op in built["step"].global_block().ops]
    assert ops.count("latent_paged_attention") == 4
    assert not [t for t in ops if "index" in t or "sparse" in t]
    assert built["fetches"]["selected"] is None
    geo = built["geometry"]
    assert geo["index_topk"] == 0 and geo["index_layers"] == []
    assert list(geo["state"]["page_pools"]) == [
        "lmd_pool_%d" % i for i in range(4)]
    assert geo["experts"] == {"held": 4, "of": 4, "top_k": 4}


# -- the step program on a described v5e ---------------------------------------------

def test_the_step_program_copies_no_pool_of_either_width():
    """The decode dispatch (4 token steps) at the published widths, two
    layers (``full`` then ``shared``), 16 slots of 2304 positions, compiled
    for a DESCRIBED v5e: Mosaic takes the three decode kernels, both
    widths of pool are updated in place (aliased, one layout each, no
    ``copy``, ``transpose`` or gather of a pool-sized array) and the
    temporaries stay far under one latent pool."""
    import re

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    from _described_compile import compile_program, session_shapes
    from test_tpu_lowering import _assert_moves_no_pool

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        device = list(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices)[0]
    except Exception as exc:
        pytest.skip("cannot describe a v5e topology here: %s" % exc)
    desc = dict(
        hidden_size=6144, num_attention_heads=64, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, q_lora_rank=2048,
        kv_lora_rank=512, intermediate_size=12288,
        moe_intermediate_size=2048, n_routed_experts=2,
        expert_shard={"of": 256, "first": 0}, num_experts_per_tok=8,
        n_shared_experts=1, first_k_dense_replace=1, num_hidden_layers=2,
        vocab_size=1024, rms_norm_eps=1e-5,
        rope_parameters={"rope_theta": 8e6}, routed_scaling_factor=2.5,
        norm_topk_prob=True, rope_interleave=True, index_topk=2048,
        index_n_heads=32, index_head_dim=128,
        indexer_types=["full", "shared"],
        mlp_layer_types=["dense", "sparse"])
    S, ps = 16, 128
    built = lmd.build_latent_moe_decoder(
        desc, S, 2304, ps, [1024, 2048], prefill_token_budget=2048,
        dtype="bfloat16", tokens_per_dispatch=4)
    geo = built["geometry"]
    state = session_shapes(built, lmd.parameter_shapes(desc, "bfloat16"), S)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = compile_program(
            built["step"], device, state,
            {"page_table": ((S, geo["pages_per_slot"]), "int64"),
             "live": ((S, 1), "int64")},
            [built["fetches"]["token"], built["fetches"]["expert_tokens"]],
            steps=4)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    for kernel, calls in ((sla.INDEX_SCORE_KERNEL_NAME, 1),
                          (sla.SPARSE_DECODE_KERNEL_NAME, 2)):
        assert len(re.findall(r"%%%s[.\d]* = " % kernel, text)) == calls
    n_pages = geo["num_pages"]
    latent, narrow = n_pages * ps * 640, n_pages * ps * 128
    # nothing as large as a latent pool is moved (the gather of the chosen
    # rows, [slots, index_topk, 640], is smaller and is the algorithm's),
    # and no array of the narrow pool's shape is copied either
    _assert_moves_no_pool(text, latent)
    assert not re.findall(
        r"= \w+\[%d,%d,128\]\S* (?:copy|transpose|gather)\(" % (n_pages, ps),
        text)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * (2 * latent + narrow)
    # the chosen rows laid out for the kernel (21 MB a layer) and a layer's
    # re-laid kv_b (29 MB): no pool's worth (at the served 24 slots and 5
    # layers the described compile read 0.17 GB beside 2.89 GB of pools)
    assert memory.temp_size_in_bytes < 128 * 2 ** 20
    for width in (640, 128):
        layouts = set(re.findall(
            r"\[%d,%d,%d\]\{([\d,]+)" % (n_pages, ps, width), text))
        assert layouts == {"2,1,0"}, (width, layouts)
