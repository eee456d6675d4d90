"""The delta-rule linear-attention / grouped-query decoder with routed
experts, tiny on the CPU (hidden 64, one attention layer of 4 query heads
on 2 key/value heads then three linear layers of 4 heads of 16, 4 of 8
experts held, vocabulary 512): each new kernel in interpret mode against
its composed reference, prefill of prompts of different lengths in one
bucket dispatch and then decode through ``DecoderOnlySession`` against the
plain reference's full forward (logits AND the matrix state), slots
leaving and being reused, a dead slot under the live mask, the round's
counters, the refusals of ``builder_for`` and the guide's share test: what
all 8 shards give adds up to the uncut layer."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.kernels import delta_rule as dr  # noqa: E402
from paddle_tpu.models import linear_attn_moe_decoder as lad  # noqa: E402
from paddle_tpu.observability import tracing  # noqa: E402
from paddle_tpu.serving.decoder_session import (  # noqa: E402
    DecoderOnlySession,
    builder_for,
)
from paddle_tpu.serving.server import ServingError  # noqa: E402
from perfbench import weights_solar  # noqa: E402
from perfbench.reference import linear_attn_moe_decoder as ref  # noqa: E402

DESC = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, linear_attn_config=dict(
        short_conv_kernel_size=4, head_dim=16, num_heads=4,
        num_kv_heads=None),
    num_hidden_layers=4, gqa_layers=[0], vocab_size=512,
    moe_intermediate_size=32, n_routed_experts=4,
    expert_shard={"of": 8, "first": 2}, n_shared_experts=1,
    num_experts_per_tok=2, norm_topk_prob=True, routed_scaling_factor=1.0,
    rms_norm_eps=1e-5, first_k_dense_replace=0, use_rope=False,
    use_gqa_gate=True, kda_use_full_proj=False, kda_allow_neg_eigval=True,
    tie_word_embeddings=False)
LINEAR = [i for i, k in enumerate(lad.layer_kinds(DESC)) if k == lad.LINEAR]


class Tap(object):
    """An executor that also fetches the logits and the choice of experts
    of every dispatch (what the benchmark's check does on the chip)."""

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
            program, feed=feed, scope=scope, fetch_list=list(fetch_list) + [
                self._f["first_logits"], self._f["first_chosen"]], **kw)
        self.prefill.append((feed, np.asarray(out[-2]), np.asarray(out[-1])))
        return out[:-2]

    def run_multi_step(self, program, steps, feed=None, fetch_list=None,
                       scope=None, **kw):
        out = self._exe.run_multi_step(
            program, steps, feed=feed, scope=scope,
            fetch_list=list(fetch_list) + [self._f["logits"]], **kw)
        self.steps.append(np.asarray(out[-1]))           # [K, S, 1, V]
        return out[:-1]


def make_session(seed=3, num_slots=6, tap=False, desc=DESC, **kw):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    params = lad.random_parameters(desc, seed, "float32")
    lad.load_parameters(scope, params, desc, "float32")
    args = dict(num_slots=num_slots, max_prompt=32, max_new_tokens=16,
                page_size=8, tokens_per_dispatch=2,
                prefill_token_budget=64, scope=scope, dtype="float32")
    args.update(kw)
    sess = DecoderOnlySession(exe, desc, **args)
    if tap:
        sess._exe = Tap(exe, sess._fetch)
    return sess, weights_solar.tree({k: jnp.asarray(v)
                                     for k, v in params.items()}, desc)


def rel(got, want):
    got, want = np.asarray(got, "float64"), np.asarray(want, "float64")
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def prompts_of(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(3, DESC["vocab_size"], n) for n in lengths]


def slot_state(sess, slot):
    """S [layers, heads, dk, dv] of one slot, as the served arrays hold
    it."""
    return np.stack([np.asarray(sess._scope.get_value("lad_s_%d" % i))[slot]
                     for i in LINEAR])


def reference_state(out, k=0):
    return np.stack([np.asarray(s[k]) for s in out["states"]])


# -- the kernels in interpret mode against their composed references ----------

def _inputs(rng, rows, H, dk, dv, decay):
    """Raw q, k, v, a log decay drawn log-uniform over ``decay`` and beta
    up to (very nearly) 2."""
    q, k = (jnp.asarray(rng.standard_normal(rows + (H * dk,)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal(rows + (H * dv,)), jnp.float32)
    g = -jnp.asarray(np.exp(rng.uniform(
        np.log(decay[0]), np.log(decay[1]), rows + (H * dk,))), jnp.float32)
    beta = rng.uniform(0.0, 2.0, rows + (H,))
    beta[..., ::3, :] = 1.9999
    return q, k, v, g, jnp.asarray(beta, jnp.float32)


# a prompt that ends inside a chunk, on a chunk's edge, inside the first
# sub-block, over several chunks and time blocks; decays near 1 (1e-5 a
# token), near 0 (e^-30 a token) and across both
@pytest.mark.parametrize("B,T,H,d,lengths,decay", [
    (3, 64, 2, 16, [64, 37, 1], (1e-5, 1e-3)),
    (2, 128, 2, 16, [128, 64], (1.0, 30.0)),
    (3, 192, 1, 32, [150, 65, 192], (1e-4, 20.0)),
    (2, 512, 2, 16, [300, 512], (1e-3, 5.0)),
    (2, 24, 2, 16, [24, 7], (1e-3, 5.0)),
])
def test_chunked_prefill_matches_the_token_loop(B, T, H, d, lengths, decay):
    rng = np.random.RandomState(T + H)
    q, k, v, g, beta = _inputs(rng, (B, T), H, d, d, decay)
    lens = jnp.asarray(lengths, jnp.int32)
    want_o, want_s = dr.chunk_prefill_reference(q, k, v, g, beta, lens)
    got_o, got_s = dr.chunk_prefill(q, k, v, g, beta, lens,
                                    force_pallas=True)
    assert np.isfinite(np.asarray(got_s)).all()
    assert rel(got_s, want_s) < 2e-5
    assert rel(got_o, want_o) < 2e-5
    # padding reads 0 and is nothing to the state: the same prompts in a
    # bucket cut to the longest give the same state
    for b, n in enumerate(lengths):
        assert not np.asarray(got_o[b, n:]).any()


def test_the_state_after_a_prompt_is_the_state_after_its_last_real_token():
    """What follows a prompt in its bucket row (other tokens, not zeros)
    changes nothing: beta = 0 and g = 0 past the length."""
    rng = np.random.RandomState(5)
    q, k, v, g, beta = _inputs(rng, (1, 128), 2, 16, 16, (1e-3, 3.0))
    lens = jnp.asarray([70], jnp.int32)
    _o, s = dr.chunk_prefill(q, k, v, g, beta, lens, force_pallas=True)
    _o, cut = dr.chunk_prefill_reference(
        q[:, :70], k[:, :70], v[:, :70], g[:, :70], beta[:, :70], lens)
    assert rel(s, cut) < 2e-5


@pytest.mark.parametrize("S,H,d", [(4, 2, 16), (3, 4, 32)])
def test_state_update_kernel_matches_its_reference(S, H, d):
    rng = np.random.RandomState(S + d)
    q, k, v, g, beta = _inputs(rng, (S,), H, d, d, (1e-4, 10.0))
    state = jnp.asarray(rng.standard_normal((S, H, d, d)), jnp.float32)
    live = jnp.asarray([1, 0, 1, 1][:S], jnp.int32)
    want_o, want_s = dr.state_update_reference(state, q, k, v, g, beta, live)
    got_o, got_s = dr.state_update(state, q, k, v, g, beta, live,
                                   force_pallas=True)
    assert rel(got_s, want_s) < 1e-6 and rel(got_o, want_o) < 1e-6
    # a slot that is not live keeps its state, bit for bit, and reads 0
    assert (np.asarray(got_s[1]) == np.asarray(state[1])).all()
    assert not np.asarray(got_o[1]).any()


def test_a_negative_eigenvalue_is_served():
    """beta near 2 on a repeated key: ``I - beta k k^T`` turns the state's
    component along ``k`` round (eigenvalue -1), which the chunked form
    must follow token for token."""
    T, d = 64, 16
    key = jnp.ones((1, T, d), jnp.float32)
    v = jnp.asarray(np.random.RandomState(1).standard_normal((1, T, d)),
                    jnp.float32)
    g = jnp.full((1, T, d), -1e-4, jnp.float32)
    beta = jnp.full((1, T, 1), 1.999, jnp.float32)
    lens = jnp.asarray([T], jnp.int32)
    want_o, want_s = dr.chunk_prefill_reference(key, key, v, g, beta, lens)
    got_o, got_s = dr.chunk_prefill(key, key, v, g, beta, lens,
                                    force_pallas=True)
    assert rel(got_s, want_s) < 1e-4 and rel(got_o, want_o) < 1e-4


# -- the session against the plain reference ----------------------------------

def test_prefill_then_decode_matches_the_reference_logits_and_state():
    """Prompts of different lengths in one bucket dispatch (two of them
    share the 16-bucket, one ends inside a page), then 6 decoded tokens:
    logits at every compared position and ``S`` in all three linear layers
    after the prefill and after the decode."""
    sess, tree = make_session(tap=True)
    prompts = prompts_of([13, 9, 30, 16])
    for p in prompts:
        sess.enqueue(p)
    admitted = sess.admit_pending()
    assert len(admitted) == 4
    # two prefill dispatches: the 16-bucket's three prompts, the 32's one
    assert sorted(len(f["prompt_len"]) for f, _l, _c in sess._exe.prefill) \
        == [2, 4]
    after_prefill = {rid: slot_state(sess, slot)
                     for slot, rid in admitted.items()}
    for _ in range(3):
        sess.step()
    for slot, rid in admitted.items():
        n = len(prompts[rid])
        toks = sess.tokens_of(slot)                          # 7 of them
        full = np.concatenate([prompts[rid], toks[:6]])
        out = ref.forward(tree, full, DESC, states_at=[n - 1, n + 5],
                          logits_at=np.arange(n - 1, n + 6))
        feed, logits, _chosen = next(
            p for p in sess._exe.prefill if slot in list(p[0]["slot_idx"]))
        row = list(feed["slot_idx"]).index(slot)
        got = np.concatenate(
            [logits[row]] + [s[:, slot, 0] for s in sess._exe.steps])
        assert rel(got, out["logits"]) < 2e-5
        assert (np.argmax(got, -1)[:7] == toks).all()
        assert rel(after_prefill[rid], reference_state(out, 0)) < 2e-5
        assert rel(slot_state(sess, slot), reference_state(out, 1)) < 2e-5


def test_a_reused_slot_starts_from_its_own_prefill():
    sess, tree = make_session(num_slots=2)
    first = prompts_of([20, 11], seed=1)
    for p in first:
        sess.enqueue(p)
    sess.admit_pending()
    for _ in range(2):
        sess.step()
    for slot in sess.active_slots:
        sess.cancel(slot)
    (again,) = prompts_of([7], seed=2)
    slot = sess.admit(again)
    out = ref.forward(tree, again, DESC, states_at=[6])
    assert rel(slot_state(sess, slot), reference_state(out)) < 2e-5
    assert sess.pool_conserved


def test_a_dead_slots_state_stays_as_it_is_under_the_live_mask():
    sess, _tree = make_session(num_slots=3)
    for p in prompts_of([12, 25, 6], seed=3):
        sess.enqueue(p)
    admitted = sess.admit_pending()
    sess.step()
    dead = sorted(admitted)[1]
    sess.cancel(dead)
    before = slot_state(sess, dead)
    windows = [np.asarray(sess._scope.get_value("lad_win_%d" % i))[:, dead]
               for i in LINEAR]
    alive = {s: slot_state(sess, s) for s in sess.active_slots}
    sess.step()
    assert (slot_state(sess, dead) == before).all()
    for i, w in zip(LINEAR, windows):
        assert (np.asarray(sess._scope.get_value("lad_win_%d" % i))[:, dead]
                == w).all()
    for s, was in alive.items():
        assert not (slot_state(sess, s) == was).all()
    assert np.isfinite(slot_state(sess, dead)).all()


def test_the_builder_declares_both_kinds_of_state():
    sess, _tree = make_session(num_slots=5)
    state = sess.geometry["state"]
    assert list(state["page_pools"]) == ["lad_k_0", "lad_v_0"]
    assert list(state["slot_arrays"]) == [
        "lad_s_1", "lad_win_1", "lad_s_2", "lad_win_2", "lad_s_3",
        "lad_win_3"]
    assert state["slot_arrays"]["lad_s_1"] == {
        "shape": (5, 4, 16, 16), "dtype": "float32", "slot_axis": 0}
    assert state["slot_arrays"]["lad_win_2"]["shape"] == (3, 5, 3 * 64)
    geo = sess.geometry
    assert geo["experts"] == {"held": 4, "of": 8, "top_k": 2}
    assert geo["prefill_chunk"] == dr.CHUNK
    assert geo["layer_kinds"] == [lad.GQA] + [lad.LINEAR] * 3
    # the router keeps all its outputs, the experts are the held ones
    shapes = lad.parameter_shapes(DESC, "float32")
    assert shapes["lad_1_router"][0] == (64, 8)
    assert shapes["lad_1_experts_gate"][0] == (4, 64, 32)
    assert shapes["lad_2_qkv"][0] == (64, 3 * 64)
    assert shapes["lad_2_conv_w"][0] == (4, 3 * 64)
    assert shapes["lad_2_a_log"] == ((4,), "float32")


def test_the_rounds_counters():
    sess, _tree = make_session(num_slots=4)
    lengths = [13, 9, 30]
    tracing.enable(True)
    try:
        rd = tracing.round_begin()
        for p in prompts_of(lengths):
            sess.enqueue(p)
        sess.admit_pending()
        sess.step()
        tracing.round_end(rd)
        head = tracing.rounds()[-1]["spans"][0]
    finally:
        tracing.enable(False)
        tracing.reset()
    # a slot: 3 linear layers x (4 x 16 x 16 float32 + 3 x 192 float32)
    per_slot = 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert sess._slot_state_bytes == per_slot
    assert head["state_slots_live"] == 3
    assert head["state_bytes_live"] == 2 * 3 * per_slot
    assert head["kv_rows_visible"] == sum(lengths) + 3
    # chunks of 64: a prompt of at most 32 tokens walks one; without rungs
    # the 16-bucket's program has 4 rows and the 32-bucket's 2, and the
    # rows of padding hold a chunk each that the kernel skips
    assert head["prefill_chunks"] == 3
    assert head["prefill_chunks_padded"] == (4 - 2) + (2 - 1)
    assert head["prefill_pad_tokens"] == 4 * 16 + 2 * 32 - sum(lengths)
    assert head["experts_routed_tokens"] == 2 * 4 * 3 * 2
    assert 0 <= head["experts_held_tokens"] <= head["experts_routed_tokens"]


def test_builder_for_refuses_by_the_key_at_fault():
    assert builder_for(DESC) is lad.build_linear_attn_moe_decoder
    lin = DESC["linear_attn_config"]
    for change, key in (
            ({"first_k_dense_replace": 1}, "first_k_dense_replace"),
            ({"linear_attn_config": dict(lin, num_kv_heads=2)},
             "num_kv_heads"),
            ({"kda_use_full_proj": True}, "kda_use_full_proj"),
            ({"use_rope": True}, "use_rope")):
        with pytest.raises(NotImplementedError, match=key):
            builder_for(dict(DESC, **change))
    with pytest.raises(ServingError, match="linear_attn_config"):
        builder_for({"hidden_size": 64})


# -- the share test: 8 shards add up to the uncut layer -----------------------

def test_eight_shards_add_up_to_the_uncut_layer():
    """The parts of a layer's output that all the shards give (each its
    own held experts of the SAME parameters, the router's every output),
    with the shared expert and the mixer counted once, add up to the
    uncut layer of the reference."""
    whole = dict(DESC, n_routed_experts=8)
    whole.pop("expert_shard")
    params = lad.random_parameters(whole, 7, "float32")
    named = {k: jnp.asarray(v) for k, v in params.items()}
    tokens = prompts_of([21], seed=4)[0]
    x = named["lad_embed"][jnp.asarray(tokens)]
    shards = 4                                   # 2 experts a shard

    def layer_of(desc, named, i, kind):
        tree = weights_solar.tree(named, desc)
        d = ref.dims(desc)
        return ref.layer(tree["layers"][i], x, tuple(sorted(d.items())),
                         kind, jnp.asarray([0], jnp.int32))[0]

    with jax.default_matmul_precision("highest"):
        for i, kind in ((0, "gqa"), (2, "linear")):
            uncut = layer_of(whole, named, i, kind)
            parts = []
            for s in range(shards):
                desc = dict(whole, n_routed_experts=2,
                            expert_shard={"of": 8, "first": 2 * s})
                mine = dict(named)
                for part in ("gate", "up", "down"):
                    name = "lad_%d_experts_%s" % (i, part)
                    mine[name] = named[name][2 * s:2 * s + 2]
                parts.append(layer_of(desc, mine, i, kind))
            # a shard's output is h + routed_s + shared: the residual,
            # the mixer and the shared expert are in every one of them
            none = dict(whole, n_routed_experts=0,
                        expert_shard={"of": 8, "first": 0})
            empty = dict(named)
            for part in ("gate", "up", "down"):
                name = "lad_%d_experts_%s" % (i, part)
                empty[name] = named[name][:0]
            once = layer_of(none, empty, i, kind)
            total = once + sum(p - once for p in parts)
            assert rel(total, uncut) < 1e-5
            assert rel(parts[0], uncut) > 1e-3
