"""The hybrid Mamba-2 / grouped-query decoder with routed experts, tiny on
the CPU (hidden 64, three Mamba-2 layers of 8 heads of 16 on a state of 16
and one attention layer of 4 query heads on 2 key/value heads, 4 of 8
experts held, vocabulary 512): prefill of prompts of different lengths in
one bucket dispatch and then decode through ``DecoderOnlySession`` against
the plain reference's full forward (logits, chosen experts AND the matrix
state), the four multipliers and the 1/16 attention scale each shown to
matter, slots leaving and being reused, a dead slot under the live mask,
the round's counters, the refusals of ``builder_for`` and the guide's share
test: what both shards give adds up to the uncut layer."""

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
from paddle_tpu.kernels import ssd  # noqa: E402
from paddle_tpu.models import hybrid_ssm_decoder as hsd  # noqa: E402
from paddle_tpu.models import ssd_moe_decoder as smd  # noqa: E402
from paddle_tpu.observability import tracing  # noqa: E402
from paddle_tpu.serving.decoder_session import (  # noqa: E402
    DecoderOnlySession,
    builder_for,
)
from paddle_tpu.serving.server import ServingError  # noqa: E402
from perfbench import weights_granite  # noqa: E402
from perfbench.reference import ssd_moe_decoder as ref  # noqa: E402

DESC = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4,
    mamba_expand=2, mamba_n_groups=1, mamba_chunk_size=256,
    mamba_conv_bias=True, mamba_proj_bias=False, attention_bias=False,
    num_hidden_layers=4,
    layer_types=["mamba", "mamba", "attention", "mamba"], vocab_size=512,
    intermediate_size=32, shared_intermediate_size=48, num_local_experts=4,
    expert_shard={"of": 8, "first": 2}, num_experts_per_tok=2,
    rms_norm_eps=1e-5, position_embedding_type="nope",
    tie_word_embeddings=True, embedding_multiplier=12.0,
    residual_multiplier=0.22, attention_multiplier=1.0 / 16,
    logits_scaling=16.0, hidden_act="silu", normalization_function="rmsnorm")
MAMBA = [i for i, k in enumerate(smd.layer_kinds(DESC)) if k == smd.MAMBA]


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
            fetch_list=list(fetch_list) + [self._f["logits"],
                                           self._f["chosen"]], **kw)
        # [K, S, 1, V] and [K, layers, S, k]
        self.steps.append((np.asarray(out[-2]), np.asarray(out[-1])))
        return out[:-2]


def make_session(seed=3, num_slots=6, tap=False, desc=DESC, **kw):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    params = smd.random_parameters(desc, seed, "float32")
    smd.load_parameters(scope, params, desc, "float32")
    args = dict(num_slots=num_slots, max_prompt=32, max_new_tokens=16,
                page_size=8, tokens_per_dispatch=2,
                prefill_token_budget=64, scope=scope, dtype="float32")
    args.update(kw)
    sess = DecoderOnlySession(exe, desc, **args)
    if tap:
        sess._exe = Tap(exe, sess._fetch)
    return sess, weights_granite.tree({k: jnp.asarray(v)
                                       for k, v in params.items()}, desc)


def rel(got, want):
    got, want = np.asarray(got, "float64"), np.asarray(want, "float64")
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def prompts_of(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(3, DESC["vocab_size"], n) for n in lengths]


def slot_state(sess, slot):
    """s [layers, heads, P, N] of one slot, from the served arrays
    (``[slots, lane groups, N, group lanes]``)."""
    return np.stack([np.asarray(ssd.to_heads(
        sess._scope.get_value("smd_s_%d" % i)[slot], DESC["mamba_n_heads"]))
        for i in MAMBA])


def reference_state(out, k=0):
    return np.stack([np.asarray(s[k]) for s in out["states"]])


# -- the session against the plain reference ----------------------------------

def test_prefill_then_decode_matches_the_reference():
    """Prompts of different lengths in one bucket dispatch (two of them
    share the 16-bucket, one ends inside a page), then 6 decoded tokens:
    logits at every compared position, the experts chosen in every layer,
    and ``s`` in all three Mamba-2 layers after the prefill and after the
    decode."""
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
        feed, logits, chosen = next(
            p for p in sess._exe.prefill if slot in list(p[0]["slot_idx"]))
        row = list(feed["slot_idx"]).index(slot)
        T = len(feed["prompt_ids"]) // len(feed["prompt_len"])
        got = np.concatenate(
            [logits[row]] + [s[:, slot, 0] for s, _c in sess._exe.steps])
        assert rel(got, out["logits"]) < 2e-5
        assert (np.argmax(got, -1)[:7] == toks).all()
        # the experts the program chose are the reference's own, layer by
        # layer (as sets: equal logits have no order)
        mine = np.concatenate(
            [chosen[:, row * T:row * T + n]]
            + [np.transpose(c[:, :, slot], (1, 0, 2))
               for _s, c in sess._exe.steps], axis=1)        # [L, n + 6, k]
        own = np.stack([np.asarray(o) for o in out["own"]])
        assert (np.sort(mine, -1) == np.sort(own, -1)).mean() > 0.99
        assert rel(after_prefill[rid], reference_state(out, 0)) < 2e-5
        assert rel(slot_state(sess, slot), reference_state(out, 1)) < 2e-5


def _logits(desc, tree, tokens):
    return np.asarray(ref.forward(tree, tokens, desc)["logits"])


@pytest.mark.parametrize("key,other", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", 0.25), ("logits_scaling", 1.0)])
def test_each_multiplier_matters(key, other):
    """The served session equals the reference WITH the description's
    multiplier and is far from the reference with another value in its
    place: none of the four is dropped on the way (``attention_multiplier``
    1/16 against head ** -0.5 = 1/4)."""
    sess, tree = make_session(tap=True, num_slots=2)
    (prompt,) = prompts_of([24], seed=5)
    sess.admit(prompt)
    _feed, logits, _chosen = sess._exe.prefill[0]
    want = _logits(DESC, tree, prompt)[-1]
    wrong = _logits(dict(DESC, **{key: other}), tree, prompt)[-1]
    assert rel(logits[0, 0], want) < 2e-5
    # the attention layer is one in four behind a 0.22 branch: its scale
    # moves the logits least, 170 x what the program is held to
    assert rel(wrong, want) > 1e-3


def test_the_seeded_logits_are_not_degenerate():
    """Under the four multipliers the seeded model's logits spread: their
    softmax is neither one-hot nor flat."""
    _sess, tree = make_session()
    (prompt,) = prompts_of([32], seed=6)
    logits = _logits(DESC, tree, prompt)
    p = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))
    entropy = -(p * np.log(p + 1e-30)).sum(-1)
    assert 0.1 < entropy.mean() < 0.98 * np.log(DESC["vocab_size"])
    assert np.isfinite(logits).all()


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
    windows = [np.asarray(sess._scope.get_value("smd_win_%d" % i))[:, dead]
               for i in MAMBA]
    alive = {s: slot_state(sess, s) for s in sess.active_slots}
    sess.step()
    assert (slot_state(sess, dead) == before).all()
    for i, w in zip(MAMBA, windows):
        assert (np.asarray(sess._scope.get_value("smd_win_%d" % i))[:, dead]
                == w).all()
    for s, was in alive.items():
        assert not (slot_state(sess, s) == was).all()
    assert np.isfinite(slot_state(sess, dead)).all()


def test_the_builder_declares_both_kinds_of_state():
    sess, _tree = make_session(num_slots=5)
    state = sess.geometry["state"]
    assert list(state["page_pools"]) == ["smd_k_2", "smd_v_2"]
    assert list(state["slot_arrays"]) == [
        "smd_s_0", "smd_win_0", "smd_s_1", "smd_win_1", "smd_s_3",
        "smd_win_3"]
    # 8 heads of 16 channels share the 128 lanes of ONE group: [slots,
    # groups, d_state, lanes]
    assert state["slot_arrays"]["smd_s_1"] == {
        "shape": (5, 1, 16, 128), "dtype": "float32", "slot_axis": 0}
    assert ssd.state_shape(5, 8, 16, 16) == (5, 1, 16, 128)
    assert state["slot_arrays"]["smd_win_3"]["shape"] == (3, 5, 128 + 32)
    geo = sess.geometry
    assert geo["experts"] == {"held": 4, "of": 8, "top_k": 2}
    assert geo["prefill_chunk"] == ssd.CHUNK
    assert geo["layer_kinds"] == DESC["layer_types"]
    assert geo["state_bytes_per_slot"] == sess._slot_state_bytes
    # the router keeps all its outputs, the experts are the held ones; the
    # head is the embedding
    shapes = smd.parameter_shapes(DESC, "float32")
    assert shapes["smd_1_router"][0] == (64, 8)
    assert shapes["smd_1_experts_gate"][0] == (4, 64, 32)
    assert shapes["smd_1_shared_up"][0] == (64, 48)
    assert shapes["smd_0_in_xbc"][0] == (64, 128 + 32)
    assert shapes["smd_0_in_dt"][0] == (64, 8)
    assert shapes["smd_0_a_log"] == ((8,), "float32")
    assert not any(name.endswith("head") or name.endswith("router_bias")
                   for name in shapes)


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
    # a slot: 3 Mamba-2 layers x (8 x 16 x 16 float32 + 3 x 160 float32)
    per_slot = 3 * (8 * 16 * 16 * 4 + 3 * 160 * 4)
    assert sess._slot_state_bytes == per_slot
    assert head["state_slots_live"] == 3
    assert head["state_bytes_live"] == 2 * 3 * per_slot
    assert head["kv_rows_visible"] == sum(lengths) + 3
    # chunks of 256: a prompt walks one; without rungs the 16-bucket's
    # program has 4 rows and the 32-bucket's 2, and the rows of padding
    # hold a chunk each that the kernel skips
    assert head["prefill_chunks"] == 3
    assert head["prefill_chunks_padded"] == (4 - 2) + (2 - 1)
    assert head["prefill_pad_tokens"] == 4 * 16 + 2 * 32 - sum(lengths)
    assert head["experts_routed_tokens"] == 2 * 4 * 3 * 2
    assert 0 <= head["experts_held_tokens"] <= head["experts_routed_tokens"]


def test_builder_for_chooses_by_key_and_refuses_by_the_key_at_fault():
    assert builder_for(DESC) is smd.build_ssd_moe_decoder
    # a Mamba-1 description (no heads) still reaches its own builder
    import test_hybrid_ssm_decoder as jamba

    assert builder_for(jamba.DESC) is hsd.build_hybrid_ssm_decoder
    for change, key in (
            ({"mamba_n_groups": 8}, "mamba_n_groups"),
            ({"mamba_proj_bias": True}, "mamba_proj_bias"),
            ({"attention_bias": True}, "attention_bias"),
            ({"position_embedding_type": "rope"}, "position_embedding_type"),
            ({"tie_word_embeddings": False}, "tie_word_embeddings")):
        with pytest.raises(NotImplementedError, match=key):
            builder_for(dict(DESC, **change))
    with pytest.raises(ServingError, match="mamba_n_heads"):
        builder_for({"hidden_size": 64})


# -- the share test: both shards add up to the uncut layer --------------------

@pytest.mark.parametrize("i,kind", [(0, "mamba"), (2, "attention")])
def test_two_shards_add_up_to_the_uncut_layer(i, kind):
    """The parts of a layer's output that both shards give (``first`` 0
    and ``first`` 4 of 8: each its own held experts of the SAME
    parameters, the router's every output), with the shared expert and the
    mixer counted once, add up to the uncut layer of the reference."""
    whole = dict(DESC, num_local_experts=8)
    whole.pop("expert_shard")
    params = smd.random_parameters(whole, 7, "float32")
    named = {k: jnp.asarray(v) for k, v in params.items()}
    tokens = prompts_of([21], seed=4)[0]
    x = named["smd_embed"][jnp.asarray(tokens)] * DESC["embedding_multiplier"]

    def layer_of(desc, named):
        tree = weights_granite.tree(named, desc)
        d = ref.dims(desc)
        return ref.layer(tree["layers"][i], x, tuple(sorted(d.items())),
                         kind, jnp.asarray([0], jnp.int32))[0]

    def cut(lo, hi):
        mine = dict(named)
        for part in ("gate", "up", "down"):
            name = "smd_%d_experts_%s" % (i, part)
            mine[name] = named[name][lo:hi]
        return mine

    with jax.default_matmul_precision("highest"):
        uncut = layer_of(whole, named)
        parts = [layer_of(dict(whole, num_local_experts=4,
                               expert_shard={"of": 8, "first": first}),
                          cut(first, first + 4)) for first in (0, 4)]
        # a shard's output is h + res * (routed_s + shared): the residual,
        # the mixer and the shared expert are in both of them
        once = layer_of(dict(whole, num_local_experts=0,
                             expert_shard={"of": 8, "first": 0}), cut(0, 0))
        total = once + sum(p - once for p in parts)
        assert rel(total, uncut) < 1e-5
        assert rel(parts[0], uncut) > 1e-3
