"""The dense Gated DeltaNet / multi-head attention decoder
(``olmo_hybrid``), tiny on the CPU (hidden 64; layers 1-3 linear with 6
heads of 24 keys beside 64 values, two heads' values a tile of the state;
layer 4 multi-head attention, 4 heads of 16; SwiGLU of 96; vocabulary 512):
prefill of prompts of different lengths and then decode through
``DecoderOnlySession`` against the plain reference's full forward (logits
and the matrix state of every linear layer), the post-norm block, no
rotation, slots leaving and being reused, the round's counters, the names
of the sub-blocks in the programs, each refusal of ``builder_for`` by its
key, and the two delta-rule kernels in interpret mode at the shapes this
model brings: a decay a head, ``dk != dv``, widths that are no lane
multiple, 3 and 5 heads."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.kernels import delta_rule as dr  # noqa: E402
from paddle_tpu.kernels.paged_attention import KernelCompileError  # noqa: E402
from paddle_tpu.models import gated_delta_decoder as gdd  # noqa: E402
from paddle_tpu.observability import tracing  # noqa: E402
from paddle_tpu.serving.decoder_session import (  # noqa: E402
    DecoderOnlySession,
    builder_for,
)
from perfbench import weights_olmo  # noqa: E402
from perfbench.reference import gated_delta_decoder as ref  # noqa: E402

DESC = dict(
    model_type="olmo_hybrid", hidden_size=64, num_attention_heads=4,
    num_key_value_heads=4, num_hidden_layers=4,
    layer_types=["linear_attention"] * 3 + ["full_attention"],
    linear_num_key_heads=6, linear_num_value_heads=6,
    linear_key_head_dim=24, linear_value_head_dim=64,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    intermediate_size=96, hidden_act="silu", vocab_size=512,
    rms_norm_eps=1e-6, attention_bias=False, tie_word_embeddings=False,
    rope_parameters={"rope_theta": None, "rope_type": "default"},
    max_position_embeddings=65536)
LINEAR = [i for i, k in enumerate(DESC["layer_types"])
          if k == gdd.LINEAR]
PS = 8


class Tap(object):
    """An executor that also fetches the logits of every dispatch (what
    the benchmark's check does on the chip)."""

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
            fetch_list=list(fetch_list) + [self._f["logits"]], **kw)
        self.steps.append(np.asarray(out[-1]))           # [K, S, 1, V]
        return out[:-1]


def make_session(seed=3, num_slots=6, tap=False, desc=DESC, **kw):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    params = gdd.random_parameters(desc, seed, "float32")
    gdd.load_parameters(scope, params, desc, "float32")
    args = dict(num_slots=num_slots, max_prompt=32, max_new_tokens=16,
                page_size=PS, tokens_per_dispatch=2,
                prefill_token_budget=64, scope=scope, dtype="float32")
    args.update(kw)
    sess = DecoderOnlySession(exe, desc, **args)
    if tap:
        sess._exe = Tap(exe, sess._fetch)
    return sess, weights_olmo.tree({k: jnp.asarray(v)
                                    for k, v in params.items()}, desc)


def rel(got, want):
    got, want = np.asarray(got, "float64"), np.asarray(want, "float64")
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def prompts_of(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(3, DESC["vocab_size"], n) for n in lengths]


def slot_state(sess, slot):
    """S [linear layers, heads, dk, dv] of one slot: the served tiles of
    two heads taken apart."""
    return np.stack([np.asarray(dr.unpack_heads(
        jnp.asarray(sess._scope.get_value("gdd_s_%d" % i))[slot], 6))
        for i in LINEAR])


def reference_state(out, k=0):
    return np.stack([np.asarray(s[k]) for s in out["states"]])


# -- the session against the plain reference ----------------------------------

def test_prefill_then_decode_matches_the_reference():
    """Prompts of different lengths in one bucket dispatch (two share the
    16-bucket, one ends inside a page), then 8 decoded tokens: logits at
    every compared position and ``S`` in all three linear layers after the
    prefill and after the decode. The reference WITH a rotation in the
    full layer (the benchmark's control C) is far from both."""
    sess, tree = make_session(tap=True)
    prompts = prompts_of([13, 9, 30, 16])
    for p in prompts:
        sess.enqueue(p)
    admitted = sess.admit_pending()
    assert len(admitted) == 4
    after_prefill = {rid: slot_state(sess, slot)
                     for slot, rid in admitted.items()}
    for _ in range(4):
        sess.step()
    for slot, rid in admitted.items():
        n = len(prompts[rid])
        toks = sess.tokens_of(slot)                          # 9 of them
        full = np.concatenate([prompts[rid], toks[:8]])
        out = ref.forward(tree, full, DESC, states_at=[n - 1, n + 7],
                          logits_at=np.arange(n - 1, n + 8))
        feed, logits = next(
            p for p in sess._exe.prefill if slot in list(p[0]["slot_idx"]))
        row = list(feed["slot_idx"]).index(slot)
        got = np.concatenate(
            [logits[row]] + [s[:, slot, 0] for s in sess._exe.steps])
        assert rel(got, out["logits"]) < 2e-5
        assert (np.argmax(got, -1)[:9] == toks).all()
        assert rel(after_prefill[rid], reference_state(out, 0)) < 2e-5
        assert rel(slot_state(sess, slot), reference_state(out, 1)) < 2e-5
        rotated = ref.forward(tree, full, DESC, rotate=500000.0,
                              logits_at=np.arange(n - 1, n + 8))
        assert rel(rotated["logits"], out["logits"]) > 1e-2


def test_a_reused_slot_starts_from_its_own_prefill_and_a_dead_one_stays():
    sess, tree = make_session(num_slots=3)
    first = prompts_of([20, 11, 6], seed=1)
    for p in first:
        sess.enqueue(p)
    admitted = sess.admit_pending()
    sess.step()
    dead = sorted(admitted)[1]
    sess.cancel(dead)
    before = slot_state(sess, dead)
    alive = {s: slot_state(sess, s) for s in sess.active_slots}
    sess.step()
    assert (slot_state(sess, dead) == before).all()
    for s, was in alive.items():
        assert not (slot_state(sess, s) == was).all()
    for slot in sess.active_slots:
        sess.cancel(slot)
    assert sess.pool_conserved and not sess.pages_in_use
    (again,) = prompts_of([7], seed=2)
    slot = sess.admit(again)
    out = ref.forward(tree, again, DESC, states_at=[6])
    assert rel(slot_state(sess, slot), reference_state(out)) < 2e-5
    assert sess.pool_conserved


def _ops(program, kind):
    return [op for op in program.global_block().ops if op.type == kind]


def test_the_programs_say_what_the_layers_are():
    """Every sub-block is named (``op_namescope``); the mixer's gate is
    SiLU, its decay a head (``[slots, 6]``), ``beta`` reaches 2, the
    prefill installs the state two heads a tile; the full layer's q and k
    are normed over the whole row and nothing is rotated; no op of an
    expert layer is in the programs."""
    sess, _tree = make_session(num_slots=2)
    step = sess._step_prog
    scopes = {}
    for op in step.global_block().ops:
        scopes.setdefault(op.type, set()).add(op.attrs.get("op_namescope"))
    assert scopes["delta_rule_state_update"] == {"gdn_mixer"}
    assert scopes["gated_head_norm"] == {"gdn_mixer"}
    assert scopes["gqa_paged_attention"] == {"mha_attention"}
    assert scopes["gated_ffn"] == {"dense_ffn"}
    assert "dropless_moe_ffn" not in scopes
    assert not [t for t in scopes if "rope" in t or "rotary" in t]
    progs = [step] + [p for rungs in sess._prefill_progs.values()
                      for p in rungs.values()]
    for prog in progs:
        gates = _ops(prog, "delta_rule_gates")
        assert len(gates) == 3
        assert all(op.attrs["beta_scale"] == 2.0 for op in gates)
        assert all(op.attrs["gate_act"] == "silu"
                   for op in _ops(prog, "gated_head_norm"))
        assert len(_ops(prog, "gated_ffn")) == 4
        # 2 norms a block, 2 of the full layer's q and k, the final one
        assert len(_ops(prog, "rms_norm")) == 2 * 4 + 2 + 1
        for op in _ops(prog, "delta_rule_prefill"):
            assert op.attrs["state_pack"] == 2
    g = step.global_block().var(
        _ops(step, "delta_rule_state_update")[0].input("G")[0])
    assert tuple(g.shape) == (2, 6)


def test_an_older_familys_ops_keep_their_attributes():
    """``state_pack`` and ``gate_act`` are written into a program only
    where they are asked for."""
    import test_linear_attn_decoder as solar

    built = builder_for(solar.DESC)(
        solar.DESC, 2, 48, 8, [8, 16], prefill_token_budget=32,
        dtype="float32")
    for prog in (built["step"], built["prefill"][16]):
        for op in _ops(prog, "gated_head_norm"):
            assert "gate_act" not in op.attrs
        for op in _ops(prog, "delta_rule_prefill"):
            assert "state_pack" not in op.attrs


def test_the_geometry_and_the_parameters():
    d = gdd.dims(DESC)
    assert (d["pack"], d["lw"], d["row"]) == (2, 2 * 144 + 384, 64)
    shapes = gdd.parameter_shapes(DESC, "float32")
    assert shapes["gdd_0_qkv"][0] == (64, 672)
    assert shapes["gdd_0_conv_w"][0] == (4, 672)
    assert shapes["gdd_0_a"][0] == shapes["gdd_0_beta"][0] == (64, 6)
    assert shapes["gdd_0_dt_bias"] == ((6,), "float32")
    assert shapes["gdd_0_gate"][0] == (64, 384)      # full rank
    assert shapes["gdd_0_o_norm"][0] == (64,)
    assert shapes["gdd_3_q_norm"][0] == (64,)        # the whole row
    assert not [n for n in shapes if "router" in n or "expert" in n
                or "in_norm" in n]
    sess, _tree = make_session(num_slots=5)
    geo = sess.geometry
    assert geo["layer_kinds"] == DESC["layer_types"]
    assert geo["prefill_chunk"] == dr.CHUNK and geo["state_pack"] == 2
    assert geo["state_bytes_slot_layer"] == 6 * 24 * 64 * 4
    assert geo["kv_row_bytes"] == 2 * 64 * 4         # float32 here
    state = geo["state"]
    assert list(state["page_pools"]) == ["gdd_k_3", "gdd_v_3"]
    assert state["page_pools"]["gdd_k_3"]["shape"] \
        == (geo["num_pages"], PS, 64)
    assert list(state["slot_arrays"]) == [
        "gdd_s_0", "gdd_win_0", "gdd_s_1", "gdd_win_1", "gdd_s_2",
        "gdd_win_2"]
    assert state["slot_arrays"]["gdd_s_2"] == {
        "shape": (5, 3, 24, 128), "dtype": "float32", "slot_axis": 0}
    assert state["slot_arrays"]["gdd_win_2"]["shape"] == (3, 5, 672)
    # values that are a lane multiple alone lie a head a tile
    assert gdd.dims(dict(DESC, linear_value_head_dim=128))["pack"] == 1


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
    # a slot: 3 linear layers x (6 x 24 x 64 float32 + 3 x 672 float32)
    per_slot = 3 * (6 * 24 * 64 * 4 + 3 * 672 * 4)
    assert sess._slot_state_bytes == per_slot
    assert head["state_slots_live"] == 3
    assert head["state_bytes_live"] == 2 * 3 * per_slot
    assert head["kv_rows_visible"] == sum(lengths) + 3
    assert head["prefill_chunks"] == 3
    assert head["prefill_chunks_padded"] == (4 - 2) + (2 - 1)
    assert head["prefill_pad_tokens"] == 4 * 16 + 2 * 32 - sum(lengths)
    assert "experts_routed_tokens" not in head


# -- what is refused, by its key ----------------------------------------------

def test_the_description_reaches_this_family():
    assert builder_for(DESC) is gdd.build_gated_delta_decoder


@pytest.mark.parametrize("change,key", [
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"attention_bias": True}, "attention_bias"),
    ({"rope_parameters": {"rope_theta": 500000.0}},
     "rope_parameters.rope_theta"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"linear_num_value_heads": 12}, "linear_num_value_heads"),
    ({"num_key_value_heads": 2}, "num_key_value_heads"),
    ({"layer_types": ["linear_attention"] * 3 + ["sliding_attention"]},
     r"layer_types\[3\]"),
])
def test_builder_for_refuses_by_the_key_at_fault(change, key):
    with pytest.raises(NotImplementedError, match=key):
        builder_for(dict(DESC, **change))


# -- the kernels in interpret mode at this model's kinds of shape -------------

def _operands(B, T, H, dk, dv, seed=0):
    rng = np.random.RandomState(seed)
    q, k = [jnp.asarray(rng.standard_normal((B, T, H * dk)), jnp.float32)
            for _ in range(2)]
    v = jnp.asarray(rng.standard_normal((B, T, H * dv)), jnp.float32)
    g = -jnp.asarray(0.3 * np.abs(rng.standard_normal((B, T, H))),
                     jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 2.0, (B, T, H)), jnp.float32)
    return q, k, v, g, beta


@pytest.mark.parametrize("H,dk,dv", [(3, 24, 40), (5, 96, 192),
                                     (4, 128, 128)])
def test_chunk_prefill_takes_a_decay_a_head_at_any_widths(H, dk, dv):
    """The chunked kernel (interpret mode) against the plain loop with
    ``g`` a head, ``beta`` up to 2, prompts that end inside a chunk and
    before the bucket; and ``g`` a head equals ``g`` broadcast a key
    channel to float32 rounding, in the kernel and in the loop."""
    B, T = 2, 128
    q, k, v, g, beta = _operands(B, T, H, dk, dv)
    lens = jnp.asarray([T - 37, T], jnp.int32)
    o_ref, s_ref = dr.chunk_prefill(q, k, v, g, beta, lens,
                                    force_reference=True)
    o, s = dr.chunk_prefill(q, k, v, g, beta, lens, force_pallas=True)
    assert float(jnp.abs(o - o_ref).max()) < 2e-5
    assert float(jnp.abs(s - s_ref).max()) < 2e-5
    assert not np.asarray(o[0, T - 37:]).any()
    wide = jnp.repeat(g, dk, axis=-1)
    o_w, s_w = dr.chunk_prefill(q, k, v, wide, beta, lens,
                                force_pallas=True)
    assert float(jnp.abs(o - o_w).max()) < 2e-5
    assert float(jnp.abs(s - s_w).max()) < 2e-5
    o_l, s_l = dr.chunk_prefill(q, k, v, wide, beta, lens,
                                force_reference=True)
    assert float(jnp.abs(o_l - o_ref).max()) < 1e-6
    assert float(jnp.abs(s_l - s_ref).max()) < 1e-6


@pytest.mark.parametrize("H,dk,dv,pack", [(3, 24, 40, 1), (5, 96, 192, 1),
                                          (6, 24, 64, 2), (4, 96, 192, 2),
                                          (4, 128, 128, 1)])
def test_state_update_takes_a_decay_a_head_and_heads_side_by_side(
        H, dk, dv, pack):
    """The one-token kernel (interpret mode) against ``token_step`` over
    a state of ``pack`` heads a tile: ``g`` a head and ``g`` a key channel
    agree, a slot that is not live keeps its state and reads 0."""
    S = 3
    q, k, v, g, beta = [x[:, 0] for x in _operands(S, 1, H, dk, dv, seed=1)]
    rng = np.random.RandomState(2)
    plain = jnp.asarray(rng.standard_normal((S, H, dk, dv)), jnp.float32)
    state = dr.pack_heads(plain, pack)
    assert state.shape == (S, H // pack, dk, pack * dv)
    assert (np.asarray(dr.unpack_heads(state, H)) == np.asarray(plain)).all()
    live = jnp.asarray([1, 0, 1], jnp.int32)
    o_ref, new_ref = dr.state_update(state, q, k, v, g, beta, live,
                                     force_reference=True)
    for decay in (g, jnp.repeat(g, dk, axis=-1)):
        o, new = dr.state_update(state, q, k, v, decay, beta, live,
                                 force_pallas=True)
        assert float(jnp.abs(o - o_ref).max()) < 1e-5
        assert float(jnp.abs(new - new_ref).max()) < 1e-5
        assert (np.asarray(new[1]) == np.asarray(state[1])).all()
        assert not np.asarray(o[1]).any()
    # the loop a head at a time says the same
    o_t, new_t = dr.token_step(
        plain, dr.l2_normalise(q.reshape(S, H, dk), dk ** -0.5),
        dr.l2_normalise(k.reshape(S, H, dk)), v.reshape(S, H, dv),
        g[..., None], beta)
    assert float(jnp.abs(dr.pack_heads(new_t, pack)[0] - new_ref[0]).max()) \
        < 1e-6
    assert float(jnp.abs(o_t.reshape(S, -1)[0] - o_ref[0]).max()) < 1e-6


def test_a_state_the_heads_cannot_lie_in_is_refused_by_its_shape():
    q, k, v, g, beta = [x[:, 0] for x in _operands(2, 1, 5, 24, 40)]
    bad = jnp.zeros((2, 2, 24, 80), jnp.float32)    # 5 heads in 2 tiles
    live = jnp.ones((2,), jnp.int32)
    with pytest.raises(KernelCompileError, match=r"\(2, 2, 24, 80\)"):
        dr.state_update(bad, q, k, v, g, beta, live, force_pallas=True)
    with pytest.raises(ValueError, match="cannot hold 5 heads"):
        dr.state_update(bad, q, k, v, g, beta, live, force_reference=True)
