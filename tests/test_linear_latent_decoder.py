"""The delta-rule linear-attention decoder whose full-attention sibling is a
NoPE LATENT layer (``kimi_linear``), tiny on the CPU (hidden 64; layers 1,
2, 3 and 5 linear with 4 heads of 16, layer 4 latent with 4 heads of 16 + 8
over a row of 32 + 8; a leading dense layer, then 4 of 8 experts held;
vocabulary 512): prefill of prompts of different lengths and then decode
through ``DecoderOnlySession`` against the plain reference's full forward
(logits, the experts chosen, the matrix state of every linear layer AND the
latent rows in the pool), no rotation anywhere, the leading dense layer,
the 1-based layer lists, beta without the factor 2, slots leaving and being
reused, the round's counters, the names of the sub-blocks in the programs,
each refusal of ``builder_for`` by its key and the guide's share test: what
all 4 shards give adds up to the uncut layer."""

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
from paddle_tpu.core.op_registry import get_op_def  # noqa: E402
from paddle_tpu.kernels import delta_rule as dr  # noqa: E402
from paddle_tpu.models import latent_moe_decoder as lmd  # noqa: E402
from paddle_tpu.models import linear_attn_moe_decoder as lad  # noqa: E402
from paddle_tpu.observability import tracing  # noqa: E402
from paddle_tpu.serving.decoder_session import (  # noqa: E402
    DecoderOnlySession,
    builder_for,
)
from perfbench import weights_kimi  # noqa: E402
from perfbench.reference import linear_latent_moe_decoder as ref  # noqa: E402

DESC = dict(
    model_type="kimi_linear", hidden_size=64, num_attention_heads=4,
    num_key_value_heads=4, head_dim=16, kv_lora_rank=32, q_lora_rank=None,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    mla_use_nope=True, linear_attn_config=dict(
        short_conv_kernel_size=4, head_dim=16, num_heads=4,
        kda_layers=[1, 2, 3, 5], full_attn_layers=[4]),
    num_hidden_layers=5, vocab_size=512, intermediate_size=96,
    first_k_dense_replace=1, moe_intermediate_size=32, moe_layer_freq=1,
    num_experts=4, expert_shard={"of": 8, "first": 2},
    num_shared_experts=1, num_experts_per_token=2, moe_renormalize=True,
    moe_router_activation_func="sigmoid", routed_scaling_factor=2.446,
    num_expert_group=1, topk_group=1, use_grouped_topk=True,
    rms_norm_eps=1e-5, rope_theta=10000, rope_scaling=None,
    num_nextn_predict_layers=0, tie_word_embeddings=False)
KINDS = lad.layer_kinds(DESC)
LINEAR = [i for i, k in enumerate(KINDS) if k == lad.LINEAR]
LATENT = [i for i, k in enumerate(KINDS) if k == lad.LATENT]
PS = 8


class Tap(object):
    """An executor that also fetches the logits and the choice of experts
    of every dispatch (what the benchmark's check does on the chip)."""

    def __init__(self, exe, fetches):
        self._exe, self._f = exe, fetches
        self.prefill, self.steps, self.chosen = [], [], []

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
        self.steps.append(np.asarray(out[-2]))           # [K, S, 1, V]
        self.chosen.append(np.asarray(out[-1]))          # [K, layers, S, k]
        return out[:-2]


def make_session(seed=3, num_slots=6, tap=False, desc=DESC, **kw):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    params = lad.random_parameters(desc, seed, "float32")
    lad.load_parameters(scope, params, desc, "float32")
    args = dict(num_slots=num_slots, max_prompt=32, max_new_tokens=16,
                page_size=PS, tokens_per_dispatch=2,
                prefill_token_budget=64, scope=scope, dtype="float32")
    args.update(kw)
    sess = DecoderOnlySession(exe, desc, **args)
    if tap:
        sess._exe = Tap(exe, sess._fetch)
    return sess, weights_kimi.tree({k: jnp.asarray(v)
                                    for k, v in params.items()}, desc)


def rel(got, want):
    got, want = np.asarray(got, "float64"), np.asarray(want, "float64")
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def prompts_of(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(3, DESC["vocab_size"], n) for n in lengths]


def slot_state(sess, slot):
    """S [linear layers, heads, dk, dv] of one slot, as served."""
    return np.stack([np.asarray(sess._scope.get_value("lad_s_%d" % i))[slot]
                     for i in LINEAR])


def slot_rows(sess, slot, n):
    """The first ``n`` latent rows of one slot [latent layers, n, C + dr],
    read from the pool through the slot's pages; and the pool's lanes past
    the row, which stay zero."""
    pages = sess._kinds[0].pages[slot]
    rows, rest = [], []
    for i in LATENT:
        pool = np.asarray(sess._scope.get_value("lad_pool_%d" % i))
        flat = np.concatenate([pool[p] for p in pages])[:n]
        rows.append(flat[:, :40])
        rest.append(flat[:, 40:])
    return np.stack(rows), np.stack(rest)


def reference_state(out, k=0):
    return np.stack([np.asarray(s[k]) for s in out["states"]])


# -- the session against the plain reference ----------------------------------

def test_prefill_then_decode_matches_the_reference():
    """Prompts of different lengths in one bucket dispatch (two of them
    share the 16-bucket, one ends inside a page), then 6 decoded tokens:
    logits at every compared position, the experts the four expert layers
    chose, ``S`` in all four linear layers after the prefill and after the
    decode, and the latent layer's rows as the pool holds them. The
    reference WITH rotation (the benchmark's control C) is far from both."""
    sess, tree = make_session(tap=True)
    prompts = prompts_of([13, 9, 30, 16])
    for p in prompts:
        sess.enqueue(p)
    admitted = sess.admit_pending()
    assert len(admitted) == 4
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
            [logits[row]] + [s[:, slot, 0] for s in sess._exe.steps])
        assert rel(got, out["logits"]) < 2e-5
        assert (np.argmax(got, -1)[:7] == toks).all()
        # the experts: 4 expert layers (the leading layer is dense), the
        # prompt's rows of the prefill then the decoded tokens'
        mine = np.concatenate(
            [chosen[:, row * T:row * T + n]]
            + [np.transpose(c[:, :, slot], (1, 0, 2))
               for c in sess._exe.chosen], axis=1)[:, :n + 6]
        assert mine.shape == (4, n + 6, 2)
        for layer, own in enumerate(out["own"]):
            assert (np.sort(mine[layer], -1)
                    == np.sort(np.asarray(own), -1)).all()
        assert rel(after_prefill[rid], reference_state(out, 0)) < 2e-5
        assert rel(slot_state(sess, slot), reference_state(out, 1)) < 2e-5
        rows, rest = slot_rows(sess, slot, n + 6)
        assert rel(rows, np.stack([np.asarray(r) for r in out["rows"]])) \
            < 2e-5
        assert not rest.any()
        rotated = ref.forward(tree, full, DESC, rotate=10000.0,
                              logits_at=np.arange(n - 1, n + 6))
        assert rel(rotated["logits"], out["logits"]) > 1e-2
        assert rel(np.asarray(rotated["rows"][0]), rows[0]) > 1e-2


def test_no_rotation_reaches_the_row_or_the_query():
    """``latent_rope_rows(rotate=False)``: the query as it was projected
    and the row ``[RMSNorm(ckv) | k_pe]``, whatever the positions (a token
    of the flat batch stands at ``n % period`` where the op rotates: two
    periods, one result); the op in its older form does move."""
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.standard_normal((8, 4 * 24)), jnp.float32)
    kva = jnp.asarray(rng.standard_normal((8, 40)), jnp.float32)
    norm = jnp.asarray(1 + 0.1 * rng.standard_normal(32), jnp.float32)
    lower = get_op_def("latent_rope_rows").lower
    ins = {"Q": [q], "KVA": [kva], "KVNorm": [norm]}
    attrs = dict(heads=4, nope_dim=16, rope_dim=8, theta=10000.0,
                 epsilon=1e-5)
    plain = [lower(None, ins, dict(attrs, period=p, rotate=False))
             for p in (4, 8)]
    for out in plain:
        assert (np.asarray(out["QOut"])
                == np.asarray(q).reshape(8, 4, 24)).all()
        assert (np.asarray(out["Row"][:, 32:]) == np.asarray(kva[:, 32:])
                ).all()
        x = np.asarray(kva[:, :32], "float64")
        want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) \
            * np.asarray(norm)
        assert rel(out["Row"][:, :32], want) < 1e-6
    moved = [lower(None, ins, dict(attrs, period=p)) for p in (4, 8)]
    assert rel(moved[0]["Row"], moved[1]["Row"]) > 1e-2
    assert rel(moved[0]["QOut"], plain[0]["QOut"]) > 1e-2


def _ops(program, kind):
    return [op for op in program.global_block().ops if op.type == kind]


def test_the_programs_name_no_rotation_and_no_factor_two():
    """Every ``latent_rope_rows`` of this family's programs says
    ``rotate: False`` and reads no positions; every ``delta_rule_gates``
    has ``beta_scale`` 1 (no ``kda_allow_neg_eigval``); the query is ONE
    product of the normed input (no ``q_a``, no norm, no ``q_b``)."""
    sess, _tree = make_session(num_slots=2)
    progs = [sess._step_prog] + [p for rungs in sess._prefill_progs.values()
                                 for p in rungs.values()]
    for prog in progs:
        (rows,) = _ops(prog, "latent_rope_rows")
        assert rows.attrs["rotate"] is False
        assert not rows.inputs.get("Positions")
        gates = _ops(prog, "delta_rule_gates")
        assert len(gates) == 4
        assert all(op.attrs["beta_scale"] == 1.0 for op in gates)
    shapes = lad.parameter_shapes(DESC, "float32")
    assert shapes["lad_3_q"][0] == (64, 4 * 24)
    assert not [n for n in shapes if "q_a" in n or "q_b" in n
                or "q_norm" in n]


def test_an_older_familys_latent_rows_keep_their_attributes():
    """``rotate`` is written into a program only where it is asked for."""
    import test_latent_moe_decoder as glm

    built = builder_for(glm.DESC)(
        glm.DESC, 2, 48, 8, [8, 16], prefill_token_budget=32,
        dtype="float32")
    for op in _ops(built["step"], "latent_rope_rows"):
        assert "rotate" not in op.attrs and op.inputs["Positions"]


def test_the_sub_blocks_are_named_in_the_programs():
    """``fluid.name_scope``: every op of a layer's mixer and FFN says which
    sub-block it is (``op_namescope``; the lowering runs it under a
    ``jax.named_scope`` of that name)."""
    sess, _tree = make_session(num_slots=2)
    scopes = [op.attrs.get("op_namescope")
              for op in sess._step_prog.global_block().ops]
    assert set(scopes) == {None, "kda_mixer", "latent_attention",
                           "dense_ffn", "moe"}
    by_type = {}
    for op in sess._step_prog.global_block().ops:
        by_type.setdefault(op.type, set()).add(op.attrs.get("op_namescope"))
    assert by_type["delta_rule_state_update"] == {"kda_mixer"}
    assert by_type["latent_paged_attention"] == {"latent_attention"}
    assert by_type["gated_ffn"] == {"dense_ffn"}
    assert by_type["dropless_moe_ffn"] == {"moe"}


def test_the_leading_dense_layer_and_the_one_based_lists():
    assert KINDS == [lad.LINEAR] * 3 + [lad.LATENT, lad.LINEAR]
    shapes = lad.parameter_shapes(DESC, "float32")
    assert shapes["lad_0_ffn_gate"][0] == (64, 96)
    assert shapes["lad_0_ffn_down"][0] == (96, 64)
    assert "lad_0_router" not in shapes and "lad_0_qkv" in shapes
    assert "lad_1_ffn_gate" not in shapes
    # the router keeps all its outputs, the experts are the held ones
    assert shapes["lad_1_router"][0] == (64, 8)
    assert shapes["lad_1_experts_gate"][0] == (4, 64, 32)
    assert shapes["lad_3_kv_a"][0] == (64, 40)
    assert shapes["lad_3_kv_b"][0] == (32, 4 * 32)
    assert shapes["lad_3_o"][0] == (64, 64)
    sess, _tree = make_session(num_slots=5)
    geo = sess.geometry
    assert geo["moe_layers"] == [1, 2, 3, 4]
    assert geo["layer_kinds"] == KINDS
    assert geo["experts"] == {"held": 4, "of": 8, "top_k": 2}
    assert geo["prefill_chunk"] == dr.CHUNK
    # a row of 40 takes a whole tile of 128 lanes in the pool, float32 here
    assert geo["row_width"] == 40 and geo["pool_width"] == 128
    assert geo["latent_row_bytes"] == 128 * 4
    state = geo["state"]
    assert list(state["page_pools"]) == ["lad_pool_3"]
    assert state["page_pools"]["lad_pool_3"]["shape"] \
        == (geo["num_pages"], PS, 128)
    assert list(state["slot_arrays"]) == [
        "lad_s_0", "lad_win_0", "lad_s_1", "lad_win_1", "lad_s_2",
        "lad_win_2", "lad_s_4", "lad_win_4"]
    assert state["slot_arrays"]["lad_s_4"] == {
        "shape": (5, 4, 16, 16), "dtype": "float32", "slot_axis": 0}


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
    rows, _rest = slot_rows(sess, slot, 7)
    assert rel(rows[0], out["rows"][0]) < 2e-5
    assert sess.pool_conserved


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
    # a slot: 4 linear layers x (4 x 16 x 16 float32 + 3 x 192 float32)
    per_slot = 4 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert sess._slot_state_bytes == per_slot
    assert head["state_slots_live"] == 3
    assert head["state_bytes_live"] == 2 * 3 * per_slot
    # the rows the latent layer's decode reads, under the name a round
    # has for rows beside slot arrays
    assert head["kv_rows_visible"] == sum(lengths) + 3
    assert "latent_rows_resident" not in head
    assert head["prefill_chunks"] == 3
    assert head["prefill_chunks_padded"] == (4 - 2) + (2 - 1)
    assert head["prefill_pad_tokens"] == 4 * 16 + 2 * 32 - sum(lengths)
    # 2 steps x 4 expert layers x 3 slots x 2 choices
    assert head["experts_routed_tokens"] == 2 * 4 * 3 * 2
    assert 0 <= head["experts_held_tokens"] <= head["experts_routed_tokens"]
    assert 0 <= head["experts_held_hit"] <= 4


# -- what is refused, by its key ----------------------------------------------

def test_a_kimi_linear_description_reaches_this_family():
    """It has BOTH ``kv_lora_rank`` and ``linear_attn_config``: the row
    that asks for both stands before ``kv_lora_rank``'s."""
    assert builder_for(DESC) is lad.build_linear_attn_moe_decoder
    assert builder_for(DESC) is not lmd.build_latent_moe_decoder


def test_a_null_query_rank_is_refused_by_its_key_in_the_latent_family():
    """Without ``linear_attn_config`` the description is the latent
    family's, whose query is compressed: a refusal by key, not the
    ``TypeError`` of ``int(None)``."""
    alone = {k: v for k, v in DESC.items() if k != "linear_attn_config"}
    with pytest.raises(NotImplementedError, match="q_lora_rank"):
        builder_for(alone)
    with pytest.raises(NotImplementedError, match="q_lora_rank"):
        lmd.decoder_dims(alone)


_LIN = DESC["linear_attn_config"]


@pytest.mark.parametrize("change,key", [
    ({"q_lora_rank": 32}, "q_lora_rank"),
    ({"mla_use_nope": False}, "mla_use_nope"),
    ({"num_expert_group": 2}, "num_expert_group"),
    ({"topk_group": 2}, "topk_group"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"moe_router_activation_func": "softmax"},
     "moe_router_activation_func"),
    ({"linear_attn_config": dict(_LIN, num_kv_heads=2)}, "num_kv_heads"),
    ({"linear_attn_config": dict(_LIN, kda_layers=[1, 2, 3])},
     "layer 5 .* neither"),
    ({"linear_attn_config": dict(_LIN, full_attn_layers=[3, 4])},
     "layer 3 .* both"),
])
def test_builder_for_refuses_by_the_key_at_fault(change, key):
    with pytest.raises(NotImplementedError, match=key):
        builder_for(dict(DESC, **change))


# -- the share test: 4 shards add up to the uncut layer -----------------------

def test_four_shards_add_up_to_the_uncut_layer():
    """The parts of a layer's output that all the shards give (each its
    own held experts of the SAME parameters, the router's every output),
    with the shared expert and the mixer counted once, add up to the uncut
    layer of the reference: a linear layer's and the latent layer's."""
    whole = dict(DESC, num_experts=8)
    whole.pop("expert_shard")
    params = lad.random_parameters(whole, 7, "float32")
    named = {k: jnp.asarray(v) for k, v in params.items()}
    tokens = prompts_of([21], seed=4)[0]
    x = named["lad_embed"][jnp.asarray(tokens)]
    shards = 4                                   # 2 experts a shard

    def layer_of(desc, named, i, kind):
        tree = weights_kimi.tree(named, desc)
        d = ref.dims(desc)
        return ref.layer(tree["layers"][i], x, tuple(sorted(d.items())),
                         kind, jnp.asarray([0], jnp.int32))[0]

    def experts_cut(i, lo, hi):
        mine = dict(named)
        for part in ("gate", "up", "down"):
            name = "lad_%d_experts_%s" % (i, part)
            mine[name] = named[name][lo:hi]
        return mine

    with jax.default_matmul_precision("highest"):
        for i, kind in ((2, "linear"), (3, "latent")):
            uncut = layer_of(whole, named, i, kind)
            parts = [layer_of(dict(whole, num_experts=2,
                                   expert_shard={"of": 8, "first": 2 * s}),
                              experts_cut(i, 2 * s, 2 * s + 2), i, kind)
                     for s in range(shards)]
            # a shard's output is h + routed_s + shared: the residual,
            # the mixer and the shared expert are in every one of them
            once = layer_of(dict(whole, num_experts=0,
                                 expert_shard={"of": 8, "first": 0}),
                            experts_cut(i, 0, 0), i, kind)
            total = once + sum(p - once for p in parts)
            assert rel(total, uncut) < 1e-5
            assert rel(parts[0], uncut) > 1e-3
