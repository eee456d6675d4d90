"""The shortcut decoder (two latent-attention blocks and two dense
feed-forwards a layer, the expert block on a shortcut across them, a
softmax router over real AND zero-compute experts; HF ``longcat_flash``),
tiny on the CPU with widths in the published RATIOS (hidden 64, 4 heads of
8+4 beside values of 8, ranks 16 / 8, dense width 128, 8 experts of width
24 beside 4 identities, top 3, 2 layers, vocabulary 512): the third
routing rule and the identities in the expert op, the flash forward at a
value width of its own, the two scale factors, prefill of several prompts
a dispatch and then decode through ``DecoderOnlySession`` and its FOUR
pools against the plain reference's full forward (logits), the shares
test, what the builder refuses."""

import importlib
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
from paddle_tpu.models import shortcut_moe_decoder as scd  # noqa: E402
from paddle_tpu.models.decoder_programs import builder_for  # noqa: E402
from paddle_tpu.observability import tracing  # noqa: E402
from paddle_tpu.ops import decoder_ops, moe_ops  # noqa: E402
from paddle_tpu.serving.decoder_session import DecoderOnlySession  # noqa: E402
from perfbench import weights_longcat  # noqa: E402
from perfbench.reference import shortcut_moe_decoder as ref  # noqa: E402

# the package exports the function under the module's name
fa = importlib.import_module("paddle_tpu.kernels.flash_attention")

DESC = dict(hidden_size=64, num_attention_heads=4, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, q_lora_rank=16,
            kv_lora_rank=8, ffn_hidden_size=128, expert_ffn_hidden_size=24,
            n_routed_experts=8, zero_expert_num=4,
            zero_expert_type="identity", moe_topk=3, num_layers=2,
            vocab_size=512, rms_norm_eps=1e-5, rope_theta=1e7,
            routed_scaling_factor=6, mla_scale_q_lora=True,
            mla_scale_kv_lora=True, attention_method="MLA",
            attention_bias=False, max_position_embeddings=131072)


class Tap(object):
    """An executor that also fetches the logits and the chosen router
    outputs of every dispatch (what the benchmark's check does)."""

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
        self.steps.append(np.asarray(out[-2]))
        self.chosen.append(np.asarray(out[-1]))
        return out[:-2]


def make_session(desc=DESC, seed=3, num_slots=4, tap=False, **kw):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    params = scd.random_parameters(desc, seed, "float32")
    scd.load_parameters(scope, params, desc, "float32")
    args = dict(num_slots=num_slots, max_prompt=32, max_new_tokens=16,
                page_size=8, tokens_per_dispatch=2,
                prefill_token_budget=64, scope=scope, dtype="float32")
    args.update(kw)
    sess = DecoderOnlySession(exe, desc, **args)
    if tap:
        sess._exe = Tap(exe, sess._fetch)
    return sess, weights_longcat.tree(
        {k: jnp.asarray(v) for k, v in params.items()}, desc)


def rel(got, want):
    got, want = np.asarray(got, "float64"), np.asarray(want, "float64")
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def prompts_of(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(3, DESC["vocab_size"], n) for n in lengths]


def _moe_params(rng, E=8, Z=4, D=64, F=24, gain=4.0):
    def mat(*shape):
        return jnp.asarray(rng.standard_normal(shape) * shape[-2] ** -0.5,
                           jnp.float32)

    return {"router": mat(D, E + Z) * gain,
            "router_bias": jnp.asarray(rng.uniform(-.01, .01, E + Z),
                                       jnp.float32),
            "gate": mat(E, D, F), "up": mat(E, D, F), "down": mat(E, F, D)}


def _moe_ins(p, x, valid=None):
    ins = {"X": [x], "RouterW": [p["router"]],
           "RouterBias": [p["router_bias"]], "ExpertWGate": [p["gate"]],
           "ExpertWUp": [p["up"]], "ExpertWDown": [p["down"]]}
    if valid is not None:
        ins["Valid"] = [valid]
    return ins


_MOE_D = dict(k=3, Z=4, scale=6.0, first=0)
_MOE_ATTRS = dict(top_k=3, norm_topk=False, scale=6.0, scoring="softmax",
                  zero_experts=4)


# -- the expert op: the third rule and the identities --------------------------

def test_softmax_routing_is_over_all_outputs_and_not_renormalised():
    rng = np.random.RandomState(0)
    p = _moe_params(rng)
    x = jnp.asarray(rng.standard_normal((13, 64)), jnp.float32)
    chosen, w = moe_ops.route_softmax(x, p["router"], p["router_bias"], 3,
                                      6.0)
    prob, biased, own = ref.route(p, x, _MOE_D)
    assert (np.sort(np.asarray(chosen), -1)
            == np.sort(np.asarray(own), -1)).all()
    assert rel(w, 6.0 * np.take_along_axis(np.asarray(prob),
                                           np.asarray(chosen), -1)) < 1e-6
    # a softmax over all 12 outputs: the chosen 3 hold less than all of it
    assert (np.asarray(w).sum(-1) < 6.0).all()
    assert np.allclose(np.asarray(prob).sum(-1), 1.0, atol=1e-6)


def test_the_bias_moves_the_choice_and_not_the_weight():
    rng = np.random.RandomState(1)
    p = _moe_params(rng)
    x = jnp.asarray(rng.standard_normal((9, 64)), jnp.float32)
    _c0, _w0 = moe_ops.route_softmax(x, p["router"], p["router_bias"], 3,
                                     6.0)
    bias = p["router_bias"].at[5].add(10.0)       # expert 5: always chosen
    chosen, w = moe_ops.route_softmax(x, p["router"], bias, 3, 6.0)
    chosen, w = np.asarray(chosen), np.asarray(w)
    assert (chosen == 5).any(-1).all()
    prob = np.asarray(ref.route(p, x, _MOE_D)[0])
    # its weight is scale * p, with nothing of the bias in it
    at = np.argmax(chosen == 5, -1)
    assert np.allclose(w[np.arange(9), at], 6.0 * prob[:, 5], rtol=1e-6)


def test_the_op_matches_the_references_expert_block():
    """Real experts through the grouped products, identities as ``w * x``,
    tokens that do not exist neither computed nor counted."""
    rng = np.random.RandomState(2)
    p = _moe_params(rng)
    x = jnp.asarray(rng.standard_normal((21, 64)), jnp.float32)
    valid = jnp.asarray(np.arange(21) % 4 != 0, jnp.int32)
    out = moe_ops._lower_dropless_moe_ffn(None, _moe_ins(p, x, valid),
                                          _MOE_ATTRS)
    want, _b, own = ref.moe(p, x, _MOE_D)
    live = np.asarray(valid) > 0
    assert rel(np.asarray(out["Out"])[live], np.asarray(want)[live]) < 1e-5
    chosen = np.asarray(out["Chosen"])
    assert (np.sort(chosen, -1) == np.sort(np.asarray(own), -1)).all()
    assert list(np.asarray(out["ExpertTokens"])) == [
        int((chosen[live] == e).sum()) for e in range(8)]
    assert int(out["ZeroTokens"][0]) == int((chosen[live] >= 8).sum()) > 0
    # without the identities' term the reference reads another result
    less, _b, _o = ref.moe(p, x, _MOE_D, identities=False)
    assert rel(np.asarray(less)[live], np.asarray(want)[live]) > 1e-2


def test_a_token_whose_choices_are_all_identities_is_itself_times_their_sum():
    rng = np.random.RandomState(3)
    p = _moe_params(rng)
    # the bias lifts every identity over every real expert
    p["router_bias"] = p["router_bias"].at[8:].add(10.0)
    x = jnp.asarray(rng.standard_normal((7, 64)), jnp.float32)
    out = moe_ops._lower_dropless_moe_ffn(None, _moe_ins(p, x), _MOE_ATTRS)
    chosen = np.asarray(out["Chosen"])
    assert (chosen >= 8).all()
    assert int(out["ZeroTokens"][0]) == 7 * 3
    assert int(np.asarray(out["ExpertTokens"]).sum()) == 0
    prob = np.asarray(ref.route(p, x, _MOE_D)[0])
    w = 6.0 * np.take_along_axis(prob, chosen, -1).sum(-1)
    assert rel(out["Out"], w[:, None] * np.asarray(x)) < 1e-6


def test_without_zero_experts_the_op_lowers_as_before():
    """``zero_experts`` 0 (every existing program): no ``ZeroTokens``, and
    the jaxpr of the lowering is what the attributes without it give."""
    rng = np.random.RandomState(4)
    p = _moe_params(rng, Z=0)
    x = jnp.asarray(rng.standard_normal((11, 64)), jnp.float32)
    old = dict(top_k=3, norm_topk=True, scale=2.5, held_first=-1)

    def lower(attrs):
        return lambda x: moe_ops._lower_dropless_moe_ffn(
            None, _moe_ins(p, x), attrs)

    out = lower(old)(x)
    assert sorted(out) == ["Chosen", "ExpertTokens", "Out"]
    assert str(jax.make_jaxpr(lower(old))(x)) == str(jax.make_jaxpr(
        lower(dict(old, zero_experts=0, scoring="sigmoid")))(x))
    # and the layer function names neither attribute unless asked
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        nn = fluid.layers
        v = {n: nn.data(n, shape=list(a.shape), dtype="float32",
                        append_batch_size=False)
             for n, a in dict(p, x=x).items()}
        nn.dropless_moe_ffn(v["x"], v["router"], v["router_bias"],
                            v["gate"], v["up"], v["down"], top_k=3)
    op = [o for o in main.global_block().ops
          if o.type == "dropless_moe_ffn"][0]
    assert "zero_experts" not in op.attrs and "scoring" not in op.attrs
    assert "ZeroTokens" not in op.outputs


def test_the_shards_parts_add_up_to_the_uncut_layer():
    """The guide's share test: over all 4 shards of 2 real experts, each
    shard's part of the routed sum (the op told which experts it holds)
    with the identities' term counted ONCE add up to the reference's uncut
    expert block; and at the layer, shard 0's whole layer (attention, both
    dense feed-forwards and the identities, which every chip computes
    alike) plus the other shards' expert parts is the uncut layer."""
    rng = np.random.RandomState(5)
    p = _moe_params(rng)
    x = jnp.asarray(rng.standard_normal((19, 64)), jnp.float32)
    uncut, _b, own = ref.moe(p, x, _MOE_D)
    total, tokens, zeros = 0.0, [], []
    for first in range(0, 8, 2):
        held = dict(p, gate=p["gate"][first:first + 2],
                    up=p["up"][first:first + 2],
                    down=p["down"][first:first + 2])
        out = moe_ops._lower_dropless_moe_ffn(
            None, _moe_ins(held, x), dict(_MOE_ATTRS, held_first=first))
        want, _b, _o = ref.moe(held, x, dict(_MOE_D, first=first))
        assert rel(out["Out"], want) < 1e-5
        # every shard computes the identities: counted once in the sum
        ident, _b, _o = ref.moe(held, x, dict(_MOE_D, first=first),
                                identities=False)
        total = total + np.asarray(out["Out"], "float64") - (
            0 if first == 0 else np.asarray(want - ident, "float64"))
        tokens += list(np.asarray(out["ExpertTokens"]))
        zeros.append(int(out["ZeroTokens"][0]))
    assert rel(total, uncut) < 1e-5
    own = np.asarray(own)
    assert tokens == list(np.bincount(own.reshape(-1), minlength=12)[:8])
    assert set(zeros) == {int((own >= 8).sum())}
    assert sum(tokens) + zeros[0] == 19 * 3

    # the layer: the shortcut's sum is added at the layer's END, so the
    # shards' parts add there too
    params = scd.random_parameters(DESC, 7, "float32")
    tree = weights_longcat.tree(
        {k: jnp.asarray(v) for k, v in params.items()}, DESC)
    layer = tree["layers"][0]
    d = ref.dims(DESC)
    dkey = tuple(sorted(d.items()))
    h = jnp.asarray(rng.standard_normal((19, 64)), jnp.float32)
    whole, _b, _o, fed = ref.layer(layer, h, dkey)
    total = None
    for first in range(0, 8, 2):
        moe = dict(layer["moe"], **{k: layer["moe"][k][first:first + 2]
                                    for k in ("gate", "up", "down")})
        dk = tuple(sorted(dict(d, first=first).items()))
        if first == 0:
            total = np.asarray(
                ref.layer(dict(layer, moe=moe), h, dk)[0], "float64")
        else:
            total += np.asarray(ref.moe(moe, fed, dict(d, first=first),
                                        identities=False)[0], "float64")
    assert rel(total, whole) < 1e-5


def test_a_large_dispatch_with_identities_goes_through_in_blocks():
    rng = np.random.RandomState(6)
    N, D, F = 2 * moe_ops._HELD_TOKEN_BLOCK, 16, 8
    p = _moe_params(rng, E=4, Z=4, D=D, F=F)
    p["router"] = jnp.pad(p["router"], ((0, 0), (8, 0)))   # 12 real, 4 held
    p["router_bias"] = jnp.zeros((16,), jnp.float32)
    x = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    valid = jnp.asarray(np.arange(N) % 5 != 0, jnp.int32)
    attrs = dict(_MOE_ATTRS, held_first=8)
    out = moe_ops._lower_dropless_moe_ffn(None, _moe_ins(p, x, valid), attrs)
    want, _b, _o = ref.moe(p, x, dict(_MOE_D, first=8))
    live = np.asarray(valid) > 0
    assert rel(np.asarray(out["Out"])[live], np.asarray(want)[live]) < 1e-5
    chosen = np.asarray(out["Chosen"])[live]
    assert list(np.asarray(out["ExpertTokens"])) == [
        int((chosen == e).sum()) for e in range(8, 12)]
    assert int(out["ZeroTokens"][0]) == int((chosen >= 12).sum())


# -- the flash forward at a value width of its own -----------------------------

@pytest.mark.parametrize("T", [48, 640], ids=["one_tile", "kv_tiles"])
def test_flash_forward_takes_a_value_width_of_its_own(T):
    """Queries and keys of 192 beside values of 128 (interpret mode): the
    kernel against ``flash_attention_reference``; nothing is padded to the
    query width."""
    rng = np.random.RandomState(7)
    q, k = (jnp.asarray(rng.standard_normal((2, 2, T, 192)) * 0.3,
                        jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.standard_normal((2, 2, T, 128)), jnp.float32)
    got = fa.flash_attention(q, k, v, causal=True, force_pallas=True)
    want = fa.flash_attention_reference(q, k, v, causal=True)
    assert got.shape == (2, 2, T, 128)
    assert rel(got, want) < 2e-5
    # the backward refuses it by name
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda v: fa.flash_attention(
            q, k, v, causal=True, force_pallas=True).sum())(v)


def test_flash_forward_at_equal_widths_is_what_it_was():
    """With ``v`` as wide as ``q`` the forward traces to the SAME jaxpr
    whether or not the value width is looked at: one set of block specs,
    the accumulator and the output at the query width."""
    rng = np.random.RandomState(8)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 2, 40, 64)),
                           jnp.float32) for _ in range(3))
    got = fa.flash_attention(q, k, v, causal=True, force_pallas=True)
    assert rel(got, fa.flash_attention_reference(q, k, v, causal=True)) \
        < 2e-5
    text = str(jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, force_pallas=True))(q, k, v))
    assert "128" not in text.split("pallas_call")[0]
    # it still differentiates
    g = jax.grad(lambda v: fa.flash_attention(
        q, k, v, causal=True, force_pallas=True).sum())(v)
    assert np.isfinite(np.asarray(g)).all()


# -- the two scale factors ------------------------------------------------------

def test_the_scale_factors_are_applied_before_the_rows_are_rounded():
    rng = np.random.RandomState(9)
    N, H, dn, dr, C = 6, 4, 8, 4, 8
    q = jnp.asarray(rng.standard_normal((N, H * (dn + dr))), jnp.float32)
    kva = jnp.asarray(rng.standard_normal((N, C + dr)), jnp.float32)
    g = jnp.asarray(1 + 0.1 * rng.standard_normal(C), jnp.float32)
    pos = jnp.asarray([0, 1, 5, 17, 40, 41])
    base = dict(heads=H, nope_dim=dn, rope_dim=dr, theta=1e7,
                interleave=True)
    ins = {"Q": [q], "KVA": [kva], "KVNorm": [g], "Positions": [pos]}
    plain = decoder_ops._lower_latent_rope_rows(None, ins, base)
    scaled = decoder_ops._lower_latent_rope_rows(
        None, ins, dict(base, q_scale=2.0, kv_scale=8 ** 0.5))
    assert rel(scaled["QOut"], 2.0 * plain["QOut"]) < 1e-6
    assert rel(scaled["Row"][:, :C], 8 ** 0.5 * plain["Row"][:, :C]) < 1e-6
    # the rotary key is not scaled
    assert (np.asarray(scaled["Row"][:, C:])
            == np.asarray(plain["Row"][:, C:])).all()
    # bfloat16 rows: the factor goes in before the rounding
    insb = {k: [v[0].astype(jnp.bfloat16) if k != "Positions" else v[0]]
            for k, v in ins.items()}
    rowb = decoder_ops._lower_latent_rope_rows(
        None, insb, dict(base, kv_scale=8 ** 0.5))["Row"]
    want = (decoder_ops.rms_norm(insb["KVA"][0][:, :C].astype(jnp.float32),
                                 insb["KVNorm"][0], 1e-5)
            * 8 ** 0.5).astype(jnp.bfloat16)
    assert (np.asarray(rowb[:, :C], "float32")
            == np.asarray(want, "float32")).all()
    # the programs of the models without them keep their attributes
    d = scd.decoder_dims(DESC)
    assert d["q_scale"] == 2.0 and abs(d["kv_scale"] - 8 ** 0.5) < 1e-12
    assert scd.decoder_dims(dict(DESC, mla_scale_q_lora=False))[
        "q_scale"] == 1.0


# -- the session ----------------------------------------------------------------

def test_batched_prefill_then_decode_match_the_reference():
    """3 prompts of different lengths in ONE prefill dispatch, then 12
    decoded positions through the session and its four pools, against the
    reference's full forward over the same tokens: logits compared, and
    the router's choice, identities among them."""
    sess, tree = make_session(tap=True)
    assert list(sess.geometry["state"]["page_pools"]) == [
        "scd_pool_%d" % j for j in range(4)]
    lengths = [9, 16, 11]
    prompts = prompts_of(lengths)
    for p in prompts:
        sess.enqueue(p)
    admitted = sess.admit_pending()
    assert len(admitted) == 3 and sess.prefill_dispatches == 1
    slots = {rid: slot for slot, rid in admitted.items()}
    for _ in range(6):
        assert sess.step() == {}
    feed, first_logits, first_chosen = sess._exe.prefill[0]
    step_logits = np.concatenate(sess._exe.steps)          # [12, S, 1, V]
    step_chosen = np.concatenate(sess._exe.chosen)         # [12, L, S, k]
    saw_identity = False
    for rid, prompt in enumerate(prompts):
        slot, n = slots[rid], len(prompt)
        toks = sess.tokens_of(slot)
        assert len(toks) == 13
        seq = np.concatenate([prompt, toks[:-1]])
        row = list(feed["slot_idx"]).index(slot)
        out = ref.forward(tree, jnp.asarray(seq), DESC,
                          logits_at=list(range(n - 1, n + 12)))
        got = np.concatenate([first_logits[row], step_logits[:, slot, 0]])
        assert rel(got, out["logits"]) < 2e-5
        assert (got.argmax(-1) == toks).all()
        own = np.stack(out["own"])                         # [L, n + 12, k]
        mine = np.concatenate(
            [first_chosen[:, row * 16:row * 16 + n],
             np.transpose(step_chosen[:, :, slot], (1, 0, 2))], 1)
        assert (np.sort(own, -1) == np.sort(mine, -1)).all()
        saw_identity = saw_identity or bool((mine >= 8).any())
        # each control reads something else
        for control in (dict(identities=False), dict(sequential=True)):
            other = ref.forward(tree, jnp.asarray(seq), DESC,
                                logits_at=list(range(n - 1, n + 12)),
                                **control)
            assert rel(other["logits"], out["logits"]) > 1e-2
    assert saw_identity
    assert sess.pool_conserved
    assert sess.pages_in_use == sum(-(-(n + 12) // 8) for n in lengths)


def test_a_held_shard_serves_its_part_and_counts_the_identities():
    """``expert_shard``: 2 of 8 real experts held, the router's 12 outputs
    kept. Logits against the reference given the same shard, and the
    round's counters: choices routed, held, and fallen on an identity."""
    desc = dict(DESC, n_routed_experts=2,
                expert_shard={"of": 8, "first": 2})
    sess, tree = make_session(desc, tap=True)
    assert sess.geometry["experts"] == {"held": 2, "of": 8, "top_k": 3,
                                        "zero": 4}
    assert tree["layers"][0]["moe"]["router"].shape == (64, 12)
    assert tree["layers"][0]["moe"]["gate"].shape[0] == 2
    prompts = prompts_of([13, 10], seed=1)
    for p in prompts:
        sess.enqueue(p)
    admitted = sess.admit_pending()
    tracing.enable(True)
    try:
        sess.step()
    finally:
        tracing.enable(False)
    c = sess.last_counters
    assert c["experts_routed_tokens"] == 2 * 2 * 2 * 3   # K x L x live x k
    chosen = np.concatenate(sess._exe.chosen)[:, :, sorted(admitted)]
    assert c["experts_zero_tokens"] == int((chosen >= 8).sum())
    assert c["experts_held_tokens"] == int(
        ((chosen >= 2) & (chosen < 4)).sum())
    feed, first_logits, _ch = sess._exe.prefill[0]
    step_logits = np.concatenate(sess._exe.steps)
    for slot, rid in admitted.items():
        prompt, n = prompts[rid], len(prompts[rid])
        toks = sess.tokens_of(slot)
        seq = np.concatenate([prompt, toks[:-1]])
        out = ref.forward(tree, jnp.asarray(seq), desc,
                          logits_at=list(range(n - 1, n + 2)))
        row = list(feed["slot_idx"]).index(slot)
        got = np.concatenate([first_logits[row], step_logits[:, slot, 0]])
        assert rel(got, out["logits"]) < 2e-5


def test_the_sub_blocks_say_which_they_are_in_the_compiled_program():
    """``fluid.name_scope`` around the expert block and the dense
    feed-forwards: the ops carry ``op_namescope`` and the lowered step's
    instructions say ``shortcut_moe`` / ``dense_ffn_0`` / ``dense_ffn_1``."""
    sess, _tree = make_session()
    ops = sess._step_prog.global_block().ops
    scopes = [op.attrs.get("op_namescope") for op in ops
              if op.type in ("dropless_moe_ffn", "gated_ffn")]
    assert scopes == ["shortcut_moe", "dense_ffn_0", "dense_ffn_1"] * 2
    for p in prompts_of([5]):
        sess.enqueue(p)
    sess.admit_pending()
    sess.step()
    text = sess._exe.compiled_text(sess._step_prog)
    text = text if isinstance(text, str) else "\n".join(text)
    for scope in ("shortcut_moe", "dense_ffn_0", "dense_ffn_1"):
        assert scope in text


# -- what the builder refuses -----------------------------------------------------

@pytest.mark.parametrize("key,value", [
    ("zero_expert_type", "copy"), ("attention_method", "GQA"),
    ("attention_bias", True), ("router_bias", True),
    ("rope_scaling", {"type": "yarn"}), ("norm_topk_prob", True),
    ("expert_shard", {"of": 8, "first": 7})],
    ids=["zero_expert_type", "attention_method", "attention_bias",
         "router_bias", "rope_scaling", "norm_topk_prob", "expert_shard"])
def test_a_description_the_builder_does_not_serve_is_refused_by_its_key(
        key, value):
    assert builder_for(DESC) is scd.build_shortcut_moe_decoder
    bad = dict(DESC, **{key: value})
    early = key != "expert_shard"
    for refuse in (scd.decoder_dims,) + ((builder_for,) if early else ()):
        with pytest.raises((NotImplementedError, ValueError)) as err:
            refuse(bad)
        assert key in str(err.value)


def test_the_description_is_this_familys_before_the_latent_decoders():
    """It has ``kv_lora_rank`` too: the row asked first takes it; a latent
    decoder's description (no ``zero_expert_num``) still goes where it
    went, and one whose value width differs from its query width builds
    (the refusal went with the flash forward's value width)."""
    from paddle_tpu.models import latent_moe_decoder as lmd

    assert "kv_lora_rank" in DESC
    glm = dict(hidden_size=64, num_attention_heads=4, qk_nope_head_dim=12,
               qk_rope_head_dim=4, v_head_dim=8, q_lora_rank=24,
               kv_lora_rank=16, intermediate_size=320,
               moe_intermediate_size=48, n_routed_experts=8,
               num_experts_per_tok=2, num_hidden_layers=2, vocab_size=512)
    assert builder_for(glm) is lmd.build_latent_moe_decoder
    assert lmd.decoder_dims(glm)["dv"] == 8
