"""A decoder-only language model whose layer holds TWO latent-attention
(MLA) blocks and two dense feed-forwards with the routed-expert block on a
SHORTCUT across them, built for SERVING from a description: a dict of the
model's own ``config.json`` keys (HF ``longcat_flash`` naming:
``num_layers``, ``ffn_hidden_size``, ``expert_ffn_hidden_size``,
``moe_topk``, ``zero_expert_num``, ...).

    layer:  h0 = x  + MLA_0(RMSNorm(x));   u0 = RMSNorm(h0)
            m  = MoE(u0)                    # the shortcut: NOT added here
            h1 = h0 + FFN_0(u0)
            h2 = h1 + MLA_1(RMSNorm(h1));  u1 = RMSNorm(h2)
            y  = h2 + FFN_1(u1) + m
    MLA:    the ``deepseek_v3`` form (``models/latent_moe_decoder.py``'s:
            the cache holds one row ``[c | RoPE(k_rope)]`` a token, decode
            attends in the absorbed form, prefill in the expanded form
            through the flash kernel) with queries and keys of
            ``qk_nope_head_dim + qk_rope_head_dim`` beside values of
            ``v_head_dim`` (192 beside 128) and two constants:
            ``mla_scale_q_lora`` multiplies the query by
            ``sqrt(hidden_size / q_lora_rank)``, ``mla_scale_kv_lora`` the
            normed compressed row by ``sqrt(hidden_size / kv_lora_rank)``,
            both in float32 before the rows are rounded (attributes of
            ``latent_rope_rows``: a checkpoint's arrays are loaded as they
            are)
    MoE:    ``p = softmax`` over the router's ``n_routed_experts +
            zero_expert_num`` outputs, the ``moe_topk`` largest of ``p +
            b`` chosen, weights ``routed_scaling_factor * p`` NOT
            renormalised; a choice among the last ``zero_expert_num`` is a
            zero-compute (identity) expert: ``+ w * u0``, no product. No
            shared expert and no dense layer: a token runs 0 to
            ``moe_topk`` real experts

With ``expert_shard`` (``{"of": E_all, "first": f}``) ``n_routed_experts``
counts the experts HELD here, as ``latent_moe_decoder`` has it: the router
keeps every output (``E_all + zero_expert_num``), the expert op computes
the held experts' part of the sum and EVERY identity (they are the token's
own chip's work), and leaves the rest out. ``vocab_size`` may be a slice.

State: a layer owns TWO pools of latent rows, ``scd_pool_<2 l + a>``
``[pages, page_size, pool_width(row)]`` for its attention block ``a``,
all under one page table. The programs' frame is
``models/decoder_programs.py``'s.
"""

import collections
import functools

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.core.types import np_dtype
from paddle_tpu.kernels.latent_attention import pool_width
from paddle_tpu.models import decoder_programs

__all__ = ["decoder_dims", "check_served", "parameter_shapes",
           "random_parameters", "load_parameters",
           "build_shortcut_moe_decoder"]

# tokens a block through the held experts: 12 choices a token sort 12288
# rows a block (the op's own 2048 would sort 24576 rows of 6144 and hold
# 1.3 GB of temporaries at the published widths, compiled for a v5e)
_TOKEN_BLOCK = 1024


def check_served(desc):
    """Refuse, by the key at fault, a description whose keys ask for what
    this builder does not serve (only the keys that are there are read)."""
    refusals = (
        ("zero_expert_type", "identity",
         "a zero-compute expert returns the token itself"),
        ("attention_method", "MLA", "the attention blocks are latent"),
        ("attention_bias", False, "the projections have no bias"),
        ("router_bias", False, "the router's product has no bias of its "
         "own (the selection bias is `e_score_correction_bias`)"),
        ("rope_scaling", None, "RoPE's frequencies are not rescaled"),
        ("norm_topk_prob", False,
         "the chosen weights are not renormalised"))
    for key, served, why in refusals:
        if desc.get(key, served) != served:
            raise NotImplementedError(
                "%s=%r: only %r is built (%s)"
                % (key, desc[key], served, why))


def decoder_dims(desc):
    """The sizes the programs are built from, by the config's keys."""
    check_served(desc)
    d = dict(
        D=int(desc["hidden_size"]), H=int(desc["num_attention_heads"]),
        dn=int(desc["qk_nope_head_dim"]), dr=int(desc["qk_rope_head_dim"]),
        dv=int(desc["v_head_dim"]), rq=int(desc["q_lora_rank"]),
        C=int(desc["kv_lora_rank"]), F=int(desc["ffn_hidden_size"]),
        Fe=int(desc["expert_ffn_hidden_size"]),
        E=int(desc["n_routed_experts"]), Z=int(desc["zero_expert_num"]),
        k=int(desc["moe_topk"]), L=int(desc["num_layers"]),
        V=int(desc["vocab_size"]),
        eps=float(desc.get("rms_norm_eps", 1e-5)),
        theta=float(desc.get("rope_theta", 10000.0)),
        scale=float(desc.get("routed_scaling_factor", 1.0)))
    d["q_scale"] = ((d["D"] / float(d["rq"])) ** 0.5
                    if desc.get("mla_scale_q_lora") else 1.0)
    d["kv_scale"] = ((d["D"] / float(d["C"])) ** 0.5
                     if desc.get("mla_scale_kv_lora") else 1.0)
    # a shard of the real experts: E held of Er routed among, from ``first``
    shard = desc.get("expert_shard")
    d["Er"] = int(shard["of"]) if shard else d["E"]
    d["first"] = int(shard["first"]) if shard else None
    if shard and not 0 <= d["first"] <= d["Er"] - d["E"]:
        raise ValueError(
            "expert_shard %r: n_routed_experts=%d experts from `first` do "
            "not lie among its `of`" % (shard, d["E"]))
    d["W"] = d["C"] + d["dr"]          # the cached row
    d["Wp"] = pool_width(d["W"])       # as the pool holds it
    return d


def parameter_shapes(desc, dtype="bfloat16"):
    """{name: (shape, dtype)} of every parameter, in layer order:
    ``scd_<l>_<a>_<part>`` for attention block ``a``'s sub-block,
    ``scd_<l>_<part>`` for the layer's router and experts. The router's
    selection bias is float32 whatever ``dtype`` is."""
    d = decoder_dims(desc)
    D, H = d["D"], d["H"]
    out = collections.OrderedDict()

    def add(name, *shape, **kw):
        out[name] = (tuple(shape), kw.get("dtype", dtype))

    add("scd_embed", d["V"], D)
    for i in range(d["L"]):
        for a in (0, 1):
            p = "scd_%d_%d_" % (i, a)
            add(p + "attn_norm", D)
            add(p + "q_a", D, d["rq"])
            add(p + "q_norm", d["rq"])
            add(p + "q_b", d["rq"], H * (d["dn"] + d["dr"]))
            add(p + "kv_a", D, d["W"])
            add(p + "kv_norm", d["C"])
            add(p + "kv_b", d["C"], H * (d["dn"] + d["dv"]))
            add(p + "o", H * d["dv"], D)
            add(p + "ffn_norm", D)
            add(p + "ffn_gate", D, d["F"])
            add(p + "ffn_up", D, d["F"])
            add(p + "ffn_down", d["F"], D)
        p = "scd_%d_" % i
        add(p + "router", D, d["Er"] + d["Z"])
        add(p + "router_bias", d["Er"] + d["Z"], dtype="float32")
        add(p + "experts_gate", d["E"], D, d["Fe"])
        add(p + "experts_up", d["E"], D, d["Fe"])
        add(p + "experts_down", d["E"], d["Fe"], D)
    add("scd_final_norm", D)
    add("scd_head", D, d["V"])
    return out


def random_parameters(desc, seed=0, dtype="float32", router_gain=4.0):
    """Seeded parameters for tests and examples (host arrays; a real
    model's come from its checkpoint): matrices ``N(0, 1/fan_in)``, norm
    scales near 1, the selection bias uniform in +-0.01, and the router
    ``N(0, router_gain^2 / fan_in)``: a token's logits then have a standard
    deviation near ``router_gain`` and the chosen few hold most of the
    probability, as a trained router's do (at unit gain every weight is
    ``scale / outputs`` and the expert block vanishes from the result)."""
    rng = np.random.RandomState(seed)
    out = collections.OrderedDict()
    for name, (shape, dt) in parameter_shapes(desc, dtype).items():
        if name.endswith("router_bias"):
            v = rng.uniform(-0.01, 0.01, shape)
        elif name.endswith("norm"):
            v = 1.0 + 0.1 * rng.standard_normal(shape)
        elif name == "scd_embed":
            v = rng.standard_normal(shape)
        else:
            v = rng.standard_normal(shape) * shape[-2] ** -0.5
            if name.endswith("router"):
                v = v * router_gain
        out[name] = np.asarray(v, "float32").astype(np_dtype(dt))
    return out


load_parameters = functools.partial(decoder_programs.load_parameters,
                                    parameter_shapes)


def _family(desc, dtype, tokens_per_dispatch):
    """This family's layers and state (``decoder_programs.DecoderFamily``).
    Beside the frame's fetches: ``chosen`` / ``first_chosen`` (a token's
    router outputs a layer, identities among them), ``expert_tokens`` (the
    tokens each held expert got) and ``zero_tokens`` (the choices that fell
    on an identity) ``[layers, 1]``."""
    nn = fluid.layers
    d = decoder_dims(desc)

    def state(S, P, ps, npp):
        # what a slot owns: pages of latent rows in TWO pools a layer,
        # one for each of its attention blocks; nothing of fixed size
        return {"page_pools": collections.OrderedDict(
            ("scd_pool_%d" % j, {"shape": (P, ps, d["Wp"]), "dtype": dtype})
            for j in range(2 * d["L"])), "slot_arrays": {}}

    def blocks(f, x, attend):
        """The L layers and the final norm on token rows ``x`` [N, D];
        ``attend(j, q, kva, w)`` writes attention block ``j``'s rows
        (``j = 2 l + a``) and attends (``w(part)``: the sub-block's
        parameter). Returns (x, chosen, tokens per held expert, choices on
        an identity), each per layer."""
        w = f.w
        chosen, counts, zeros = [], [], []
        for i in range(d["L"]):
            p = "scd_%d_" % i
            for a in (0, 1):
                def part(name, p=p + "%d_" % a):
                    return w(p + name)

                nx = nn.rms_norm(x, part("attn_norm"), d["eps"])
                cq = nn.rms_norm(nn.dense_projection(nx, part("q_a")),
                                 part("q_norm"), d["eps"])
                q = nn.dense_projection(cq, part("q_b"))
                kva = nn.dense_projection(nx, part("kv_a"))
                att = attend(2 * i + a, q, kva, part)
                x = nn.elementwise_add(
                    x, nn.dense_projection(att, part("o")))
                u = nn.rms_norm(x, part("ffn_norm"), d["eps"])
                if a == 0:
                    # the shortcut: the expert block reads the FIRST
                    # sub-block's normed rows and is added after the
                    # second's feed-forward
                    with fluid.name_scope("shortcut_moe"):
                        moe, ch, cnt, zero = nn.dropless_moe_ffn(
                            u, w(p + "router"), w(p + "router_bias"),
                            w(p + "experts_gate"), w(p + "experts_up"),
                            w(p + "experts_down"), valid=f.valid,
                            top_k=d["k"], norm_topk=False, scale=d["scale"],
                            held_first=d["first"], scoring="softmax",
                            zero_experts=d["Z"], token_block=_TOKEN_BLOCK)
                    chosen.append(ch)
                    counts.append(cnt)
                    zeros.append(zero)
                with fluid.name_scope("dense_ffn_%d" % a):
                    x = nn.elementwise_add(x, nn.gated_ffn(
                        u, part("ffn_gate"), part("ffn_up"),
                        part("ffn_down")))
            x = nn.elementwise_add(x, moe)
        return (nn.rms_norm(x, w("scd_final_norm"), d["eps"]), chosen,
                counts, zeros)

    rope_attrs = dict(heads=d["H"], nope_dim=d["dn"], rope_dim=d["dr"],
                      theta=d["theta"], epsilon=d["eps"], interleave=True,
                      q_scale=d["q_scale"], kv_scale=d["kv_scale"])

    def prefill(f, x):
        B, T = f.rows, f.bucket

        def attend(j, q, kva, w):
            q, row = nn.latent_rope_rows(
                q, kva, w("kv_norm"), period=T, **rope_attrs)
            nn.latent_row_prefill(f.state["scd_pool_%d" % j], row,
                                  f.page_rows, f.lens)
            return nn.latent_prefill_attention(
                q, row, w("kv_b"), prompts=B, nope_dim=d["dn"])

        x, chosen, _counts, _zeros = blocks(f, x, attend)
        return x, [("first_chosen", chosen)]

    def step(f, x):
        def attend(j, q, kva, w):
            pool = f.state["scd_pool_%d" % j]
            q, row = nn.latent_rope_rows(
                q, kva, w("kv_norm"), positions=f.pos, **rope_attrs)
            nn.latent_row_write(pool, row, f.table, f.pos)
            return nn.latent_paged_attention(
                q, w("kv_b"), pool, f.table, f.lengths, nope_dim=d["dn"])

        x, chosen, counts, zeros = blocks(f, x, attend)
        return x, [("chosen", chosen), ("expert_tokens", counts),
                   ("zero_tokens", zeros)]

    return decoder_programs.DecoderFamily(
        "scd", parameter_shapes(desc, dtype), d["V"], state, prefill, step,
        geometry={
            "row_width": d["W"], "pool_width": d["Wp"],
            "moe_layers": list(range(d["L"])),
            # the real experts held of those routed among, the identities
            # beside them, a token's choices over both
            "experts": {"held": d["E"], "of": d["Er"], "top_k": d["k"],
                        "zero": d["Z"]}})


build_shortcut_moe_decoder = functools.partial(
    decoder_programs.build_decoder_programs, _family)
