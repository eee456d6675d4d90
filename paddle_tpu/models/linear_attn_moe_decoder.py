"""A decoder-only language model whose mixers are of two kinds, gated
delta-rule LINEAR attention in most layers and softmax attention in a few,
with routed experts, built for SERVING from a description: a dict of the
model's own ``config.json`` keys. Two public namings are read:

* HF ``solar_open2`` (``gqa_layers``, ``use_gqa_gate``,
  ``kda_allow_neg_eigval``, ``n_routed_experts``): the full layer is gated
  grouped-query attention over K and V page pools, every FFN is routed;
* HF ``kimi_linear`` (``kv_lora_rank`` beside ``linear_attn_config``,
  1-based ``kda_layers`` / ``full_attn_layers``, ``num_experts``,
  ``num_experts_per_token``, ``moe_renormalize``, ``mla_use_nope``): the
  full layer is LATENT attention (``models/latent_moe_decoder.py``'s layer
  functions) with no rotation and no query compression over a pool of
  latent rows, and the first ``first_k_dense_replace`` layers' FFN is a
  dense SwiGLU.

    block:   h = x + Mixer_i(RMSNorm(x));   y = h + FFN(RMSNorm(h))
    FFN:     Shared(x) + scale * sum_i w_i Expert_i(x), top-k of E routed
             experts by sigmoid score plus a selection bias, no token dropped
             (SwiGLU(``intermediate_size``) in a leading dense layer)
    gqa:     q, k, v, gate = u Wq, u Wk, u Wv, u Wg (no bias, NO positional
             encoding), causal softmax, grouped-query;
             out = (attn * sigmoid(gate)) Wo
    latent:  q = u Wq [H, dn + dr]; [ckv | k_pe] = u Wkv_a; the cache
             holds ``[RMSNorm(ckv) | k_pe]`` (NO rotation), every head's
             ``[k_nope | v] = row[:C] Wkv_b``, ``k = [k_nope | k_pe]``;
             causal softmax at (dn + dr)^-1/2; out = attn Wo (no gate);
             decode in the absorbed form, prefill in the expanded form
    linear:  [q | k | v] = silu(conv(u Wqkv)) (causal, depthwise,
             ``short_conv_kernel_size`` taps, no bias); q and k
             L2-normalised a head; log decay a KEY CHANNEL g = -exp(A_log_h)
             * softplus((u Wfa) Wfb + dt_bias); beta = 2 sigmoid(u Wb)
             (``kda_allow_neg_eigval``: else 1); a matrix state a head,
             S = (I - beta k k^T) Diag(exp g) S + beta k v^T; o = S^T q;
             out = (RMSNorm_head(o) * sigmoid((u Wga) Wgb + b_g)) Wo
    logits = RMSNorm(y_L) @ W_head, float32

A slot owns TWO kinds of state, and the builder declares both
(``geometry["state"]``): a grouped-query layer has K and V page pools
``lad_k_<i>`` / ``lad_v_<i>`` ``[pages, page_size, kv_heads * head_dim]``
and a latent layer ONE pool of rows ``lad_pool_<i>`` ``[pages, page_size,
pool_width(kv_lora_rank + qk_rope_head_dim)]`` (576 -> 640 lanes), which
grow with the sequence through the page table; a linear layer has
fixed-size arrays indexed by the slot itself, the matrix state
``lad_s_<i>`` ``[slots, heads, dk, dv]`` float32 (``dv`` on the lanes:
``kernels/delta_rule.py``) and the convolution's window ``lad_win_<i>``
``[taps - 1, slots, 2 heads dk + heads dv]`` of the ``q | k | v`` row.

With ``expert_shard`` (``{"of": E_all, "first": f}``) the experts' count
(``n_routed_experts`` / ``num_experts``) is of the experts HELD here
(``models/latent_moe_decoder.py`` has the rule); ``vocab_size`` may be a
slice of the published vocabulary.

The programs' frame (buckets, budget, rungs, feeds, sampler, fetches) is
``models/decoder_programs.py``'s. Here a prefill's delta rule walks each
prompt's REAL tokens in chunks and the state installed for its slot is the
one after its last real token; a reused slot's rows are overwritten whole.
In a step the state arrays and the pools are donated and updated in place;
a slot that is not live keeps its state rows. A ``kimi_linear``
description's layers are built under ``fluid.name_scope`` (``kda_mixer``,
``latent_attention``, ``dense_ffn``, ``moe``): a compiled program's
instructions say which sub-block they are; a ``solar_open2`` description's
programs are what they were.

The matrices are stored input-major; the three projections and the three
convolutions of a linear layer are stored as ONE ``q | k | v`` matrix and
one ``[taps, q | k | v]`` weight: a checkpoint's loader concatenates once.
"""

import collections
import contextlib
import functools

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.core.types import np_dtype
from paddle_tpu.kernels.delta_rule import CHUNK
from paddle_tpu.kernels.latent_attention import pool_width
from paddle_tpu.models import decoder_programs

__all__ = ["linear_dims", "check_served", "layer_kinds", "parameter_shapes",
           "random_parameters", "load_parameters",
           "build_linear_attn_moe_decoder"]

LINEAR, GQA, LATENT = "linear_attention", "gqa", "latent"
# a mixer's ``fluid.name_scope`` in a ``kimi_linear`` description's programs
_SCOPES = {LINEAR: "kda_mixer", GQA: "gqa_attention",
           LATENT: "latent_attention"}


def _latent(desc):
    """The description is ``kimi_linear``'s: its full layers are latent."""
    return "kv_lora_rank" in desc


# (key, the value read, the one built, why), by naming
def _solar_refusals(desc, lin):
    return (
        ("first_k_dense_replace", desc.get("first_k_dense_replace", 0),
         0, "every layer's feed-forward is routed experts"),
        ("linear_attn_config.num_kv_heads", lin.get("num_kv_heads"),
         None, "a linear layer has as many value heads as heads"),
        ("kda_use_full_proj", bool(desc.get("kda_use_full_proj", False)),
         False, "the decay and output gates are low-rank"),
        ("use_rope", bool(desc.get("use_rope", False)), False,
         "no layer has a positional encoding"),
        ("use_gqa_gate", bool(desc.get("use_gqa_gate", True)), True,
         "the attention layers' output is gated"),
        ("tie_word_embeddings",
         bool(desc.get("tie_word_embeddings", False)), False,
         "the head is its own matrix"),
        ("n_group", desc.get("n_group", 1), 1,
         "one routing group"),
        ("topk_group", desc.get("topk_group", 1), 1,
         "one routing group"))


def _kimi_refusals(desc, lin):
    return (
        ("q_lora_rank", desc.get("q_lora_rank"), None,
         "the query comes straight from the normed input"),
        ("mla_use_nope", bool(desc.get("mla_use_nope", False)), True,
         "the latent layers have no positional encoding"),
        ("num_expert_group", desc.get("num_expert_group", 1), 1,
         "one routing group"),
        ("topk_group", desc.get("topk_group", 1), 1,
         "one routing group"),
        ("moe_layer_freq", desc.get("moe_layer_freq", 1), 1,
         "every layer past the leading dense ones is routed experts"),
        ("rope_scaling", desc.get("rope_scaling"), None,
         "nothing is rotated, so nothing is rescaled"),
        ("num_nextn_predict_layers",
         desc.get("num_nextn_predict_layers", 0), 0,
         "no multi-token-prediction module is loaded"),
        ("tie_word_embeddings",
         bool(desc.get("tie_word_embeddings", False)), False,
         "the head is its own matrix"),
        ("moe_router_activation_func",
         desc.get("moe_router_activation_func", "sigmoid"), "sigmoid",
         "the router scores by sigmoid"),
        ("linear_attn_config.num_kv_heads", lin.get("num_kv_heads"),
         None, "a linear layer has as many value heads as heads"))


def check_served(desc):
    """Refuse, by the key at fault, a description whose keys ask for what
    this builder does not serve (``linear_dims`` and the session's
    ``builder_for`` both ask)."""
    lin = desc["linear_attn_config"]
    refusals = _kimi_refusals if _latent(desc) else _solar_refusals
    for key, value, served, why in refusals(desc, lin):
        if value != served:
            raise NotImplementedError(
                "%s=%r: only %r is built (%s)" % (key, value, served, why))
    if _latent(desc):
        layer_kinds(desc)       # a layer in neither list, or in both


def linear_dims(desc):
    """The sizes the programs are built from, by the config's keys
    (``kimi_linear``'s names for the experts beside ``solar_open2``'s)."""
    check_served(desc)
    lin = desc["linear_attn_config"]
    latent = _latent(desc)

    def either(kimi, solar, default=None):
        key = kimi if latent else solar
        return desc[key] if default is None else desc.get(key, default)

    d = dict(
        D=int(desc["hidden_size"]), H=int(desc["num_attention_heads"]),
        Hl=int(lin["num_heads"]), dl=int(lin["head_dim"]),
        kw=int(lin["short_conv_kernel_size"]),
        Fm=int(desc["moe_intermediate_size"]),
        E=int(either("num_experts", "n_routed_experts")),
        k=int(either("num_experts_per_token", "num_experts_per_tok")),
        shared=int(either("num_shared_experts", "n_shared_experts", 0)),
        dense=int(desc.get("first_k_dense_replace", 0)),
        L=int(desc["num_hidden_layers"]), V=int(desc["vocab_size"]),
        eps=float(desc.get("rms_norm_eps", 1e-5)),
        scale=float(desc.get("routed_scaling_factor", 1.0)),
        norm_topk=bool(either("moe_renormalize", "norm_topk_prob", True)),
        beta_scale=2.0 if desc.get("kda_allow_neg_eigval", False) else 1.0)
    if latent:
        d.update(dn=int(desc["qk_nope_head_dim"]),
                 dr=int(desc["qk_rope_head_dim"]),
                 dv=int(desc["v_head_dim"]), C=int(desc["kv_lora_rank"]))
        d["W"] = d["C"] + d["dr"]           # the cached row
        d["Wp"] = pool_width(d["W"])        # as the pool holds it
        if d["dense"]:
            d["F"] = int(desc["intermediate_size"])
    else:
        d.update(Hkv=int(desc["num_key_value_heads"]),
                 dh=int(desc["head_dim"]))
        if d["H"] % d["Hkv"]:
            raise ValueError("%d query heads over %d key/value heads: "
                             "heads must divide" % (d["H"], d["Hkv"]))
    shard = desc.get("expert_shard")
    d["Er"] = int(shard["of"]) if shard else d["E"]
    d["first"] = int(shard["first"]) if shard else None
    if shard and not 0 <= d["first"] <= d["Er"] - d["E"]:
        raise ValueError(
            "expert_shard %r: %d held experts from `first` do not lie "
            "among its `of`" % (shard, d["E"]))
    d["lw"] = d["Hl"] * d["dl"]        # a linear layer's q, k or v row
    return d


def layer_kinds(desc):
    """``"gqa"``, ``"latent"`` or ``"linear_attention"`` for every layer.
    ``solar_open2`` names its attention layers (``gqa_layers``, from 0);
    ``kimi_linear`` names both kinds under ``linear_attn_config``, from 1,
    and every layer stands in just one of the two lists."""
    L = int(desc["num_hidden_layers"])
    if not _latent(desc):
        gqa = set(int(i) for i in desc["gqa_layers"])
        return [GQA if i in gqa else LINEAR for i in range(L)]
    lin = desc["linear_attn_config"]
    kda = [int(i) for i in lin["kda_layers"]]
    full = [int(i) for i in lin["full_attn_layers"]]
    for i in range(1, L + 1):
        if (i in kda) == (i in full):
            raise NotImplementedError(
                "linear_attn_config.kda_layers / full_attn_layers: layer %d "
                "(counted from 1) stands in %s of the two lists, and a "
                "layer is one kind" % (i, "both" if i in kda else "neither"))
    return [LATENT if i in full else LINEAR for i in range(1, L + 1)]


def parameter_shapes(desc, dtype="bfloat16"):
    """{name: (shape, dtype)} of every parameter, in layer order. The
    router's selection bias, ``a_log`` and ``dt_bias`` are float32
    whatever ``dtype`` is."""
    d = linear_dims(desc)
    D, lw, dl, H = d["D"], d["lw"], d["dl"], d["H"]
    out = collections.OrderedDict()

    def add(name, *shape, **kw):
        out[name] = (tuple(shape), kw.get("dtype", dtype))

    add("lad_embed", d["V"], D)
    for i, kind in enumerate(layer_kinds(desc)):
        p = "lad_%d_" % i
        add(p + "in_norm", D)
        if kind == GQA:
            qw, row = H * d["dh"], d["Hkv"] * d["dh"]
            add(p + "q", D, qw)
            add(p + "k", D, row)
            add(p + "v", D, row)
            add(p + "gate", D, qw)
            add(p + "o", qw, D)
        elif kind == LATENT:
            add(p + "q", D, H * (d["dn"] + d["dr"]))
            add(p + "kv_a", D, d["W"])
            add(p + "kv_norm", d["C"])
            add(p + "kv_b", d["C"], H * (d["dn"] + d["dv"]))
            add(p + "o", H * d["dv"], D)
        else:
            add(p + "qkv", D, 3 * lw)
            add(p + "conv_w", d["kw"], 3 * lw)
            add(p + "f_a", D, dl)
            add(p + "f_b", dl, lw)
            add(p + "dt_bias", lw, dtype="float32")
            add(p + "a_log", d["Hl"], dtype="float32")
            add(p + "beta", D, d["Hl"])
            add(p + "g_a", D, dl)
            add(p + "g_b", dl, lw)
            add(p + "g_bias", lw)
            add(p + "o_norm", dl)
            add(p + "o", lw, D)
        add(p + "ff_norm", D)
        if i < d["dense"]:
            add(p + "ffn_gate", D, d["F"])
            add(p + "ffn_up", D, d["F"])
            add(p + "ffn_down", d["F"], D)
            continue
        add(p + "router", D, d["Er"])
        add(p + "router_bias", d["Er"], dtype="float32")
        add(p + "experts_gate", d["E"], D, d["Fm"])
        add(p + "experts_up", d["E"], D, d["Fm"])
        add(p + "experts_down", d["E"], d["Fm"], D)
        if d["shared"]:
            Fs = d["Fm"] * d["shared"]
            add(p + "shared_gate", D, Fs)
            add(p + "shared_up", D, Fs)
            add(p + "shared_down", Fs, D)
    add("lad_final_norm", D)
    add("lad_head", D, d["V"])
    return out


def random_parameters(desc, seed=0, dtype="float32"):
    """Seeded parameters for tests and examples (host arrays): matrices
    ``N(0, 1/fan_in)``, norm scales near 1, the selection bias uniform in
    +-0.01, ``a_log = log(U(1, 16))`` a head and ``dt_bias`` the inverse
    softplus of a log-uniform 1e-3..1e-1 (the public initialisers)."""
    rng = np.random.RandomState(seed)
    out = collections.OrderedDict()
    for name, (shape, dt) in parameter_shapes(desc, dtype).items():
        if name.endswith("router_bias"):
            v = rng.uniform(-0.01, 0.01, shape)
        elif name.endswith("a_log"):
            v = np.log(rng.uniform(1.0, 16.0, shape))
        elif name.endswith("dt_bias"):
            delta = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
            v = delta + np.log(-np.expm1(-delta))          # softplus^-1
        elif name.endswith("g_bias"):
            v = 0.1 * rng.standard_normal(shape)
        elif name.endswith("norm"):
            v = 1.0 + 0.1 * rng.standard_normal(shape)
        elif name.endswith("conv_w"):
            v = rng.standard_normal(shape) * shape[0] ** -0.5
        elif name == "lad_embed":
            v = rng.standard_normal(shape)
        else:
            v = rng.standard_normal(shape) * shape[-2] ** -0.5
        out[name] = np.asarray(v, "float32").astype(np_dtype(dt))
    return out


load_parameters = functools.partial(decoder_programs.load_parameters,
                                    parameter_shapes)


def _family(desc, dtype, tokens_per_dispatch):
    """This family's layers and state (``decoder_programs.DecoderFamily``);
    ``geometry["prefill_chunk"]`` is the tokens a chunk of the delta
    rule's prefill walks."""
    nn = fluid.layers
    d = linear_dims(desc)
    kinds = layer_kinds(desc)
    latent = _latent(desc)
    lw, Hl, dl = d["lw"], d["Hl"], d["dl"]
    row = d["W"] if latent else d["Hkv"] * d["dh"]
    # a ``kimi_linear`` description's sub-blocks are named in its programs
    scope = fluid.name_scope if latent else (
        lambda name: contextlib.nullcontext())

    def state(S, P, ps, npp):
        page_pools, slot_arrays = collections.OrderedDict(), \
            collections.OrderedDict()
        for i, kind in enumerate(kinds):
            if kind == GQA:
                for part in "kv":
                    page_pools["lad_%s_%d" % (part, i)] = {
                        "shape": (P, ps, row), "dtype": dtype}
            elif kind == LATENT:
                page_pools["lad_pool_%d" % i] = {
                    "shape": (P, ps, d["Wp"]), "dtype": dtype}
            else:
                slot_arrays["lad_s_%d" % i] = {
                    "shape": (S, Hl, dl, dl), "dtype": "float32",
                    "slot_axis": 0}
                slot_arrays["lad_win_%d" % i] = {
                    "shape": (d["kw"] - 1, S, 3 * lw), "dtype": dtype,
                    "slot_axis": 1}
        return {"page_pools": page_pools, "slot_arrays": slot_arrays}

    def linear_mixer(nx, w, mix):
        """The delta-rule mixer on the normed rows ``nx`` up to its output
        projection (``w(part)``: the layer's parameter)."""
        def low_rank(a, b, out_dtype="input"):
            return nn.dense_projection(
                nn.dense_projection(nx, w(a)), w(b), out_dtype=out_dtype)

        g, beta = nn.delta_rule_gates(
            low_rank("f_a", "f_b", "float32"), w("dt_bias"), w("a_log"),
            nn.dense_projection(nx, w("beta"), out_dtype="float32"),
            heads=Hl, beta_scale=d["beta_scale"])
        o = mix(nn.dense_projection(nx, w("qkv")), g, beta, w)
        gate = nn.elementwise_add(low_rank("g_a", "g_b"), w("g_bias"))
        return nn.gated_head_norm(o, w("o_norm"), gate, heads=Hl,
                                  epsilon=d["eps"])

    def blocks(f, x, attend, latent_attend, mix):
        """The L blocks and the final norm on token rows ``x`` [N, D];
        ``attend(i, q, k, v)`` writes a grouped-query layer's rows and
        attends, ``latent_attend(i, q, kva, w)`` a latent layer's,
        ``mix(i, qkv, g, beta, w)`` runs a linear layer's convolution and
        delta rule (``w(part)``: its parameters). Returns (x, chosen per
        expert layer, tokens per held expert per expert layer)."""
        chosen, counts = [], []
        for i, kind in enumerate(kinds):
            def w(part, p="lad_%d_" % i):
                return f.w(p + part)

            nx = nn.rms_norm(x, w("in_norm"), d["eps"])
            with scope(_SCOPES[kind]):
                if kind == GQA:
                    q, k, v, gate = [nn.dense_projection(nx, w(part))
                                     for part in ("q", "k", "v", "gate")]
                    y = nn.sigmoid_gate(attend(i, q, k, v), gate)
                elif kind == LATENT:
                    # the query straight from the normed input: no
                    # compression (``q_lora_rank`` null)
                    y = latent_attend(
                        i, nn.dense_projection(nx, w("q")),
                        nn.dense_projection(nx, w("kv_a")), w)
                else:
                    y = linear_mixer(nx, w, functools.partial(mix, i))
                x = nn.elementwise_add(x, nn.dense_projection(y, w("o")))
            nx = nn.rms_norm(x, w("ff_norm"), d["eps"])
            if i < d["dense"]:
                with scope("dense_ffn"):
                    x = nn.elementwise_add(x, nn.gated_ffn(
                        nx, w("ffn_gate"), w("ffn_up"), w("ffn_down")))
                continue
            with scope("moe"):
                shared = ((w("shared_gate"), w("shared_up"),
                           w("shared_down")) if d["shared"] else None)
                ff, ch, cnt = nn.dropless_moe_ffn(
                    nx, w("router"), w("router_bias"), w("experts_gate"),
                    w("experts_up"), w("experts_down"), shared=shared,
                    valid=f.valid, top_k=d["k"], norm_topk=d["norm_topk"],
                    scale=d["scale"], held_first=d["first"])
                chosen.append(ch)
                counts.append(cnt)
                x = nn.elementwise_add(x, ff)
        return (nn.rms_norm(x, f.w("lad_final_norm"), d["eps"]), chosen,
                counts)

    def no_bias():
        # the convolution of ``ssm_ops`` takes a bias; this model has none
        return nn.fill_constant([3 * lw], dtype, 0.0)

    def latent_rows(q, kva, w):
        """A latent layer's query ``[N, H, dn + dr]`` and the row to
        cache, neither rotated (``check_served``: ``mla_use_nope``)."""
        return nn.latent_rope_rows(
            q, kva, w("kv_norm"), heads=d["H"], nope_dim=d["dn"],
            rope_dim=d["dr"], theta=0.0, epsilon=d["eps"], rotate=False)

    def prefill(f, x):
        def attend(i, q, k, v):
            nn.latent_row_prefill(f.state["lad_k_%d" % i], k,
                                  f.page_rows, f.lens)
            nn.latent_row_prefill(f.state["lad_v_%d" % i], v,
                                  f.page_rows, f.lens)
            # the flash kernel at the long buckets' tiles; no band
            return nn.window_prefill_attention(
                q, k, v, prompts=f.rows, heads=d["H"], kv_heads=d["Hkv"],
                window=0)

        def latent_attend(i, q, kva, w):
            q, rows = latent_rows(q, kva, w)
            nn.latent_row_prefill(f.state["lad_pool_%d" % i], rows,
                                  f.page_rows, f.lens)
            return nn.latent_prefill_attention(
                q, rows, w("kv_b"), prompts=f.rows, nope_dim=d["dn"])

        def mix(i, qkv, g, beta, w):
            qkv, window = nn.ssm_causal_conv(
                qkv, w("conv_w"), no_bias(), f.lens)
            q, k, v = nn.split(qkv, 3, dim=-1)
            o, last = nn.delta_rule_prefill(q, k, v, g, beta, f.lens)
            nn.slot_state_write(f.state["lad_s_%d" % i], f.slot_idx,
                                last, axis=0)
            nn.slot_state_write(f.state["lad_win_%d" % i], f.slot_idx,
                                window, axis=1)
            return o

        x, chosen, _counts = blocks(f, x, attend, latent_attend, mix)
        return x, [("first_chosen", chosen)]

    def step(f, x):
        def attend(i, q, k, v):
            k_pool, v_pool = f.state["lad_k_%d" % i], \
                f.state["lad_v_%d" % i]
            nn.latent_row_write(k_pool, k, f.table, f.pos)
            nn.latent_row_write(v_pool, v, f.table, f.pos)
            return nn.gqa_paged_attention(q, k_pool, v_pool, f.table,
                                          f.lengths, heads=d["H"])

        def latent_attend(i, q, kva, w):
            pool = f.state["lad_pool_%d" % i]
            q, rows = latent_rows(q, kva, w)
            nn.latent_row_write(pool, rows, f.table, f.pos)
            return nn.latent_paged_attention(
                q, w("kv_b"), pool, f.table, f.lengths, nope_dim=d["dn"])

        def mix(i, qkv, g, beta, w):
            qkv = nn.ssm_conv_step(f.state["lad_win_%d" % i], qkv,
                                   w("conv_w"), no_bias(), f.live)
            q, k, v = nn.split(qkv, 3, dim=-1)
            return nn.delta_rule_state_update(
                f.state["lad_s_%d" % i], q, k, v, g, beta, f.live)

        x, chosen, counts = blocks(f, x, attend, latent_attend, mix)
        return x, [("chosen", chosen), ("expert_tokens", counts)]

    geometry = {
        "row_width": row, "layer_kinds": kinds,
        "moe_layers": list(range(d["dense"], d["L"])),
        # the tokens a chunk of the linear layers' prefill walks
        "prefill_chunk": CHUNK,
        # the experts held of those routed among, a token's choices
        "experts": {"held": d["E"], "of": d["Er"], "top_k": d["k"]}}
    if latent:
        # what a position's row takes in ONE latent layer's pool: a
        # reader turns the round's ``kv_rows_visible`` into bytes with it
        geometry.update(pool_width=d["Wp"], latent_row_bytes=d["Wp"]
                        * np.dtype(np_dtype(dtype)).itemsize)
    return decoder_programs.DecoderFamily(
        "lad", parameter_shapes(desc, dtype), d["V"], state, prefill,
        step, geometry=geometry)


build_linear_attn_moe_decoder = functools.partial(
    decoder_programs.build_decoder_programs, _family)
