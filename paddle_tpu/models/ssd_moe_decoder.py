"""A hybrid decoder-only language model whose mixers are Mamba-2
(state-space duality) layers with a few grouped-query attention layers
among them, routed experts beside a shared expert in EVERY layer, and
multipliers on the embedding, the residual branches, the attention scores
and the logits, built for SERVING from a description: a dict of the
model's own ``config.json`` keys (HF ``granitemoehybrid`` naming:
``layer_types``, ``mamba_n_heads``, ``mamba_d_head``, ``mamba_d_state``,
``num_local_experts``, ``shared_intermediate_size``, ``*_multiplier``).

    x_0     = Embedding[ids] * embedding_multiplier
    block:    h = x + residual_multiplier * Mixer_i(RMSNorm(x))
              y = h + residual_multiplier * (Experts(u) + Shared(u)),
              u = RMSNorm(h)
    logits  = (RMSNorm(x_L) @ Embedding^T) / logits_scaling   (tied)
    layer i:  attention where ``layer_types[i] == "attention"``, else
              Mamba-2
    Attention: grouped-query, causal, NO positional encoding, no bias,
              scores * attention_multiplier (not 1 / sqrt(head))
    Mamba-2:  z, xBC, dt = n W_z, n W_xbc, n W_dt;
              xBC = silu(conv(xBC) + b) (causal, depthwise, ``mamba_d_conv``
              taps); [x | B | C] = xBC, ONE group: every head reads the same
              B and C; Delta_h = softplus(dt_h + dt_bias_h); A_h =
              -exp(A_log_h), a scalar a head;
              s_h <- exp(Delta_h A_h) s_h + Delta_h x_h (x) B;
              y_h = s_h C + D_h x_h;
              out = (RMSNorm(y * silu(z)) * w) W_out: the gate BEFORE the
              norm, one norm over the whole row
    Experts:  top-k of the router's raw logits, a softmax over the k chosen
              logits, no bias, no token dropped; Shared: a gated
              feed-forward added once

A slot owns TWO kinds of state, and the builder declares both
(``geometry["state"]``): an attention layer has K and V page pools
``smd_k_<i>`` / ``smd_v_<i>`` ``[pages, page_size, kv_heads * head]`` that
grow with the sequence through the page table; a Mamba-2 layer has
fixed-size arrays indexed by the slot itself, the matrix state
``smd_s_<i>`` ``[slots, heads * d_head / 128, d_state, 128]`` float32
(``d_state`` on the sublanes and ``x``'s own channels on the lanes, two
heads of 64 side by side: ``kernels/ssd.py`` says why, ``ssd.to_heads``
gives ``[slots, heads, d_head, d_state]``) and the convolution's window
``smd_win_<i>`` ``[d_conv - 1, slots, heads * d_head + 2 d_state]`` of the
``x | B | C`` row.

With ``expert_shard`` (``{"of": E_all, "first": f}``) ``num_local_experts``
counts the experts HELD here (``models/latent_moe_decoder.py`` has the
rule); ``vocab_size`` may be a slice of the published vocabulary.

The programs' frame (buckets, budget, rungs, feeds, sampler, fetches) is
``models/decoder_programs.py``'s. Here a prefill's recurrence walks each
prompt's REAL tokens in chunks and the state installed for its slot is the
one after its last real token; a reused slot's rows are overwritten whole.
In a step the state arrays and the pools are donated and updated in place;
a slot that is not live keeps its state rows.

The matrices are stored input-major; HF's one ``in_proj`` is stored as its
three column blocks ``in_z``, ``in_xbc`` and ``in_dt`` (a checkpoint's
loader cuts once), so that no dispatch copies a slice of a prefill's
widest activation. The 1 / ``attention_multiplier`` scale reaches the
attention ops, which take ``head ** -0.5``, through the query: ``q`` is
the float32 product times ``attention_multiplier * sqrt(head)``, rounded
once.
"""

import collections
import functools

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.core.types import np_dtype
from paddle_tpu.kernels.ssd import CHUNK, state_shape
from paddle_tpu.models import decoder_programs

__all__ = ["ssd_dims", "check_served", "layer_kinds", "parameter_shapes",
           "random_parameters", "load_parameters", "build_ssd_moe_decoder"]

MAMBA, ATTENTION = "mamba", "attention"


def check_served(desc):
    """Refuse, by the key at fault, a description whose keys ask for what
    this builder does not serve (``ssd_dims`` and the session's
    ``builder_for`` both ask)."""
    for key, value, served, why in (
            ("mamba_n_groups", desc.get("mamba_n_groups", 1), 1,
             "every head reads one B and one C"),
            ("mamba_proj_bias", bool(desc.get("mamba_proj_bias", False)),
             False, "the mixer's projections have no bias"),
            ("mamba_conv_bias", bool(desc.get("mamba_conv_bias", True)),
             True, "the convolution has a bias"),
            ("attention_bias", bool(desc.get("attention_bias", False)),
             False, "the attention projections have no bias"),
            ("position_embedding_type",
             desc.get("position_embedding_type", "nope"), "nope",
             "no layer has a positional encoding"),
            ("tie_word_embeddings",
             bool(desc.get("tie_word_embeddings", True)), True,
             "the head is the embedding table"),
            ("hidden_act", desc.get("hidden_act", "silu"), "silu",
             "the gated feed-forwards and the mixer's gate are SiLU"),
            ("normalization_function",
             desc.get("normalization_function", "rmsnorm"), "rmsnorm",
             "every norm is an RMSNorm")):
        if value != served:
            raise NotImplementedError(
                "%s=%r: only %r is built (%s)" % (key, value, served, why))


def ssd_dims(desc):
    """The sizes the programs are built from, by the config's keys."""
    check_served(desc)
    D, H = int(desc["hidden_size"]), int(desc["num_attention_heads"])
    d = dict(
        D=D, H=H, Hkv=int(desc["num_key_value_heads"]),
        dh=int(desc.get("head_dim") or D // H),
        Hm=int(desc["mamba_n_heads"]), P=int(desc["mamba_d_head"]),
        n=int(desc["mamba_d_state"]), kw=int(desc["mamba_d_conv"]),
        F=int(desc["intermediate_size"]),
        Fs=int(desc["shared_intermediate_size"]),
        E=int(desc["num_local_experts"]), k=int(desc["num_experts_per_tok"]),
        L=int(desc["num_hidden_layers"]), V=int(desc["vocab_size"]),
        eps=float(desc.get("rms_norm_eps", 1e-5)),
        emb=float(desc.get("embedding_multiplier", 1.0)),
        res=float(desc.get("residual_multiplier", 1.0)),
        logit=float(desc.get("logits_scaling", 1.0)))
    d["di"] = d["Hm"] * d["P"]
    d["cw"] = d["di"] + 2 * d["n"]       # the convolved x | B | C row
    d["att"] = float(desc.get("attention_multiplier", d["dh"] ** -0.5))
    if H % d["Hkv"]:
        raise ValueError("%d query heads over %d key/value heads: heads "
                         "must divide" % (H, d["Hkv"]))
    if d["di"] != int(desc.get("mamba_expand", 2)) * D:
        raise ValueError(
            "mamba_n_heads %d x mamba_d_head %d is not mamba_expand %r x "
            "hidden_size %d" % (d["Hm"], d["P"], desc.get("mamba_expand", 2),
                                D))
    if len(desc["layer_types"]) != d["L"]:
        raise ValueError("layer_types names %d layers, num_hidden_layers "
                         "is %d" % (len(desc["layer_types"]), d["L"]))
    shard = desc.get("expert_shard")
    d["Er"] = int(shard["of"]) if shard else d["E"]
    d["first"] = int(shard["first"]) if shard else None
    if shard and not 0 <= d["first"] <= d["Er"] - d["E"]:
        raise ValueError(
            "expert_shard %r: num_local_experts=%d experts from `first` do "
            "not lie among its `of`" % (shard, d["E"]))
    return d


def layer_kinds(desc):
    """``"attention"`` or ``"mamba"`` for every layer."""
    kinds = [str(kind) for kind in desc["layer_types"]]
    unknown = sorted(set(kinds) - {MAMBA, ATTENTION})
    if unknown:
        raise NotImplementedError("layer_types %r: only %r and %r are built"
                                  % (unknown, MAMBA, ATTENTION))
    return kinds


def parameter_shapes(desc, dtype="bfloat16"):
    """{name: (shape, dtype)} of every parameter, in layer order.
    ``dt_bias``, ``a_log`` and ``d_skip`` are float32 whatever ``dtype``
    is."""
    d = ssd_dims(desc)
    D, di, cw = d["D"], d["di"], d["cw"]
    qw, row = d["H"] * d["dh"], d["Hkv"] * d["dh"]
    out = collections.OrderedDict()

    def add(name, *shape, **kw):
        out[name] = (tuple(shape), kw.get("dtype", dtype))

    add("smd_embed", d["V"], D)
    for i, kind in enumerate(layer_kinds(desc)):
        p = "smd_%d_" % i
        add(p + "in_norm", D)
        if kind == ATTENTION:
            add(p + "q", D, qw)
            add(p + "k", D, row)
            add(p + "v", D, row)
            add(p + "o", qw, D)
        else:
            add(p + "in_z", D, di)
            add(p + "in_xbc", D, cw)
            add(p + "in_dt", D, d["Hm"])
            add(p + "conv_w", d["kw"], cw)
            add(p + "conv_b", cw)
            add(p + "dt_bias", d["Hm"], dtype="float32")
            add(p + "a_log", d["Hm"], dtype="float32")
            add(p + "d_skip", d["Hm"], dtype="float32")
            add(p + "mix_norm", di)
            add(p + "out_proj", di, D)
        add(p + "ff_norm", D)
        add(p + "router", D, d["Er"])
        add(p + "experts_gate", d["E"], D, d["F"])
        add(p + "experts_up", d["E"], D, d["F"])
        add(p + "experts_down", d["E"], d["F"], D)
        add(p + "shared_gate", D, d["Fs"])
        add(p + "shared_up", D, d["Fs"])
        add(p + "shared_down", d["Fs"], D)
    add("smd_final_norm", D)
    return out


def random_parameters(desc, seed=0, dtype="float32"):
    """Seeded parameters for tests and examples (host arrays): matrices
    ``N(0, 1/fan_in)``, norm scales near 1, ``a_log = log(U(1, 16))`` a
    head, ``dt_bias`` the inverse softplus of a log-uniform 1e-3..1e-1 and
    ``D`` = 1 (Mamba-2's own initialisers)."""
    rng = np.random.RandomState(seed)
    out = collections.OrderedDict()
    for name, (shape, dt) in parameter_shapes(desc, dtype).items():
        if name.endswith("a_log"):
            v = np.log(rng.uniform(1.0, 16.0, shape))
        elif name.endswith("dt_bias"):
            delta = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
            v = delta + np.log(-np.expm1(-delta))          # softplus^-1
        elif name.endswith("d_skip"):
            v = np.ones(shape)
        elif name.endswith("norm"):
            v = 1.0 + 0.1 * rng.standard_normal(shape)
        elif name.endswith("conv_b"):
            v = 0.1 * rng.standard_normal(shape)
        elif name.endswith("conv_w"):
            v = rng.standard_normal(shape) * shape[0] ** -0.5
        elif name == "smd_embed":
            v = rng.standard_normal(shape)
        else:
            v = rng.standard_normal(shape) * shape[-2] ** -0.5
        out[name] = np.asarray(v, "float32").astype(np_dtype(dt))
    return out


load_parameters = functools.partial(decoder_programs.load_parameters,
                                    parameter_shapes)


def _family(desc, dtype, tokens_per_dispatch):
    """This family's layers and state (``decoder_programs.DecoderFamily``);
    ``geometry["prefill_chunk"]`` is the tokens a chunk of the Mamba-2
    prefill walks."""
    nn = fluid.layers
    d = ssd_dims(desc)
    kinds = layer_kinds(desc)
    row, n = d["Hkv"] * d["dh"], d["n"]

    def state(S, P, ps, npp):
        page_pools, slot_arrays = collections.OrderedDict(), \
            collections.OrderedDict()
        for i, kind in enumerate(kinds):
            if kind == ATTENTION:
                for part in "kv":
                    page_pools["smd_%s_%d" % (part, i)] = {
                        "shape": (P, ps, row), "dtype": dtype}
            else:
                slot_arrays["smd_s_%d" % i] = {
                    "shape": state_shape(S, d["Hm"], d["P"], n),
                    "dtype": "float32",
                    "slot_axis": 0}
                slot_arrays["smd_win_%d" % i] = {
                    "shape": (d["kw"] - 1, S, d["cw"]), "dtype": dtype,
                    "slot_axis": 1}
        return {"page_pools": page_pools, "slot_arrays": slot_arrays}

    def blocks(f, x, attend, mix):
        """The L blocks and the final norm on token rows ``x`` [N, D];
        ``attend(i, q, k, v)`` writes an attention layer's rows and
        attends, ``mix(i, xbc, dt, w)`` runs a Mamba-2 layer's convolution
        and recurrence (``w(part)``: its parameters). Returns (x, chosen
        per layer, tokens per held expert per layer)."""
        w = f.w
        chosen, counts = [], []
        x = nn.scale(x, scale=d["emb"])
        for i, kind in enumerate(kinds):
            p = "smd_%d_" % i
            nx = nn.rms_norm(x, w(p + "in_norm"), d["eps"])
            if kind == ATTENTION:
                # the ops' scale is head ** -0.5: the rest goes into q
                q = nn.cast(nn.scale(
                    nn.dense_projection(nx, w(p + "q"), out_dtype="float32"),
                    scale=d["att"] * d["dh"] ** 0.5), dtype)
                att = attend(i, q, nn.dense_projection(nx, w(p + "k")),
                             nn.dense_projection(nx, w(p + "v")))
                out = nn.dense_projection(att, w(p + "o"))
            else:
                y = mix(i, nn.dense_projection(nx, w(p + "in_xbc")),
                        nn.dense_projection(nx, w(p + "in_dt")),
                        lambda part, p=p: w(p + part))
                out = nn.dense_projection(
                    nn.gated_row_norm(
                        y, nn.dense_projection(nx, w(p + "in_z")),
                        w(p + "mix_norm"), d["eps"]), w(p + "out_proj"))
            x = nn.scaled_residual(x, out, d["res"])
            nx = nn.rms_norm(x, w(p + "ff_norm"), d["eps"])
            ff, ch, cnt = nn.dropless_moe_ffn(
                nx, w(p + "router"), None, w(p + "experts_gate"),
                w(p + "experts_up"), w(p + "experts_down"),
                shared=(w(p + "shared_gate"), w(p + "shared_up"),
                        w(p + "shared_down")), valid=f.valid,
                top_k=d["k"], held_first=d["first"],
                scoring="softmax_topk")
            chosen.append(ch)
            counts.append(cnt)
            x = nn.scaled_residual(x, ff, d["res"])
        return nn.rms_norm(x, w("smd_final_norm"), d["eps"]), chosen, counts

    def prefill(f, x):
        def attend(i, q, k, v):
            nn.latent_row_prefill(f.state["smd_k_%d" % i], k,
                                  f.page_rows, f.lens)
            nn.latent_row_prefill(f.state["smd_v_%d" % i], v,
                                  f.page_rows, f.lens)
            # the flash kernel at the long buckets' tiles; no band
            return nn.window_prefill_attention(
                q, k, v, prompts=f.rows, heads=d["H"], kv_heads=d["Hkv"],
                window=0)

        def mix(i, xbc, dt, w):
            xbc, window = nn.ssm_causal_conv(
                xbc, w("conv_w"), w("conv_b"), f.lens)
            y, last = nn.ssd_prefill(xbc, dt, w("dt_bias"), w("a_log"),
                                     w("d_skip"), f.lens, d_state=n)
            nn.slot_state_write(f.state["smd_s_%d" % i], f.slot_idx,
                                last, axis=0)
            nn.slot_state_write(f.state["smd_win_%d" % i], f.slot_idx,
                                window, axis=1)
            return y

        x, chosen, _counts = blocks(f, x, attend, mix)
        return x, [("first_chosen", chosen)]

    def step(f, x):
        def attend(i, q, k, v):
            k_pool, v_pool = f.state["smd_k_%d" % i], \
                f.state["smd_v_%d" % i]
            nn.latent_row_write(k_pool, k, f.table, f.pos)
            nn.latent_row_write(v_pool, v, f.table, f.pos)
            return nn.gqa_paged_attention(q, k_pool, v_pool, f.table,
                                          f.lengths, heads=d["H"])

        def mix(i, xbc, dt, w):
            xbc = nn.ssm_conv_step(f.state["smd_win_%d" % i], xbc,
                                   w("conv_w"), w("conv_b"), f.live)
            return nn.ssd_state_update(
                f.state["smd_s_%d" % i], xbc, dt, w("dt_bias"),
                w("a_log"), w("d_skip"), f.live, d_state=n)

        x, chosen, counts = blocks(f, x, attend, mix)
        return x, [("chosen", chosen), ("expert_tokens", counts)]

    def head(f, rows):
        # tied embeddings; the logits' scaling is a division in float32
        return nn.scale(nn.tied_vocab_projection(rows, f.w("smd_embed")),
                        scale=1.0 / d["logit"])

    return decoder_programs.DecoderFamily(
        "smd", parameter_shapes(desc, dtype), d["V"], state, prefill,
        step, geometry={
            "row_width": row, "layer_kinds": kinds,
            "moe_layers": list(range(d["L"])),
            # the tokens a chunk of the Mamba-2 layers' prefill walks
            "prefill_chunk": CHUNK,
            # a slot's matrix state and window, all Mamba-2 layers
            "state_bytes_per_slot": kinds.count(MAMBA) * (
                d["Hm"] * d["P"] * n * 4 + (d["kw"] - 1) * d["cw"]
                * np.dtype(np_dtype(dtype)).itemsize),
            # the experts held of those routed among, a token's choices
            "experts": {"held": d["E"], "of": d["Er"], "top_k": d["k"]}},
        head=head)


build_ssd_moe_decoder = functools.partial(
    decoder_programs.build_decoder_programs, _family)
