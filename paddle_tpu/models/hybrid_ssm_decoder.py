"""A hybrid decoder-only language model, state-space (Mamba-1) layers with
a few attention layers among them, built for SERVING from a description:
a dict of the model's own ``config.json`` keys (HF ``jamba`` naming).

    block:   h = x + Mixer_i(RMSNorm(x));   y = h + MLP(RMSNorm(h))
    MLP:     down(silu(gate(u)) * up(u)), no bias
    layer i: attention when i % attn_layer_period == attn_layer_offset,
             else the state-space mixer
    Mixer:   [x | z] = in_proj(u); x = silu(conv(x)); [dt | B | C] =
             x_proj(x), each through its own RMSNorm; Delta =
             softplus(dt_proj(dt) + b_dt); s = exp(Delta (x) A) * s +
             (Delta * x) (x) B; out_proj((s . C + D * x) * silu(z))
    Attention: grouped-query (``num_key_value_heads`` K/V heads of
             ``hidden_size / num_attention_heads``), causal, NO positional
             encoding, no bias
    logits = RMSNorm(y_L) @ Embedding^T (``tie_word_embeddings``)

A slot owns TWO kinds of state, and the builder declares both
(``geometry["state"]``): an attention layer has K and V page pools
``hsd_k_<i>``/``hsd_v_<i>`` ``[pages, page_size, kv_heads * head]`` that
grow with the sequence through the page table; a state-space layer has
fixed-size arrays indexed by the slot itself, ``hsd_ssm_<i>`` ``[slots,
d_state, d_inner]`` float32 and the convolution's window ``hsd_win_<i>``
``[d_conv - 1, slots, d_inner]`` (``kernels/selective_scan.py`` says why
``d_inner`` is the minor axis).

The programs' frame (buckets, budget, rungs, feeds, sampler, fetches) is
``models/decoder_programs.py``'s. Here a prefill's scan walks each
prompt's REAL tokens and the state installed for its slot is the one after
its last real token: the bucket's padding is nothing to it. A reused slot's
rows are overwritten whole, so it starts from its own prefill and never
from its predecessor. In a step the state arrays and the pools are donated
and updated in place; a slot that is not live keeps its state rows as they
are (finite: zero or a finished stream's).

The matrices are stored input-major (``[in, out]``), ``A_log`` and the
convolution's weight with ``d_inner`` minor (``[d_state, d_inner]``,
``[d_conv, d_inner]``): a checkpoint's loader transposes once.
"""

import collections
import functools

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.core.types import np_dtype
from paddle_tpu.models import decoder_programs

__all__ = ["hybrid_dims", "layer_kinds", "parameter_shapes",
           "random_parameters", "load_parameters",
           "build_hybrid_ssm_decoder"]

MAMBA, ATTENTION = "mamba", "attention"


def hybrid_dims(desc):
    """The sizes the programs are built from, by the config's keys."""
    D, H = int(desc["hidden_size"]), int(desc["num_attention_heads"])
    d = dict(
        D=D, H=H, Hkv=int(desc["num_key_value_heads"]), dh=D // H,
        F=int(desc["intermediate_size"]), L=int(desc["num_hidden_layers"]),
        V=int(desc["vocab_size"]),
        di=int(desc["mamba_expand"]) * D, n=int(desc["mamba_d_state"]),
        kw=int(desc["mamba_d_conv"]), r=int(desc["mamba_dt_rank"]),
        period=int(desc["attn_layer_period"]),
        offset=int(desc["attn_layer_offset"]),
        eps=float(desc.get("rms_norm_eps", 1e-6)))
    if D % H or H % d["Hkv"]:
        raise ValueError("hidden_size %d, %d heads, %d key/value heads: "
                         "heads must divide" % (D, H, d["Hkv"]))
    if int(desc.get("num_experts", 1)) != 1:
        raise NotImplementedError(
            "num_experts=%r: only the dense feed-forward is built"
            % desc.get("num_experts"))
    if desc.get("sliding_window") is not None \
            or desc.get("mamba_proj_bias", False) \
            or not desc.get("mamba_conv_bias", True) \
            or not desc.get("tie_word_embeddings", True):
        raise NotImplementedError(
            "built: no sliding window, no projection bias, a convolution "
            "bias, tied embeddings; got %r" % {
                k: desc.get(k) for k in (
                    "sliding_window", "mamba_proj_bias", "mamba_conv_bias",
                    "tie_word_embeddings")})
    return d


def layer_kinds(desc):
    """``"attention"`` or ``"mamba"`` for every layer, HF's
    ``layers_block_type``."""
    period, offset = int(desc["attn_layer_period"]), \
        int(desc["attn_layer_offset"])
    return [ATTENTION if i % period == offset else MAMBA
            for i in range(int(desc["num_hidden_layers"]))]


def parameter_shapes(desc, dtype="bfloat16"):
    """{name: (shape, dtype)} of every parameter, in layer order."""
    d = hybrid_dims(desc)
    D, di, n, r = d["D"], d["di"], d["n"], d["r"]
    out = collections.OrderedDict()

    def add(name, *shape):
        out[name] = (tuple(shape), dtype)

    add("hsd_embed", d["V"], D)
    for i, kind in enumerate(layer_kinds(desc)):
        p = "hsd_%d_" % i
        add(p + "in_norm", D)
        if kind == ATTENTION:
            add(p + "q", D, d["H"] * d["dh"])
            add(p + "k", D, d["Hkv"] * d["dh"])
            add(p + "v", D, d["Hkv"] * d["dh"])
            add(p + "o", d["H"] * d["dh"], D)
        else:
            add(p + "in_proj", D, 2 * di)
            add(p + "conv_w", d["kw"], di)
            add(p + "conv_b", di)
            add(p + "x_proj", di, r + 2 * n)
            add(p + "dt_norm", r)
            add(p + "b_norm", n)
            add(p + "c_norm", n)
            add(p + "dt_proj", r, di)
            add(p + "dt_bias", di)
            add(p + "a_log", n, di)
            add(p + "d_skip", di)
            add(p + "out_proj", di, D)
        add(p + "ff_norm", D)
        add(p + "ffn_gate", D, d["F"])
        add(p + "ffn_up", D, d["F"])
        add(p + "ffn_down", d["F"], D)
    add("hsd_final_norm", D)
    return out


def random_parameters(desc, seed=0, dtype="float32"):
    """Seeded parameters for tests and examples (host arrays): matrices
    ``N(0, 1/fan_in)``, norm scales near 1, ``A = -exp(a_log)`` spanning
    1..``d_state`` and ``dt_bias`` such that ``softplus`` gives Delta in
    1e-3..1e-1 (Mamba's own initialisers)."""
    rng = np.random.RandomState(seed)
    d = hybrid_dims(desc)
    out = collections.OrderedDict()
    for name, (shape, dt) in parameter_shapes(desc, dtype).items():
        if name.endswith("a_log"):
            v = np.log(np.arange(1, d["n"] + 1, dtype="float64"))[:, None] \
                * np.ones(shape)
        elif name.endswith("dt_bias"):
            delta = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
            v = delta + np.log(-np.expm1(-delta))      # softplus^-1
        elif name.endswith("d_skip"):
            v = np.ones(shape)
        elif name.endswith("norm"):
            v = 1.0 + 0.1 * rng.standard_normal(shape)
        elif name.endswith("conv_b"):
            v = 0.1 * rng.standard_normal(shape)
        elif name.endswith("conv_w"):
            v = rng.standard_normal(shape) * shape[0] ** -0.5
        elif name == "hsd_embed":
            v = rng.standard_normal(shape)
        else:
            v = rng.standard_normal(shape) * shape[-2] ** -0.5
        out[name] = np.asarray(v, "float32").astype(np_dtype(dt))
    return out


load_parameters = functools.partial(decoder_programs.load_parameters,
                                    parameter_shapes)


def _family(desc, dtype, tokens_per_dispatch):
    """This family's layers and state (``decoder_programs.DecoderFamily``)."""
    nn = fluid.layers
    d = hybrid_dims(desc)
    kinds = layer_kinds(desc)
    row = d["Hkv"] * d["dh"]
    di, n, k1 = d["di"], d["n"], d["kw"] - 1

    def state(S, P, ps, npp):
        page_pools, slot_arrays = collections.OrderedDict(), \
            collections.OrderedDict()
        for i, kind in enumerate(kinds):
            if kind == ATTENTION:
                for part in "kv":
                    page_pools["hsd_%s_%d" % (part, i)] = {
                        "shape": (P, ps, row), "dtype": dtype}
            else:
                slot_arrays["hsd_ssm_%d" % i] = {
                    "shape": (S, n, di), "dtype": "float32", "slot_axis": 0}
                slot_arrays["hsd_win_%d" % i] = {
                    "shape": (k1, S, di), "dtype": dtype, "slot_axis": 1}
        return {"page_pools": page_pools, "slot_arrays": slot_arrays}

    def blocks(f, x, attend, mix):
        """The L blocks and the final norm on token rows ``x`` [N, D];
        ``attend(i, q, k, v)`` writes an attention layer's rows and
        attends, ``mix(i, x, z, w)`` runs a state-space layer's
        convolution and recurrence (``w(part)``: its parameters)."""
        w = f.w
        for i, kind in enumerate(kinds):
            p = "hsd_%d_" % i
            nx = nn.rms_norm(x, w(p + "in_norm"), d["eps"])
            if kind == ATTENTION:
                att = attend(i, *[nn.dense_projection(nx, w(p + part))
                                  for part in "qkv"])
                out = nn.dense_projection(att, w(p + "o"))
            else:
                xs, z = nn.split(
                    nn.dense_projection(nx, w(p + "in_proj")), 2, dim=-1)
                y = mix(i, xs, z, lambda part, p=p: w(p + part))
                out = nn.dense_projection(y, w(p + "out_proj"))
            x = nn.elementwise_add(x, out)
            nx = nn.rms_norm(x, w(p + "ff_norm"), d["eps"])
            x = nn.elementwise_add(x, nn.gated_ffn(
                nx, w(p + "ffn_gate"), w(p + "ffn_up"), w(p + "ffn_down")))
        return nn.rms_norm(x, w("hsd_final_norm"), d["eps"]), []

    def selective(xc, w):
        """Delta, B and C of the convolved rows ``xc``."""
        u = nn.dense_projection(xc, w("x_proj"), out_dtype="float32")
        return nn.ssm_delta_b_c(
            u, w("dt_norm"), w("b_norm"), w("c_norm"), w("dt_proj"),
            w("dt_bias"), dt_rank=d["r"], d_state=n, epsilon=d["eps"])

    def prefill(f, x):
        def attend(i, q, k, v):
            nn.latent_row_prefill(f.state["hsd_k_%d" % i], k,
                                  f.page_rows, f.lens)
            nn.latent_row_prefill(f.state["hsd_v_%d" % i], v,
                                  f.page_rows, f.lens)
            return nn.gqa_prefill_attention(
                q, k, v, prompts=f.rows, heads=d["H"], kv_heads=d["Hkv"])

        def mix(i, xs, z, w):
            xc, window = nn.ssm_causal_conv(
                xs, w("conv_w"), w("conv_b"), f.lens)
            delta, b, c = selective(xc, w)
            y, last = nn.ssm_prefill_scan(
                xc, delta, b, c, w("a_log"), w("d_skip"), z, f.lens)
            nn.slot_state_write(f.state["hsd_ssm_%d" % i], f.slot_idx,
                                last, axis=0)
            nn.slot_state_write(f.state["hsd_win_%d" % i], f.slot_idx,
                                window, axis=1)
            return y

        return blocks(f, x, attend, mix)

    def step(f, x):
        def attend(i, q, k, v):
            k_pool, v_pool = f.state["hsd_k_%d" % i], \
                f.state["hsd_v_%d" % i]
            nn.latent_row_write(k_pool, k, f.table, f.pos)
            nn.latent_row_write(v_pool, v, f.table, f.pos)
            return nn.gqa_paged_attention(q, k_pool, v_pool, f.table,
                                          f.lengths, heads=d["H"])

        def mix(i, xs, z, w):
            xc = nn.ssm_conv_step(f.state["hsd_win_%d" % i], xs,
                                  w("conv_w"), w("conv_b"), f.live)
            delta, b, c = selective(xc, w)
            return nn.ssm_state_update(
                f.state["hsd_ssm_%d" % i], xc, delta, b, c, w("a_log"),
                w("d_skip"), z, f.live)

        return blocks(f, x, attend, mix)

    return decoder_programs.DecoderFamily(
        "hsd", parameter_shapes(desc, dtype), d["V"], state, prefill,
        step, geometry={"row_width": row, "layer_kinds": kinds},
        # tied embeddings; no expert layer asks which rows are real
        head=lambda f, rows: nn.tied_vocab_projection(
            rows, f.w("hsd_embed")), mask=False)


build_hybrid_ssm_decoder = functools.partial(
    decoder_programs.build_decoder_programs, _family)
