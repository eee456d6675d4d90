"""A decoder-only language model whose attention layers are of two kinds,
sliding-window and full, with routed experts, built for SERVING from a
description: a dict of the model's own ``config.json`` keys (HF ``afmoe``
naming: ``layer_types``, ``sliding_window``, ``num_dense_layers``,
``num_experts``, ``route_scale``, ``mup_enabled``).

    h0 = Embedding[ids] * sqrt(hidden_size)             (``mup_enabled``)
    block: h = h + RMSNorm_post_attn(Attn(RMSNorm(h)))
           h = h + RMSNorm_post_mlp(FFN(RMSNorm_pre_mlp(h)))
    Attn:  q, k, v, g = a Wq, a Wk, a Wv, a Wg (no bias), grouped-query;
           RMSNorm over each head of q and of k; RoPE on q and k on a
           ``sliding_attention`` layer, NO positional encoding on a
           ``full_attention`` layer; causal softmax, on a sliding layer
           over the last ``sliding_window`` positions (key p is visible
           to query t iff 0 <= t - p < window); out = (attn * sigmoid(g)) Wo
    FFN:   SwiGLU in the first ``num_dense_layers`` layers, then
           Shared(x) + route_scale * sum_i w_i Expert_i(x), top-k of E by
           sigmoid score plus a selection bias, no token dropped
    logits = RMSNorm(h_L) @ W_head, float32

A slot owns TWO kinds of page pool, and the builder declares both
(``geometry["state"]``): a full layer's K and V pools ``wmd_k_<i>`` /
``wmd_v_<i>`` ``[pages, page_size, kv_heads * head_dim]`` grow with the
sequence through the page table, ``pages_per_slot`` pages a slot; a window
layer's are RINGS of ``R = ceil((window + tokens_per_dispatch - 1) /
page_size) + 1`` pages a slot (``state["windowed"]``: the window, the
pools, ``R``, the ring's size and the names of its two feeds), enough for
the rows a dispatch's first query can see to the row its last step
writes, at any alignment (``kernels/window_paged_attention.py``).

The programs' frame (buckets, budget, rungs, feeds, sampler, fetches) is
``models/decoder_programs.py``'s, which gives a declared ring its feeds
(``window_rows [B, R]`` for a prefill, ``window_table [S, R]`` for a
step). A prefill runs the flash kernel (``window=`` on a window layer); a
full layer writes every page of the prompt, a window layer only those its
ring keeps after the prompt. The matrices are stored input-major.
"""

import collections
import functools

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.core.types import np_dtype
from paddle_tpu.kernels.paged_attention import pages_for
from paddle_tpu.models import decoder_programs

__all__ = ["windowed_dims", "parameter_shapes", "random_parameters",
           "load_parameters", "ring_pages_per_slot",
           "build_windowed_moe_decoder"]

SLIDING, FULL = "sliding_attention", "full_attention"


def windowed_dims(desc):
    """The sizes the programs are built from, by the config's keys."""
    d = dict(
        D=int(desc["hidden_size"]), H=int(desc["num_attention_heads"]),
        Hkv=int(desc["num_key_value_heads"]), dh=int(desc["head_dim"]),
        F=int(desc["intermediate_size"]),
        Fm=int(desc["moe_intermediate_size"]), E=int(desc["num_experts"]),
        k=int(desc["num_experts_per_tok"]),
        shared=int(desc.get("num_shared_experts", 0)),
        dense=int(desc.get("num_dense_layers", 0)),
        L=int(desc["num_hidden_layers"]), V=int(desc["vocab_size"]),
        W=int(desc["sliding_window"]),
        eps=float(desc.get("rms_norm_eps", 1e-5)),
        theta=float(desc.get("rope_theta", 10000.0)),
        scale=float(desc.get("route_scale", 1.0)),
        norm_topk=bool(desc.get("route_norm", True)),
        embed_scale=(float(desc["hidden_size"]) ** 0.5
                     if desc.get("mup_enabled", False) else 1.0))
    kinds = list(desc["layer_types"])
    if len(kinds) != d["L"] or set(kinds) - {SLIDING, FULL}:
        raise ValueError("layer_types must name %d layers, each %r or %r; "
                         "got %r" % (d["L"], SLIDING, FULL, kinds))
    if d["H"] % d["Hkv"]:
        raise ValueError("%d query heads over %d key/value heads: heads "
                         "must divide" % (d["H"], d["Hkv"]))
    if desc.get("score_func", "sigmoid") != "sigmoid" \
            or desc.get("n_group", 1) != 1 \
            or desc.get("topk_group", 1) != 1 \
            or desc.get("rope_scaling") is not None \
            or desc.get("tie_word_embeddings", False):
        raise NotImplementedError(
            "built: sigmoid scores, one routing group, no RoPE scaling, an "
            "untied head; got %r" % {
                k: desc.get(k) for k in (
                    "score_func", "n_group", "topk_group", "rope_scaling",
                    "tie_word_embeddings")})
    d["kinds"] = kinds
    return d


def ring_pages_per_slot(window, tokens_per_dispatch, page_size):
    """Pages a slot's ring needs in a window layer: the rows the first of
    a dispatch's queries sees to the row its last step writes are ``window
    + tokens_per_dispatch - 1`` positions, and one page more for their
    alignment."""
    return pages_for(int(window) + int(tokens_per_dispatch) - 1,
                     int(page_size)) + 1


def parameter_shapes(desc, dtype="bfloat16"):
    """{name: (shape, dtype)} of every parameter, in layer order. The
    router's selection bias is float32 whatever ``dtype`` is."""
    d = windowed_dims(desc)
    D, row, qw = d["D"], d["Hkv"] * d["dh"], d["H"] * d["dh"]
    out = collections.OrderedDict()

    def add(name, *shape, **kw):
        out[name] = (tuple(shape), kw.get("dtype", dtype))

    add("wmd_embed", d["V"], D)
    for i in range(d["L"]):
        p = "wmd_%d_" % i
        add(p + "attn_norm", D)
        add(p + "q", D, qw)
        add(p + "k", D, row)
        add(p + "v", D, row)
        add(p + "gate", D, qw)
        add(p + "q_norm", d["dh"])
        add(p + "k_norm", d["dh"])
        add(p + "o", qw, D)
        add(p + "post_attn_norm", D)
        add(p + "pre_mlp_norm", D)
        if i < d["dense"]:
            add(p + "ffn_gate", D, d["F"])
            add(p + "ffn_up", D, d["F"])
            add(p + "ffn_down", d["F"], D)
        else:
            add(p + "router", D, d["E"])
            add(p + "router_bias", d["E"], dtype="float32")
            add(p + "experts_gate", d["E"], D, d["Fm"])
            add(p + "experts_up", d["E"], D, d["Fm"])
            add(p + "experts_down", d["E"], d["Fm"], D)
            if d["shared"]:
                Fs = d["Fm"] * d["shared"]
                add(p + "shared_gate", D, Fs)
                add(p + "shared_up", D, Fs)
                add(p + "shared_down", Fs, D)
        add(p + "post_mlp_norm", D)
    add("wmd_final_norm", D)
    add("wmd_head", D, d["V"])
    return out


def random_parameters(desc, seed=0, dtype="float32"):
    """Seeded parameters for tests and examples (host arrays): matrices
    ``N(0, 1/fan_in)``, the embedding ``N(0, 1/hidden_size)`` (the
    ``mup_enabled`` scale brings its rows back to variance 1), norm
    scales near 1, the selection bias uniform in +-0.01."""
    rng = np.random.RandomState(seed)
    d = windowed_dims(desc)
    out = collections.OrderedDict()
    for name, (shape, dt) in parameter_shapes(desc, dtype).items():
        if name.endswith("router_bias"):
            v = rng.uniform(-0.01, 0.01, shape)
        elif name.endswith("norm"):
            v = 1.0 + 0.1 * rng.standard_normal(shape)
        elif name == "wmd_embed":
            v = rng.standard_normal(shape) / d["embed_scale"]
        else:
            v = rng.standard_normal(shape) * shape[-2] ** -0.5
        out[name] = np.asarray(v, "float32").astype(np_dtype(dt))
    return out


load_parameters = functools.partial(decoder_programs.load_parameters,
                                    parameter_shapes)


def _family(desc, dtype, tokens_per_dispatch):
    """This family's layers and state (``decoder_programs.DecoderFamily``);
    ``geometry["state"]["windowed"]`` declares the window layers' rings.
    The session's ``num_pages`` sizes the full layers' pools; a ring is
    always at full occupancy (``1 + num_slots * R`` pages)."""
    nn = fluid.layers
    d = windowed_dims(desc)
    kinds, W = d["kinds"], d["W"]
    row = d["Hkv"] * d["dh"]

    def state(S, P, ps, npp):
        R = min(ring_pages_per_slot(W, tokens_per_dispatch, ps), npp)
        Pw = 1 + S * R
        page_pools = collections.OrderedDict()
        for i, kind in enumerate(kinds):
            for part in "kv":
                page_pools["wmd_%s_%d" % (part, i)] = {
                    "shape": (Pw if kind == SLIDING else P, ps, row),
                    "dtype": dtype}
        windowed = [{
            "window": W, "pages_per_slot": R, "num_pages": Pw,
            "pools": [n for n in page_pools
                      if kinds[int(n.rsplit("_", 1)[1])] == SLIDING],
            "table_feed": "window_table", "rows_feed": "window_rows"}] \
            if SLIDING in kinds else []
        return {"page_pools": page_pools, "slot_arrays": {},
                "windowed": windowed}

    def blocks(f, x, attend):
        """The L blocks and the final norm on token rows ``x`` [N, D];
        ``attend(i, q, k, v, q_norm, k_norm)`` norms q and k a head, turns
        them on a sliding layer, writes layer ``i``'s rows and attends.
        Returns (x, chosen per expert layer, tokens per expert per expert
        layer)."""
        w = f.w
        chosen, counts = [], []
        x = nn.scale(x, scale=d["embed_scale"])
        for i in range(d["L"]):
            p = "wmd_%d_" % i
            nx = nn.rms_norm(x, w(p + "attn_norm"), d["eps"])
            q, k, v, gate = [nn.dense_projection(nx, w(p + part))
                             for part in ("q", "k", "v", "gate")]
            att = nn.sigmoid_gate(attend(i, q, k, v, w(p + "q_norm"),
                                         w(p + "k_norm")), gate)
            x = nn.elementwise_add(x, nn.rms_norm(
                nn.dense_projection(att, w(p + "o")),
                w(p + "post_attn_norm"), d["eps"]))
            nx = nn.rms_norm(x, w(p + "pre_mlp_norm"), d["eps"])
            if i < d["dense"]:
                ff = nn.gated_ffn(nx, w(p + "ffn_gate"), w(p + "ffn_up"),
                                  w(p + "ffn_down"))
            else:
                shared = ((w(p + "shared_gate"), w(p + "shared_up"),
                           w(p + "shared_down")) if d["shared"] else None)
                ff, ch, cnt = nn.dropless_moe_ffn(
                    nx, w(p + "router"), w(p + "router_bias"),
                    w(p + "experts_gate"), w(p + "experts_up"),
                    w(p + "experts_down"), shared=shared, valid=f.valid,
                    top_k=d["k"], norm_topk=d["norm_topk"],
                    scale=d["scale"])
                chosen.append(ch)
                counts.append(cnt)
            x = nn.elementwise_add(x, nn.rms_norm(
                ff, w(p + "post_mlp_norm"), d["eps"]))
        return nn.rms_norm(x, w("wmd_final_norm"), d["eps"]), chosen, counts

    norm_attrs = dict(heads=d["H"], kv_heads=d["Hkv"], theta=d["theta"],
                      epsilon=d["eps"])

    def prefill(f, x):
        def attend(i, q, k, v, q_norm, k_norm):
            sliding = kinds[i] == SLIDING
            q, k = nn.qk_norm_rope(q, k, q_norm, k_norm, rope=sliding,
                                   period=f.bucket, **norm_attrs)
            for part, rows in (("k", k), ("v", v)):
                pool = f.state["wmd_%s_%d" % (part, i)]
                if sliding:
                    nn.window_row_prefill(pool, rows, f.ring_rows[0],
                                          f.lens, W)
                else:
                    nn.latent_row_prefill(pool, rows, f.page_rows, f.lens)
            return nn.window_prefill_attention(
                q, k, v, prompts=f.rows, heads=d["H"], kv_heads=d["Hkv"],
                window=W if sliding else 0)

        x, chosen, _counts = blocks(f, x, attend)
        return x, [("first_chosen", chosen)]

    def step(f, x):
        def attend(i, q, k, v, q_norm, k_norm):
            sliding = kinds[i] == SLIDING
            q, k = nn.qk_norm_rope(q, k, q_norm, k_norm, rope=sliding,
                                   positions=f.pos, **norm_attrs)
            k_pool, v_pool = f.state["wmd_k_%d" % i], \
                f.state["wmd_v_%d" % i]
            if sliding:
                ring = f.ring_tables[0]
                nn.window_row_write(k_pool, k, ring, f.pos)
                nn.window_row_write(v_pool, v, ring, f.pos)
                return nn.window_paged_attention(
                    q, k_pool, v_pool, ring, f.lengths, heads=d["H"],
                    window=W)
            nn.latent_row_write(k_pool, k, f.table, f.pos)
            nn.latent_row_write(v_pool, v, f.table, f.pos)
            return nn.gqa_paged_attention(q, k_pool, v_pool, f.table,
                                          f.lengths, heads=d["H"])

        x, chosen, counts = blocks(f, x, attend)
        return x, [("chosen", chosen), ("expert_tokens", counts)]

    return decoder_programs.DecoderFamily(
        "wmd", parameter_shapes(desc, dtype), d["V"], state, prefill,
        step, geometry={
            "row_width": row, "layer_kinds": kinds,
            "moe_layers": list(range(d["dense"], d["L"]))})


build_windowed_moe_decoder = functools.partial(
    decoder_programs.build_decoder_programs, _family)
