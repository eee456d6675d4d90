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

``build_windowed_moe_decoder`` returns what ``build_latent_moe_decoder``
returns, with the same feeds plus the ring's (``window_rows [B, R]`` for
a prefill, ``window_table [S, R]`` for a step), so
``serving.decoder_session.DecoderOnlySession`` dispatches any of the
three:

* ``init`` zeroes the pools.
* ``prefill[T]``: ``prompts_per_dispatch(T)`` prompts a dispatch through
  the flash kernel (``window=`` on a window layer); a full layer writes
  every page of the prompt, a window layer only those its ring keeps
  after the prompt.
* ``step``: one token for every slot, ``tokens_per_dispatch`` a dispatch
  by ``Executor.run_multi_step``.

Parameters are declared by name (``parameter_shapes``) and loaded
(``load_parameters``); the matrices are stored input-major.
"""

import collections

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.core.types import np_dtype
from paddle_tpu.kernels.paged_attention import pages_for

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


def load_parameters(scope, named, desc=None, dtype=None):
    """Put a checkpoint's arrays into ``scope`` under the programs' names.
    With ``desc`` every parameter must be there with its shape."""
    from paddle_tpu.models.latent_moe_decoder import load_named

    load_named(scope, named, desc and parameter_shapes(desc,
                                                       dtype or "bfloat16"))


def build_windowed_moe_decoder(desc, num_slots, max_positions, page_size,
                               prefill_buckets, num_pages=None,
                               prefill_token_budget=2048, sampler=None,
                               dtype="bfloat16", probe_rows=0,
                               tokens_per_dispatch=1):
    """Build the serving programs (module docstring). Returns what
    ``models.latent_moe_decoder.build_latent_moe_decoder`` returns;
    ``geometry["state"]["windowed"]`` declares the window layers' rings.
    ``num_pages`` sizes the full layers' pools; a ring is always at full
    occupancy (``1 + num_slots * R`` pages)."""
    from paddle_tpu import unique_name
    from paddle_tpu.models.transformer import _sampler_attrs

    nn = fluid.layers
    d = windowed_dims(desc)
    kinds, W = d["kinds"], d["W"]
    S, ps = int(num_slots), int(page_size)
    npp = pages_for(max_positions, ps)
    P = int(num_pages) if num_pages else 1 + S * npp
    R = min(ring_pages_per_slot(W, tokens_per_dispatch, ps), npp)
    Pw = 1 + S * R
    samp = _sampler_attrs(sampler)
    buckets = sorted(int(t) for t in prefill_buckets)
    if any(t % ps for t in buckets):
        raise ValueError("every prefill bucket (%s) must be a multiple of "
                         "the page size %d: rows are written a page at a "
                         "time" % (buckets, ps))
    per_dispatch = {t: max(1, int(prefill_token_budget) // t)
                    for t in buckets}
    shapes = parameter_shapes(desc, dtype)
    row = d["Hkv"] * d["dh"]
    moe_layers = list(range(d["dense"], d["L"]))
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

    def declare(blk, name, shape, dt):
        return blk.create_var(name=name, shape=list(shape), dtype=dt,
                              persistable=True)

    def state(blk):
        pools = {name: declare(blk, name, spec["shape"], spec["dtype"])
                 for name, spec in page_pools.items()}
        return (pools, declare(blk, "wmd_tok", (S, 1), "int64"),
                declare(blk, "wmd_pos", (S, 1), "int64"))

    def blocks(blk, x, attend, valid):
        """The L blocks and the final norm on token rows ``x`` [N, D];
        ``attend(i, q, k, v)`` writes layer ``i``'s rows and attends (q
        and k after their norms and RoPE). Returns (x, chosen per expert
        layer, tokens per expert per expert layer)."""
        def w(name):
            return declare(blk, name, *shapes[name])

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
                    w(p + "experts_down"), shared=shared, valid=valid,
                    top_k=d["k"], norm_topk=d["norm_topk"],
                    scale=d["scale"])
                chosen.append(ch)
                counts.append(cnt)
            x = nn.elementwise_add(x, nn.rms_norm(
                ff, w(p + "post_mlp_norm"), d["eps"]))
        return nn.rms_norm(x, w("wmd_final_norm"), d["eps"]), chosen, counts

    def stacked(name, parts, shape):
        """The per-layer parts as ONE fetchable variable."""
        out = nn.concat([nn.reshape(c, shape=[1] + shape) for c in parts],
                        axis=0)
        return nn.assign(out, output=fluid.default_main_program()
                         .global_block().create_var(name=name,
                                                    dtype="int32"))

    def logits_of(blk, rows, name, count):
        out = nn.dense_projection(
            rows, declare(blk, "wmd_head", *shapes["wmd_head"]),
            out_dtype="float32")
        return nn.assign(nn.reshape(out, shape=[count, 1, d["V"]]),
                         output=blk.create_var(name=name, dtype="float32"))

    def feed(name, shape):
        return nn.data(name, shape=shape, dtype="int64",
                       append_batch_size=False)

    norm_attrs = dict(heads=d["H"], kv_heads=d["Hkv"], theta=d["theta"],
                      epsilon=d["eps"])

    with unique_name.guard({}):
        init = fluid.Program()
        with fluid.program_guard(init, fluid.Program()):
            pools, tok, pos = state(init.global_block())
            for name, var in pools.items():
                spec = page_pools[name]
                nn.assign(nn.fill_constant(list(spec["shape"]),
                                           spec["dtype"], 0.0), output=var)
            nn.assign(nn.fill_constant([S, 1], "int64", 0), output=tok)
            nn.assign(nn.fill_constant([S, 1], "int64", 0), output=pos)

        prefill = {}
        for T in buckets:
            B = per_dispatch[T]
            prog = prefill[T] = fluid.Program()
            with unique_name.guard({}), \
                    fluid.program_guard(prog, fluid.Program()):
                blk = prog.global_block()
                pools, tok, pos = state(blk)
                ids = feed("prompt_ids", [B * T])
                lens = feed("prompt_len", [B])
                slot_idx = feed("slot_idx", [B])
                page_rows = feed("page_rows", [B, npp])
                ring_rows = feed("window_rows", [B, R]) if windowed \
                    else None
                last_idx = feed("last_idx", [B])
                valid = nn.reshape(
                    nn.sequence_mask(lens, maxlen=T, dtype="int64"),
                    shape=[B * T])
                x = nn.embedding_rows(
                    declare(blk, "wmd_embed", *shapes["wmd_embed"]), ids)

                def attend(i, q, k, v, q_norm, k_norm, B=B, T=T,
                           pools=pools, page_rows=page_rows,
                           ring_rows=ring_rows, lens=lens):
                    sliding = kinds[i] == SLIDING
                    q, k = nn.qk_norm_rope(q, k, q_norm, k_norm,
                                           rope=sliding, period=T,
                                           **norm_attrs)
                    for part, rows in (("k", k), ("v", v)):
                        pool = pools["wmd_%s_%d" % (part, i)]
                        if sliding:
                            nn.window_row_prefill(pool, rows, ring_rows,
                                                  lens, W)
                        else:
                            nn.latent_row_prefill(pool, rows, page_rows,
                                                  lens)
                    return nn.window_prefill_attention(
                        q, k, v, prompts=B, heads=d["H"],
                        kv_heads=d["Hkv"], window=W if sliding else 0)

                x, chosen, counts = blocks(blk, x, attend, valid)
                logits = logits_of(blk, nn.gather(x, last_idx),
                                   "wmd_first_logits", B)
                lens2 = nn.reshape(lens, shape=[B, 1])
                first, _p, _d = nn.slot_decode_sample(
                    logits, lens2, eos_id=0,
                    max_length=int(max_positions) + 2, **samp)
                nn.assign(first, output=blk.create_var(
                    name="wmd_first_tok", dtype="int64"))
                nn.slot_rows_write(tok, slot_idx, first)
                nn.slot_rows_write(pos, slot_idx, lens2)
                if chosen:
                    stacked("wmd_first_chosen", chosen, [B * T, d["k"]])

        step = fluid.Program()
        with unique_name.guard({}), \
                fluid.program_guard(step, fluid.Program()):
            blk = step.global_block()
            pools, tok, pos = state(blk)
            table = feed("page_table", [S, npp])
            ring_table = feed("window_table", [S, R]) if windowed else None
            live = feed("live", [S, 1])
            # resident rows AFTER this step's write; 0 for an empty slot
            lengths = nn.elementwise_mul(
                nn.increment(pos, value=1, in_place=False), live)
            done = nn.elementwise_sub(
                nn.fill_constant([S, 1], "int64", 1), live)
            x = nn.embedding_rows(
                declare(blk, "wmd_embed", *shapes["wmd_embed"]), tok)

            def attend(i, q, k, v, q_norm, k_norm):
                sliding = kinds[i] == SLIDING
                q, k = nn.qk_norm_rope(q, k, q_norm, k_norm, rope=sliding,
                                       positions=pos, **norm_attrs)
                k_pool, v_pool = pools["wmd_k_%d" % i], \
                    pools["wmd_v_%d" % i]
                if sliding:
                    nn.window_row_write(k_pool, k, ring_table, pos)
                    nn.window_row_write(v_pool, v, ring_table, pos)
                    return nn.window_paged_attention(
                        q, k_pool, v_pool, ring_table, lengths,
                        heads=d["H"], window=W)
                nn.latent_row_write(k_pool, k, table, pos)
                nn.latent_row_write(v_pool, v, table, pos)
                return nn.gqa_paged_attention(q, k_pool, v_pool, table,
                                              lengths, heads=d["H"])

            x, chosen, counts = blocks(blk, x, attend, live)
            logits = logits_of(blk, x, "wmd_logits", S)
            if probe_rows:
                probe = feed("probe_slots", [int(probe_rows)])
                nn.assign(
                    nn.gather(nn.reshape(logits, shape=[S, d["V"]]), probe),
                    output=blk.create_var(name="wmd_probe_logits",
                                          dtype="float32"))
            tok_new, pos_new, _done = nn.slot_decode_sample(
                logits, pos, done=done, eos_id=0,
                max_length=int(max_positions) + 2, **samp)
            nn.assign(tok_new, output=blk.create_var(
                name="wmd_step_tok", dtype="int64"))
            if chosen:
                stacked("wmd_chosen", chosen, [S, d["k"]])
                stacked("wmd_expert_tokens", counts, [d["E"]])
            nn.assign(tok_new, output=tok)
            nn.assign(pos_new, output=pos)

    has_moe = bool(moe_layers)
    return {
        "init": init, "prefill": prefill, "step": step,
        "fetches": {
            "token": "wmd_step_tok", "first_token": "wmd_first_tok",
            "logits": "wmd_logits", "first_logits": "wmd_first_logits",
            "probe_logits": "wmd_probe_logits" if probe_rows else None,
            "expert_tokens": "wmd_expert_tokens" if has_moe else None,
            "chosen": "wmd_chosen" if has_moe else None,
            "first_chosen": "wmd_first_chosen" if has_moe else None},
        "geometry": {
            "num_slots": S, "page_size": ps, "pages_per_slot": npp,
            "num_pages": P, "row_width": row, "buckets": buckets,
            "prompts_per_dispatch": per_dispatch,
            "prefill_token_budget": int(prefill_token_budget),
            "layer_kinds": kinds, "moe_layers": moe_layers, "dtype": dtype,
            "state": {"page_pools": page_pools, "slot_arrays": {},
                      "windowed": windowed}},
    }
