"""Layers of a decoder-only block with latent (MLA) attention and routed
experts (``ops/decoder_ops.py``, ``ops/moe_ops.py`` ``dropless_moe_ffn``).
They take flat token rows ``[N, ...]`` and the parameters as variables:
the serving builder (``models/latent_moe_decoder.py``) declares those by
name and a checkpoint fills them. The last four are a gated delta-rule
linear-attention mixer's (``ops/linear_attention_ops.py``,
``models/linear_attn_moe_decoder.py``)."""

from paddle_tpu.layer_helper import LayerHelper

__all__ = [
    "rms_norm",
    "gated_ffn",
    "dense_projection",
    "dropless_moe_ffn",
    "latent_rope_rows",
    "latent_row_write",
    "latent_row_prefill",
    "latent_paged_attention",
    "latent_prefill_attention",
    "indexer_rows",
    "index_select_decode",
    "sparse_latent_paged_attention",
    "index_select_prefill",
    "sparse_latent_prefill_attention",
    "slot_rows_write",
    "embedding_rows",
    "delta_rule_gates",
    "delta_rule_prefill",
    "delta_rule_state_update",
    "gated_head_norm",
]


def _one(op_type, inputs, attrs=None, dtype=None, name=None):
    helper = LayerHelper(op_type, name=name)
    first = next(iter(inputs.values()))[0]
    out = helper.create_variable_for_type_inference(dtype or first.dtype)
    helper.append_op(type=op_type, inputs=inputs, outputs={"Out": [out]},
                     attrs=attrs or {})
    return out


def rms_norm(x, scale, epsilon=1e-5, name=None):
    """``x / sqrt(mean(x^2) + epsilon) * scale`` over the last axis, the
    statistics in float32."""
    return _one("rms_norm", {"X": [x], "Scale": [scale]},
                {"epsilon": float(epsilon)}, name=name)


def gated_ffn(x, w_gate, w_up, w_down, name=None):
    """SwiGLU: ``(silu(x Wg) * (x Wu)) Wd``."""
    return _one("gated_ffn", {"X": [x], "WGate": [w_gate], "WUp": [w_up],
                              "WDown": [w_down]}, name=name)


def dense_projection(x, w, out_dtype="input", name=None):
    """``x @ w`` accumulated in float32; ``out_dtype="float32"`` keeps the
    accumulator (the logits), ``"input"`` rounds to ``x``'s dtype."""
    return _one("dense_projection", {"X": [x], "W": [w]},
                {"out_dtype": out_dtype},
                dtype="float32" if out_dtype == "float32" else None,
                name=name)


def dropless_moe_ffn(x, router_w, router_bias, expert_w_gate, expert_w_up,
                     expert_w_down, shared=None, valid=None, top_k=1,
                     norm_topk=True, scale=1.0, held_first=None, name=None,
                     scoring=None, zero_experts=0, token_block=None):
    """Routed experts with no capacity and no dropped token: sigmoid
    scores plus a selection bias, the ``top_k`` largest chosen, the
    (token, expert) pairs sorted by expert and computed as grouped matrix
    products; ``shared`` = (gate, up, down) of a shared expert added once.
    ``scoring="softmax_topk"`` is the second rule: the ``top_k`` largest
    raw logits, a softmax over those alone, ``router_bias`` None;
    ``scoring="softmax"`` the third: a softmax over ALL the router's
    outputs, the ``top_k`` largest of ``p + router_bias`` chosen, the
    weights ``scale * p`` of the chosen, not renormalised. With
    ``zero_experts`` = Z the router's last Z outputs are zero-compute
    (identity) experts: a choice that falls on one adds ``weight * x``
    and has no expert's products, held shard or not, and a fourth value
    is returned, the valid tokens' choices that fell on one ``[1]``.
    ``token_block``: the tokens a block in which a held shard's large
    dispatch goes through the experts (the op's 2048 when None; the rows
    sorted for a block are ``token_block * top_k``).
    ``valid`` [N] marks the tokens that exist (others are neither computed
    nor counted). With ``held_first`` the expert weights are a shard of
    the router's experts that starts there: the pairs of experts held
    elsewhere are left out of the sum. Returns (out [N, D], chosen [N, top_k],
    tokens each held expert got [E])."""
    helper = LayerHelper("dropless_moe_ffn", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    chosen = helper.create_variable_for_type_inference(
        "int32", stop_gradient=True)
    counts = helper.create_variable_for_type_inference(
        "int32", stop_gradient=True)
    inputs = {"X": [x], "RouterW": [router_w],
              "ExpertWGate": [expert_w_gate], "ExpertWUp": [expert_w_up],
              "ExpertWDown": [expert_w_down]}
    if router_bias is not None:
        inputs["RouterBias"] = [router_bias]
    if shared is not None:
        inputs.update(SharedWGate=[shared[0]], SharedWUp=[shared[1]],
                      SharedWDown=[shared[2]])
    if valid is not None:
        inputs["Valid"] = [valid]
    outputs = {"Out": [out], "Chosen": [chosen], "ExpertTokens": [counts]}
    # a rule, and the identities, are named only where they are asked for:
    # the programs of the models that do not ask keep what they had
    extra = {"scoring": scoring} if scoring else {}
    if zero_experts:
        zeros = helper.create_variable_for_type_inference(
            "int32", stop_gradient=True)
        outputs["ZeroTokens"] = [zeros]
        extra["zero_experts"] = int(zero_experts)
    if token_block:
        extra["token_block"] = int(token_block)
    helper.append_op(
        type="dropless_moe_ffn", inputs=inputs, outputs=outputs,
        attrs=dict({"top_k": int(top_k), "norm_topk": bool(norm_topk),
                    "scale": float(scale),
                    "held_first": -1 if held_first is None
                    else int(held_first)}, **extra))
    if zero_experts:
        return out, chosen, counts, zeros
    return out, chosen, counts


def latent_rope_rows(q, kva, kv_norm, heads, nope_dim, rope_dim, theta,
                     positions=None, period=0, epsilon=1e-5,
                     interleave=False, name=None, q_scale=1.0,
                     kv_scale=1.0, rotate=True):
    """The query ``[N, H, dn + dr]`` with RoPE on its rotary part, and the
    row to cache ``[N, C + dr]`` = ``[RMSNorm(ckv) | RoPE(k_rope)]``.
    ``positions`` [N] (decode), or none and ``period`` = the bucket length
    (prefill: token ``n`` stands at ``n % period``). ``interleave``:
    RoPE over adjacent pairs, else over the two halves. ``q_scale``
    multiplies the query (both parts) and ``kv_scale`` the normed
    compressed part of the row (not its rotary key), in float32 before
    they are rounded: the two constants of a model whose low-rank paths
    are rescaled (``mla_scale_q_lora`` / ``mla_scale_kv_lora``).
    ``rotate=False`` is a model with NO positional encoding
    (``mla_use_nope``): neither rotary part is rotated, ``positions`` and
    ``period`` are not read and the scales must be 1."""
    if not rotate and ((q_scale, kv_scale) != (1.0, 1.0)
                       or positions is not None):
        raise ValueError("latent_rope_rows(rotate=False) takes no "
                         "positions and no q_scale / kv_scale")
    helper = LayerHelper("latent_rope_rows", name=name)
    q_out = helper.create_variable_for_type_inference(q.dtype)
    row = helper.create_variable_for_type_inference(q.dtype)
    inputs = {"Q": [q], "KVA": [kva], "KVNorm": [kv_norm]}
    if positions is not None:
        inputs["Positions"] = [positions]
    attrs = {"heads": int(heads), "nope_dim": int(nope_dim),
             "rope_dim": int(rope_dim), "theta": float(theta),
             "period": int(period), "epsilon": float(epsilon),
             "interleave": bool(interleave)}
    # named only where a model has them: the others' programs keep the
    # attributes they had
    if (q_scale, kv_scale) != (1.0, 1.0):
        attrs.update(q_scale=float(q_scale), kv_scale=float(kv_scale))
    if not rotate:
        attrs["rotate"] = False
    helper.append_op(
        type="latent_rope_rows", inputs=inputs,
        outputs={"QOut": [q_out], "Row": [row]}, attrs=attrs)
    return q_out, row


def latent_row_write(pool, rows, page_table, pos, name=None):
    """Decode's cache write; ``pool`` is updated in place (bound as the
    op's output, the optimizer-style state convention)."""
    helper = LayerHelper("latent_row_write", name=name)
    helper.append_op(
        type="latent_row_write",
        inputs={"Pool": [pool], "Rows": [rows], "PageTable": [page_table],
                "Pos": [pos]},
        outputs={"PoolOut": [pool]})
    return pool


def latent_row_prefill(pool, rows, page_rows, lens, name=None):
    """Prefill's cache write, a page at a time; ``pool`` in place."""
    helper = LayerHelper("latent_row_prefill", name=name)
    helper.append_op(
        type="latent_row_prefill",
        inputs={"Pool": [pool], "Rows": [rows], "PageRows": [page_rows],
                "Lens": [lens]},
        outputs={"PoolOut": [pool]})
    return pool


def latent_paged_attention(q, kv_b, pool, page_table, lengths, nope_dim,
                           name=None):
    """Absorbed-form decode attention of every slot over its cached rows
    (``kernels/latent_attention.py``): ``[S, H * v_dim]``."""
    return _one("latent_paged_attention",
                {"Q": [q], "KVB": [kv_b], "Pool": [pool],
                 "PageTable": [page_table], "Lengths": [lengths]},
                {"nope_dim": int(nope_dim)}, name=name)


def latent_prefill_attention(q, rows, kv_b, prompts, nope_dim, name=None):
    """Expanded-form causal attention of ``prompts`` prompts of equal
    (bucket) length through the flash kernel, the values at their own
    width: ``[N, H * v_dim]``."""
    return _one("latent_prefill_attention",
                {"Q": [q], "Rows": [rows], "KVB": [kv_b]},
                {"prompts": int(prompts), "nope_dim": int(nope_dim)},
                name=name)


def indexer_rows(cq, x, w_q, w_k, k_scale, k_shift, w_w, heads, rope_dim,
                 theta, positions=None, period=0, interleave=True,
                 epsilon=1e-6, name=None):
    """A sparse-attention indexer's rows a token
    (``ops/sparse_attention_ops.py``): its heads' queries ``[N, J, dI]``
    from the compressed query ``cq``, its one key ``[N, dI]`` (the narrow
    pool's row) and its heads' weights ``[N, J]`` float32 from the block's
    normed input ``x``; ``positions`` / ``period`` as ``latent_rope_rows``."""
    helper = LayerHelper("indexer_rows", name=name)
    q = helper.create_variable_for_type_inference(x.dtype)
    k = helper.create_variable_for_type_inference(x.dtype)
    w = helper.create_variable_for_type_inference("float32")
    inputs = {"CQ": [cq], "X": [x], "WQ": [w_q], "WK": [w_k],
              "KScale": [k_scale], "KShift": [k_shift], "WW": [w_w]}
    if positions is not None:
        inputs["Positions"] = [positions]
    helper.append_op(
        type="indexer_rows", inputs=inputs,
        outputs={"Q": [q], "K": [k], "W": [w]},
        attrs={"heads": int(heads), "rope_dim": int(rope_dim),
               "theta": float(theta), "period": int(period),
               "interleave": bool(interleave), "epsilon": float(epsilon)})
    return q, k, w


def index_select_decode(q, w, pool, page_table, lengths, top_k, name=None):
    """Every slot's ``top_k`` cached positions of largest index score, as
    positions ``[S, top_k]`` int32 (``-1``: none, last in the row)."""
    helper = LayerHelper("index_select_decode", name=name)
    out = helper.create_variable_for_type_inference(
        "int32", stop_gradient=True)
    helper.append_op(
        type="index_select_decode",
        inputs={"Q": [q], "W": [w], "Pool": [pool],
                "PageTable": [page_table], "Lengths": [lengths]},
        outputs={"Selected": [out]}, attrs={"top_k": int(top_k)})
    return out


def sparse_latent_paged_attention(q, kv_b, pool, page_table, selected,
                                  nope_dim, name=None):
    """``latent_paged_attention`` of every slot over its ``selected``
    positions alone."""
    return _one("sparse_latent_paged_attention",
                {"Q": [q], "KVB": [kv_b], "Pool": [pool],
                 "PageTable": [page_table], "Selected": [selected]},
                {"nope_dim": int(nope_dim)}, name=name)


def index_select_prefill(q, k, w, lens, prompts, top_k, name=None):
    """Every prompt row's ``top_k`` earlier positions of largest index
    score, as a mask ``[prompts, T, T]`` int8 (nothing for the rows past
    a prompt's length ``lens`` [prompts])."""
    helper = LayerHelper("index_select_prefill", name=name)
    out = helper.create_variable_for_type_inference(
        "int8", stop_gradient=True)
    helper.append_op(
        type="index_select_prefill",
        inputs={"Q": [q], "K": [k], "W": [w], "Lens": [lens]},
        outputs={"Mask": [out]},
        attrs={"prompts": int(prompts), "top_k": int(top_k)})
    return out


def sparse_latent_prefill_attention(q, rows, kv_b, mask, lens, prompts,
                                    nope_dim, name=None):
    """Expanded-form prefill attention under a choice's ``mask`` (None:
    every earlier position), bfloat16 on the MXU; the rows past a
    prompt's length ``lens`` [prompts] are not computed."""
    inputs = {"Q": [q], "Rows": [rows], "KVB": [kv_b], "Lens": [lens]}
    if mask is not None:
        inputs["Mask"] = [mask]
    return _one("sparse_latent_prefill_attention", inputs,
                {"prompts": int(prompts), "nope_dim": int(nope_dim)},
                name=name)


def slot_rows_write(state, index, values, name=None):
    """``state[index[b]] = values[b]``, in place; an index past the last
    row (a prefill batch's padding) writes nothing."""
    helper = LayerHelper("slot_rows_write", name=name)
    helper.append_op(
        type="slot_rows_write",
        inputs={"State": [state], "Index": [index], "Values": [values]},
        outputs={"StateOut": [state]})
    return state


def embedding_rows(table, ids, name=None):
    """Rows ``ids`` [N] of an embedding table that is already a variable
    (``layers.embedding`` creates its own parameter)."""
    return _one("lookup_table", {"W": [table], "Ids": [ids]}, name=name)


def delta_rule_gates(f, dt_bias, a_log, b, heads, beta_scale=1.0, name=None):
    """A delta-rule mixer's gates (``ops/linear_attention_ops.py``): the
    log decay ``-exp(a_log) * softplus(f + dt_bias)``, a key channel [N,
    heads * dk] or a head [N, heads] as ``f`` is, and the step
    ``beta_scale * sigmoid(b)`` [N, heads], both float32."""
    helper = LayerHelper("delta_rule_gates", name=name)
    g, beta = [helper.create_variable_for_type_inference("float32")
               for _ in range(2)]
    helper.append_op(
        type="delta_rule_gates",
        inputs={"X": [f], "DtBias": [dt_bias], "ALog": [a_log], "B": [b]},
        outputs={"G": [g], "Beta": [beta]},
        attrs={"heads": int(heads), "beta_scale": float(beta_scale)})
    return g, beta


def delta_rule_prefill(q, k, v, g, beta, lens, state_pack=1, name=None):
    """The gated delta rule over a prefill dispatch's prompts (one a
    bucket row), in chunks; ``g`` a key channel [N, heads * dk] or a head
    [N, heads]. Returns (out [N, heads * dv] float32, state [prompts,
    heads / state_pack, dk, state_pack * dv] float32 after each prompt's
    last real token: ``state_pack`` heads' value lanes side by side, as
    the served array holds them)."""
    helper = LayerHelper("delta_rule_prefill", name=name)
    out, state = [helper.create_variable_for_type_inference("float32")
                  for _ in range(2)]
    helper.append_op(
        type="delta_rule_prefill",
        inputs={"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta],
                "Lens": [lens]},
        outputs={"Out": [out], "State": [state]},
        # named only where asked: the other models' programs keep theirs
        attrs={"state_pack": int(state_pack)} if state_pack != 1 else {})
    return out, state


def delta_rule_state_update(state, q, k, v, g, beta, live, name=None):
    """One token of the gated delta rule for every slot: out [S, heads *
    dv] float32; ``state`` [S, heads / pack, dk, pack * dv] is updated in
    place (a slot that is not live keeps its own and reads 0)."""
    helper = LayerHelper("delta_rule_state_update", name=name)
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        type="delta_rule_state_update",
        inputs={"State": [state], "Q": [q], "K": [k], "V": [v], "G": [g],
                "Beta": [beta], "Live": [live]},
        outputs={"Out": [out], "StateOut": [state]})
    return out


def gated_head_norm(x, scale, gate, heads, epsilon=1e-5, gate_act=None,
                    name=None):
    """``RMSNorm`` over each of ``heads`` heads of ``x`` (one ``scale``
    vector) times ``sigmoid(gate)``, or ``silu(gate)`` under ``gate_act=
    "silu"``, in ``gate``'s dtype."""
    attrs = {"heads": int(heads), "epsilon": float(epsilon)}
    if gate_act:        # named only where asked
        attrs["gate_act"] = str(gate_act)
    return _one("gated_head_norm",
                {"X": [x], "Scale": [scale], "Gate": [gate]}, attrs,
                dtype=gate.dtype, name=name)
