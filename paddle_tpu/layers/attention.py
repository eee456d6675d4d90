"""Attention layers: scaled dot-product + multi-head attention.

Reference role: composed-op attention in the reference's Transformer test
model (tests/unittests/dist_transformer.py multi_head_attention); here the
core is the fused scaled_dot_product_attention op (Pallas flash kernel on
TPU, paddle_tpu/kernels/flash_attention.py).
"""

from paddle_tpu.layer_helper import LayerHelper

__all__ = [
    "scaled_dot_product_attention",
    "multi_head_attention",
    "paged_attention",
    "paged_kv_write",
    "paged_kv_prefill",
    "paged_copy_page",
    "grouped_cross_attention",
    "paged_tree_attention",
    "paged_spec_kv_write",
    "paged_spec_kv_compact",
    "slot_decode_sample",
    "slot_beam_search",
    "slot_speculative_accept",
    "label_smooth",
    "add_position_encoding",
    "rotary_position_embedding",
    "moe_ffn",
]


def scaled_dot_product_attention(
    queries, keys, values, mask=None, causal=False, sm_scale=None,
    impl="auto", seq_parallel_axis=None, kv_group=1, window=0, name=None
):
    """Fused attention over [batch, heads, seq, head_dim] tensors.

    With ``seq_parallel_axis`` (the name of a ParallelExecutor mesh
    axis), the op runs ring attention with the sequence sharded over
    that axis — in-program context parallelism for sequences too long
    for one chip."""
    helper = LayerHelper("sdpa", name=name)
    out = helper.create_variable_for_type_inference(queries.dtype)
    inputs = {"Q": [queries], "K": [keys], "V": [values]}
    if mask is not None:
        inputs["Mask"] = [mask]
    helper.append_op(
        type="scaled_dot_product_attention",
        inputs=inputs,
        outputs={"Out": [out]},
        attrs={
            "causal": causal,
            "sm_scale": float(sm_scale or 0.0),
            "impl": impl,
            "seq_parallel_axis": seq_parallel_axis or "",
            "kv_group": int(kv_group),
            "window": int(window),
        },
    )
    return out


def multi_head_attention(
    queries,
    keys,
    values,
    d_key,
    d_value,
    d_model,
    n_head=1,
    n_kv_head=None,
    dropout_rate=0.0,
    mask=None,
    causal=False,
    param_attr=None,
    is_test=False,
    name=None,
):
    """Projections + fused attention + output projection.

    queries/keys/values: [batch, seq, d_model]; returns [batch, seq,
    d_model]. All four projections are single fused matmuls (MXU-sized).

    ``n_kv_head`` enables grouped-query attention (GQA; beyond the
    reference): K/V are projected to n_kv_head heads (n_head must be a
    multiple) and the attention op serves each kv head to its query
    group through the kernel's index map — no repeated K/V tensor
    materializes, and the K/V projection weights and any cached K/V
    shrink by n_head/n_kv_head. n_kv_head=1 is multi-query attention.
    """
    from paddle_tpu.layers import nn as nn_layers

    if keys is None:
        keys = queries
    if values is None:
        values = keys

    kv_heads = n_head if n_kv_head is None else int(n_kv_head)
    if kv_heads < 1 or n_head % kv_heads != 0:
        raise ValueError(
            "multi_head_attention: n_kv_head (%d) must be >= 1 and "
            "divide n_head (%d)" % (kv_heads, n_head))
    q = nn_layers.fc(
        input=queries, size=d_key * n_head, num_flatten_dims=2,
        bias_attr=False, param_attr=param_attr,
        name=(name + "_q") if name else None,
    )
    k = nn_layers.fc(
        input=keys, size=d_key * kv_heads, num_flatten_dims=2,
        bias_attr=False, param_attr=param_attr,
        name=(name + "_k") if name else None,
    )
    v = nn_layers.fc(
        input=values, size=d_value * kv_heads, num_flatten_dims=2,
        bias_attr=False, param_attr=param_attr,
        name=(name + "_v") if name else None,
    )

    def split_heads(x, d_head, heads):
        # [B, T, H*dh] -> [B, H, T, dh]
        reshaped = nn_layers.reshape(x, shape=[0, 0, heads, d_head])
        return nn_layers.transpose(reshaped, perm=[0, 2, 1, 3])

    qh = split_heads(q, d_key, n_head)
    kh = split_heads(k, d_key, kv_heads)
    vh = split_heads(v, d_value, kv_heads)

    # grouped K/V ride through the attention op's kv_group attr: the
    # Pallas kernel maps query head h to kv head h // group in its index
    # map, so the repeated K/V never materializes
    ctx = scaled_dot_product_attention(
        qh, kh, vh, mask=mask, causal=causal,
        sm_scale=d_key ** -0.5, kv_group=n_head // kv_heads,
    )
    # [B, H, T, dh] -> [B, T, H*dh]
    merged = nn_layers.reshape(
        nn_layers.transpose(ctx, perm=[0, 2, 1, 3]),
        shape=[0, 0, n_head * d_value],
    )
    if dropout_rate:
        merged = nn_layers.dropout(
            merged, dropout_prob=dropout_rate, is_test=is_test
        )
    return nn_layers.fc(
        input=merged, size=d_model, num_flatten_dims=2, bias_attr=False,
        param_attr=param_attr, name=(name + "_o") if name else None,
    )


def rotary_position_embedding(q, k, position=None, base=10000.0,
                              name=None):
    """RoPE over [batch, heads, seq, head_dim] q/k (rotate-half
    convention); returns (q_rot, k_rot). ``position``: optional [1] int
    offset for KV-cached decoding. Beyond the reference — pairs with
    flash attention and n_kv_head for a modern attention stack."""
    helper = LayerHelper("rope", name=name)
    q_out = helper.create_variable_for_type_inference(q.dtype)
    k_out = helper.create_variable_for_type_inference(k.dtype)
    inputs = {"Q": [q], "K": [k]}
    if position is not None:
        inputs["Position"] = [position]
    helper.append_op(
        type="rotary_embedding",
        inputs=inputs,
        outputs={"QOut": [q_out], "KOut": [k_out]},
        attrs={"base": float(base)},
    )
    return q_out, k_out


def paged_attention(query, k_pool, v_pool, page_table, lengths,
                    sm_scale=None, impl="auto", name=None):
    """Ragged paged-attention decode (kernels/paged_attention.py).

    ``query`` [S, H, 1, dh] (one token per slot), ``k_pool``/``v_pool``
    [num_pages, page_size, H * dh] (whole token rows, heads
    contiguous), ``page_table`` [S, pages_per_slot]
    int page ids, ``lengths`` [S] (or [S, 1]) resident tokens per slot.
    Per-slot cost is bounded by the slot's OWN length — empty pages and
    unoccupied slots are skipped, so decode traffic scales with tokens
    actually resident, not ``S x max_length``."""
    helper = LayerHelper("paged_attention", name=name)
    out = helper.create_variable_for_type_inference(query.dtype)
    helper.append_op(
        type="paged_attention",
        inputs={"Q": [query], "KPool": [k_pool], "VPool": [v_pool],
                "PageTable": [page_table], "Lengths": [lengths]},
        outputs={"Out": [out]},
        attrs={"sm_scale": float(sm_scale or 0.0), "impl": impl},
    )
    return out


def paged_kv_write(k_pool, v_pool, k_new, v_new, page_table, pos,
                   name=None):
    """O(page) KV-pool write: each slot's new K/V row ``[S, H, 1, dh]``
    lands as one ``H * dh`` row of the ``[num_pages, page_size, H * dh]``
    pool at (``page_table[s, pos // page_size]``, ``pos % page_size``).
    Pass the pool vars as both input and output (the optimizer-style
    in-place state convention): this layer binds ``KOut``/``VOut`` back
    onto the pool vars, so the executor threads the update."""
    helper = LayerHelper("paged_kv_write", name=name)
    helper.append_op(
        type="paged_kv_write",
        inputs={"KPool": [k_pool], "VPool": [v_pool], "KNew": [k_new],
                "VNew": [v_new], "PageTable": [page_table], "Pos": [pos]},
        outputs={"KOut": [k_pool], "VOut": [v_pool]},
    )
    return k_pool, v_pool


def paged_kv_prefill(k_pool, v_pool, k_new, v_new, page_row, write_from,
                     length, name=None):
    """Chunked-prefill KV scatter: land a forced prefix's whole
    ``[1, H, T, dh]`` K/V rows into the slot's pages in one op —
    position ``p`` writes at ``(page_row[p // page_size],
    p % page_size)`` for ``write_from <= p < length - 1``; positions a
    prefix-cache hit already covers, and the pad tail, route to the
    trash page. In-place state convention: binds ``KOut``/``VOut`` back
    onto the pool vars."""
    helper = LayerHelper("paged_kv_prefill", name=name)
    helper.append_op(
        type="paged_kv_prefill",
        inputs={"KPool": [k_pool], "VPool": [v_pool], "KNew": [k_new],
                "VNew": [v_new], "PageRow": [page_row],
                "WriteFrom": [write_from], "Len": [length]},
        outputs={"KOut": [k_pool], "VOut": [v_pool]},
    )
    return k_pool, v_pool


def paged_copy_page(k_pool, v_pool, src_page, dst_page, name=None):
    """On-device page copy (the COW primitive): ``pool[dst] =
    pool[src]`` for both the K and V pool in one op. ``src_page`` /
    ``dst_page`` are ``[n]`` int tensors, one pair or a whole window's,
    every source read before any destination is written (the op's
    lowering says which windows that allows). The serving session
    dispatches this before repointing a forked slot's table row at the
    private copy. In-place state convention on the pool vars."""
    helper = LayerHelper("paged_copy_page", name=name)
    helper.append_op(
        type="paged_copy_page",
        inputs={"KPool": [k_pool], "VPool": [v_pool], "Src": [src_page],
                "Dst": [dst_page]},
        outputs={"KOut": [k_pool], "VOut": [v_pool]},
    )
    return k_pool, v_pool


def paged_tree_attention(query, k_pool, v_pool, page_table, base_lens,
                         anc, sm_scale=None, max_length=0, impl="auto",
                         name=None):
    """Speculative tree-verify attention over the paged pool
    (kernels/paged_attention.py ``paged_tree_attention``).

    ``query`` [S, H, N, dh] — N speculation-tree nodes per slot, laid
    out linearly in the slot's write pages at storage positions
    ``base .. base + N - 1``; ``base_lens`` [S] (or [S, 1]) committed
    rows per slot (-1 marks a done slot: output exactly 0); ``anc``
    [S, N, N] ancestor mask (diagonal included). Node ``n`` attends
    every committed row plus its own root path — K speculated tokens
    verified in ONE target dispatch."""
    helper = LayerHelper("paged_tree_attention", name=name)
    out = helper.create_variable_for_type_inference(query.dtype)
    helper.append_op(
        type="paged_tree_attention",
        inputs={"Q": [query], "KPool": [k_pool], "VPool": [v_pool],
                "PageTable": [page_table], "BaseLens": [base_lens],
                "Anc": [anc]},
        outputs={"Out": [out]},
        attrs={"sm_scale": float(sm_scale or 0.0), "impl": impl,
               "max_length": int(max_length)},
    )
    return out


def paged_spec_kv_write(k_pool, v_pool, k_new, v_new, page_table, pos,
                        name=None):
    """Tree write for the speculative verify step: all N tree nodes'
    K/V rows ``[S, H, N, dh]`` land at storage positions ``pos[s] ..
    pos[s] + N - 1`` through the table (rows past the table's coverage
    trash-route). In-place state convention: binds ``KOut``/``VOut``
    back onto the pool vars."""
    helper = LayerHelper("paged_spec_kv_write", name=name)
    helper.append_op(
        type="paged_spec_kv_write",
        inputs={"KPool": [k_pool], "VPool": [v_pool], "KNew": [k_new],
                "VNew": [v_new], "PageTable": [page_table], "Pos": [pos]},
        outputs={"KOut": [k_pool], "VOut": [v_pool]},
    )
    return k_pool, v_pool


def paged_spec_kv_compact(k_pool, v_pool, page_table, pos, path,
                          accept_len, name=None):
    """Survivor commit of the accepted speculation path: storage row
    ``pos + j`` receives tree node ``path[s, j]``'s K/V row for
    ``1 <= j < accept_len[s]`` — rejected branches stay behind past the
    new resident length and are never attended again. In-place state
    convention on the pool vars."""
    helper = LayerHelper("paged_spec_kv_compact", name=name)
    helper.append_op(
        type="paged_spec_kv_compact",
        inputs={"KPool": [k_pool], "VPool": [v_pool],
                "PageTable": [page_table], "Pos": [pos], "Path": [path],
                "AcceptLen": [accept_len]},
        outputs={"KOut": [k_pool], "VOut": [v_pool]},
    )
    return k_pool, v_pool


def grouped_cross_attention(query, k_pool, v_pool, group_of, mask,
                            sm_scale=None, impl="auto", name=None,
                            live=None):
    """Group-indexed cross attention for the paged decode step.

    ``query`` [S, H, N, dh] (N = 1 in the step program, the tree's
    nodes in tree verify); ``k_pool``/``v_pool`` [G, H, T_src, dh] —
    one cross K/V row per admitted SOURCE, not per slot; ``group_of``
    [S, 1] (or [S]) int group ids; ``mask`` [G, T_src] validity rows,
    prefix-valid (``sequence_mask`` rows: the kernel reads a row as
    its count of valid positions). Each slot attends over its group's
    row; on a TPU the decode kernel reads that row in place by index
    (kernels/cross_attention_decode.py), so N slots decoding
    continuations of one source cost one group's HBM instead of N
    dense rows. The reference path gathers the rows. ``live`` [S, 1]
    (optional) is nonzero where the slot holds a stream: a dead slot
    reads and multiplies nothing and its rows come back exactly 0."""
    helper = LayerHelper("grouped_cross_attention", name=name)
    out = helper.create_variable_for_type_inference(query.dtype)
    inputs = {"Q": [query], "KPool": [k_pool], "VPool": [v_pool],
              "GroupOf": [group_of], "Mask": [mask]}
    if live is not None:
        inputs["Live"] = [live]
    helper.append_op(
        type="grouped_cross_attention",
        inputs=inputs,
        outputs={"Out": [out]},
        attrs={"sm_scale": float(sm_scale or 0.0), "impl": impl},
    )
    return out


def slot_decode_sample(logits, pos, done=None, strategy="greedy",
                       temperature=1.0, top_k=0, base_seed=0, eos_id=2,
                       max_length=0, name=None):
    """Per-slot token selection + slot lifecycle step for the decode
    loop: sample (greedy / temperature / top-k; PRNG keyed on
    ``(base_seed, slot, position)`` so seeded replays are bit-identical
    at any dispatch granularity), force eos on finished slots, advance
    positions with the max-length clamp, latch the done flag. Returns
    ``(token [S, 1], new_pos [S, 1], new_done [S, 1])``.
    ``max_length`` is the decode budget (the slot pool's ``T``) and is
    REQUIRED: the position clamp is ``min(pos + 1, max_length - 1)``,
    so an unset budget would pin every slot to position -1."""
    if int(max_length) < 2:
        raise ValueError(
            "slot_decode_sample needs max_length >= 2 (the decode "
            "budget; positions clamp to max_length - 1), got %r"
            % (max_length,))
    if strategy == "top_k" and int(top_k) < 1:
        raise ValueError(
            "slot_decode_sample strategy 'top_k' needs top_k >= 1 — "
            "0 would silently sample the full vocabulary")
    helper = LayerHelper("slot_decode_sample", name=name)
    tok = helper.create_variable_for_type_inference("int64")
    new_pos = helper.create_variable_for_type_inference("int64")
    new_done = helper.create_variable_for_type_inference("int64")
    inputs = {"Logits": [logits], "Pos": [pos]}
    if done is not None:
        inputs["Done"] = [done]
    helper.append_op(
        type="slot_decode_sample",
        inputs=inputs,
        outputs={"Out": [tok], "PosOut": [new_pos], "DoneOut": [new_done]},
        attrs={"strategy": strategy, "temperature": float(temperature),
               "top_k": int(top_k), "base_seed": int(base_seed),
               "eos_id": int(eos_id), "max_length": int(max_length)},
    )
    return tok, new_pos, new_done


def slot_beam_search(logits, tok, pos, done, score, beam_width,
                     eos_id=2, max_length=0, name=None):
    """Batched beam selection + parent gather over the slot pool
    (``ops/beam_search_ops.py`` ``slot_beam_search``): the ``S = B*K``
    slots are K-wide beam LANES; one ``lax.top_k`` lattice per lane
    selects survivors, and each survivor adopts its parent's
    position/done state in-graph — the session gathers the page-table
    rows by the returned GLOBAL parent indices, so a hypothesis reorder
    moves table rows and refcounts, never KV bytes. Returns ``(token,
    new_pos, new_done, new_score, parent)`` — all ``[S, 1]``."""
    if int(beam_width) < 2:
        raise ValueError(
            "slot_beam_search needs beam_width >= 2 (width 1 is "
            "slot_decode_sample's job), got %r" % (beam_width,))
    if int(max_length) < 2:
        raise ValueError(
            "slot_beam_search needs max_length >= 2 (the decode "
            "budget), got %r" % (max_length,))
    helper = LayerHelper("slot_beam_search", name=name)
    tok_out = helper.create_variable_for_type_inference("int64")
    new_pos = helper.create_variable_for_type_inference("int64")
    new_done = helper.create_variable_for_type_inference("int64")
    new_score = helper.create_variable_for_type_inference("float32")
    parent = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="slot_beam_search",
        inputs={"Logits": [logits], "Tok": [tok], "Pos": [pos],
                "Done": [done], "Score": [score]},
        outputs={"Out": [tok_out], "PosOut": [new_pos],
                 "DoneOut": [new_done], "ScoreOut": [new_score],
                 "ParentOut": [parent]},
        attrs={"beam_width": int(beam_width), "eos_id": int(eos_id),
               "max_length": int(max_length)},
    )
    return tok_out, new_pos, new_done, new_score, parent


def slot_speculative_accept(logits, nodes, parent, pos, done,
                            strategy="greedy", temperature=1.0, top_k=0,
                            base_seed=0, eos_id=2, max_length=0,
                            name=None):
    """In-graph accept/reject walk for speculative decoding
    (``ops/speculative_ops.py``): replay the sequential sampling rule
    down the speculation tree — same token-choice core and
    ``(base_seed, slot, position)`` PRNG keys as ``slot_decode_sample``,
    same ``slot_lifecycle_advance`` formula — and commit the longest
    draft prefix the target itself would emit, plus one correction or
    bonus token. ``logits`` [S, N, V]; ``nodes``/``parent`` [S, N];
    returns ``(anchor_tok [S,1], tok_seq [S,N], accept_len [S,1],
    path [S,N], new_pos [S,1], new_done [S,1])``."""
    if int(max_length) < 2:
        raise ValueError(
            "slot_speculative_accept needs max_length >= 2 (the decode "
            "budget), got %r" % (max_length,))
    if strategy == "top_k" and int(top_k) < 1:
        raise ValueError(
            "slot_speculative_accept strategy 'top_k' needs top_k >= 1 "
            "— 0 would silently sample the full vocabulary")
    helper = LayerHelper("slot_speculative_accept", name=name)
    anchor = helper.create_variable_for_type_inference("int64")
    tok_seq = helper.create_variable_for_type_inference("int64")
    accept_len = helper.create_variable_for_type_inference("int64")
    path = helper.create_variable_for_type_inference("int64")
    new_pos = helper.create_variable_for_type_inference("int64")
    new_done = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="slot_speculative_accept",
        inputs={"Logits": [logits], "Nodes": [nodes], "Parent": [parent],
                "Pos": [pos], "Done": [done]},
        outputs={"Out": [anchor], "TokSeq": [tok_seq],
                 "AcceptLen": [accept_len], "Path": [path],
                 "PosOut": [new_pos], "DoneOut": [new_done]},
        attrs={"strategy": strategy, "temperature": float(temperature),
               "top_k": int(top_k), "base_seed": int(base_seed),
               "eos_id": int(eos_id), "max_length": int(max_length)},
    )
    return anchor, tok_seq, accept_len, path, new_pos, new_done


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    helper = LayerHelper("label_smooth", name=name)
    out = helper.create_variable_for_type_inference(label.dtype)
    inputs = {"X": [label]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist]
    helper.append_op(
        type="label_smooth",
        inputs=inputs,
        outputs={"Out": [out]},
        attrs={"epsilon": float(epsilon)},
    )
    return out


def add_position_encoding(input, alpha=1.0, beta=1.0, name=None):
    helper = LayerHelper("add_position_encoding", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="add_position_encoding",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"alpha": float(alpha), "beta": float(beta)},
    )
    return out


def moe_ffn(
    x,
    num_experts,
    d_hidden,
    top_k=1,
    capacity_factor=1.25,
    act="gelu",
    mask=None,
    param_attr=None,
    name=None,
):
    """Mixture-of-Experts feed-forward block (Switch-Transformer style;
    ops/moe_ops.py). x: [batch, seq, d_model]; returns (out, aux_loss) —
    add ``aux_loss`` (scaled, typically by 1e-2) to the training loss to
    balance expert load. ``mask`` ([batch, seq] validity, 1 = real
    token) keeps padding out of routing: pads consume no expert
    capacity and are excluded from the load-balancing statistics.

    Expert parallelism: shard the stacked expert parameters on dim 0
    over a mesh axis via ParallelExecutor(sharding_overrides=...); GSPMD
    inserts the token all-to-alls.
    """
    import copy

    from paddle_tpu import initializer
    from paddle_tpu.param_attr import ParamAttr

    helper = LayerHelper("moe_ffn", param_attr=param_attr, name=name)
    d_model = int(x.shape[-1])
    e, h = int(num_experts), int(d_hidden)

    def _slot_attr(suffix):
        # Five distinct parameters: a single user-NAMED ParamAttr would
        # otherwise alias them all (create_parameter returns the existing
        # var on name collision), so suffix the name per slot.
        attr = ParamAttr._to_attr(copy.copy(helper.param_attr))
        if getattr(attr, "name", None):
            attr.name = attr.name + "_" + suffix
        return attr

    gate_w = helper.create_parameter(
        attr=_slot_attr("gate"), shape=[d_model, e], dtype=x.dtype)
    w1 = helper.create_parameter(
        attr=_slot_attr("w1"), shape=[e, d_model, h], dtype=x.dtype)
    b1 = helper.create_parameter(
        attr=_slot_attr("b1"), shape=[e, h], dtype=x.dtype,
        default_initializer=initializer.Constant(0.0))
    w2 = helper.create_parameter(
        attr=_slot_attr("w2"), shape=[e, h, d_model], dtype=x.dtype)
    b2 = helper.create_parameter(
        attr=_slot_attr("b2"), shape=[e, d_model], dtype=x.dtype,
        default_initializer=initializer.Constant(0.0))
    out = helper.create_variable_for_type_inference(x.dtype)
    aux = helper.create_variable_for_type_inference(x.dtype)
    op_inputs = {"X": [x], "GateW": [gate_w], "ExpertW1": [w1],
                 "ExpertB1": [b1], "ExpertW2": [w2], "ExpertB2": [b2]}
    if mask is not None:
        op_inputs["Mask"] = [mask]
    helper.append_op(
        type="moe_ffn",
        inputs=op_inputs,
        outputs={"Out": [out], "AuxLoss": [aux]},
        attrs={"top_k": int(top_k),
               "capacity_factor": float(capacity_factor), "act": act},
    )
    return out, aux
