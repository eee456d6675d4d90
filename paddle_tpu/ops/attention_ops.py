"""Attention ops.

The reference has no fused attention (SURVEY.md §5.7) — Transformer there
is composed ops (tests/unittests/dist_transformer.py). Here attention is a
first-class op lowered to the Pallas flash kernel on TPU / fused XLA math
elsewhere, because it sets the long-context performance ceiling.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.op_registry import register_op
from paddle_tpu.kernels.flash_attention import (
    flash_attention,
    flash_attention_reference,
    uses_kernel,
)


def _lower_sdpa(ctx, ins, attrs):
    q = ins["Q"][0]  # [B, H, T, d]
    k = ins["K"][0]
    v = ins["V"][0]
    mask = ins.get("Mask", [None])[0]
    sm_scale = attrs.get("sm_scale", 0.0) or None
    causal = attrs.get("causal", False)
    seq_axis = attrs.get("seq_parallel_axis", "")
    if seq_axis:
        # sequence-parallel region inside the program: Q/K/V reshard so
        # the SEQUENCE spans the named mesh axis and K/V blocks rotate on
        # ppermute (parallel/ring_attention.py) — long-context attention
        # whose per-chip memory is O(T / axis_size). Requires the
        # ParallelExecutor compile's mesh (the ambient mesh).
        from paddle_tpu.core.lowering import ambient_mesh
        from paddle_tpu.parallel.ring_attention import ring_attention

        if mask is not None:
            raise ValueError(
                "scaled_dot_product_attention: seq_parallel_axis does not "
                "take an explicit Mask (use causal=)")
        if attrs.get("impl", "auto") != "auto":
            raise ValueError(
                "scaled_dot_product_attention: impl=%r conflicts with "
                "seq_parallel_axis (the ring path IS the implementation)"
                % attrs["impl"])
        if int(attrs.get("kv_group", 1)) != 1:
            raise ValueError(
                "scaled_dot_product_attention: kv_group > 1 is not "
                "supported with seq_parallel_axis yet — repeat K/V to "
                "full heads before the ring")
        if int(attrs.get("window", 0)) != 0:
            raise ValueError(
                "scaled_dot_product_attention: window is not supported "
                "with seq_parallel_axis yet (the ring absorbs whole "
                "blocks)")
        mesh = ambient_mesh()
        if mesh is None or seq_axis not in mesh.shape:
            raise ValueError(
                "scaled_dot_product_attention: seq_parallel_axis=%r needs "
                "a ParallelExecutor mesh containing that axis (got %s)"
                % (seq_axis, None if mesh is None else tuple(mesh.shape)))
        n = mesh.shape[seq_axis]
        if q.shape[2] % n != 0:
            raise ValueError(
                "scaled_dot_product_attention: sequence length %d not "
                "divisible by seq_parallel_axis %r size %d"
                % (q.shape[2], seq_axis, n))
        return ring_attention(
            q, k, v, mesh=mesh, axis_name=seq_axis, causal=causal,
            sm_scale=sm_scale,
        )
    if mask is not None:
        # Mask: [B, T_k] validity (1=keep) or [B, 1|H, T_q, T_k] full mask.
        if mask.ndim == 2:
            mask = mask[:, None, None, :]
        mask = mask.astype(bool)
    impl = attrs.get("impl", "auto")
    if impl == "auto":
        from paddle_tpu import flags

        impl = flags.get("attention_impl")
    # impl == "reference" routes through the same entry with
    # force_reference so the grouped-K/V handling lives in ONE place
    routing = dict(force_reference=(impl == "reference"),
                   force_pallas=(impl == "pallas"))
    attend = functools.partial(
        flash_attention, causal=causal, sm_scale=sm_scale,
        kv_group=int(attrs.get("kv_group", 1)),
        window=int(attrs.get("window", 0)), **routing)
    from paddle_tpu.core.lowering import ambient_mesh

    mesh = ambient_mesh()
    if (mesh is not None and mesh.size > 1
            and uses_kernel(mask, **routing)):
        return _kernel_per_device(mesh, attend, q, k, v, mask)
    return attend(q, k, v, mask=mask)


def _kernel_per_device(mesh, attend, q, k, v, mask):
    """GSPMD cannot partition a Mosaic kernel (the lowering refuses:
    "wrap the call in a shard_map"), so under a ParallelExecutor mesh
    the kernel runs per device on that device's own batch x heads block:
    batch over the mesh's batch axes, heads over its tensor axis, each
    only where it divides evenly (else that dim stays whole on every
    device). Attention has no cross-batch or cross-head term, so the
    body needs no collective and its backward shards the same way."""
    from jax.sharding import PartitionSpec as P

    sizes = dict(mesh.shape)
    batch = tuple(a for a in ("data", "fsdp") if sizes.get(a, 1) > 1)
    if q.shape[0] % int(np.prod([sizes[a] for a in batch] or [1])):
        batch = ()
    heads = next(
        (a for a in ("tp", "model") if sizes.get(a, 1) > 1
         and q.shape[1] % sizes[a] == 0 and k.shape[1] % sizes[a] == 0),
        None)
    qkv = P(batch or None, heads, None, None)
    args, specs = [q, k, v], [qkv, qkv, qkv]
    if mask is not None:
        # a key-validity mask ([B, 1, 1, S] — full masks never reach
        # the kernel) follows the batch
        args.append(mask)
        specs.append(P(batch or None, None, None, None))
    return jax.shard_map(
        lambda q_, k_, v_, *m: attend(q_, k_, v_, mask=m[0] if m else None),
        mesh=mesh, in_specs=tuple(specs), out_specs=qkv,
        check_vma=False)(*args)


register_op(
    "scaled_dot_product_attention",
    inputs=["Q", "K", "V", "Mask"],
    outputs=["Out"],
    attrs={"causal": False, "sm_scale": 0.0, "impl": "auto",
           "seq_parallel_axis": "", "kv_group": 1, "window": 0},
    lower=_lower_sdpa,
    no_grad_inputs=("Mask",),
    # Out mirrors Q's shape/dtype. Declared (not eval_shape'd) because the
    # seq-parallel form needs the PE mesh, which doesn't exist at build
    # time.
    infer_shape=lambda block, op: _sdpa_infer_shape(block, op),
)


def _sdpa_infer_shape(block, op):
    q = block._find_var_recursive(op.input("Q")[0])
    for name in op.output("Out"):
        out = block._find_var_recursive(name)
        if out is not None and q is not None:
            out.shape = list(q.shape) if q.shape is not None else None
            out.dtype = q.dtype


def _lower_paged_attention(ctx, ins, attrs):
    """Ragged paged-attention decode (kernels/paged_attention.py): one
    query token per slot attends over its block-paged KV pages, cost
    bounded by the slot's OWN resident length — the serving decode
    analog of the flash kernel's "[T, S] never materializes" contract."""
    from paddle_tpu.kernels.paged_attention import paged_attention

    q = ins["Q"][0]  # [S, H, 1, dh]
    k_pool = ins["KPool"][0]  # [P, page_size, H * dh]
    v_pool = ins["VPool"][0]
    table = jnp.reshape(ins["PageTable"][0],
                        (q.shape[0], -1)).astype(jnp.int32)
    lengths = jnp.reshape(ins["Lengths"][0], (-1,)).astype(jnp.int32)
    sm_scale = attrs.get("sm_scale", 0.0) or None
    impl = attrs.get("impl", "auto")
    if impl == "auto":
        from paddle_tpu import flags

        impl = flags.get("paged_attention")
    out = paged_attention(
        q[:, :, 0, :], k_pool, v_pool, table, lengths, sm_scale=sm_scale,
        force_reference=(impl == "reference"),
        force_pallas=(impl == "pallas"),
    )
    return out[:, :, None, :]


def _paged_attention_infer_shape(block, op):
    q = block._find_var_recursive(op.input("Q")[0])
    for name in op.output("Out"):
        out = block._find_var_recursive(name)
        if out is not None and q is not None:
            out.shape = list(q.shape) if q.shape is not None else None
            out.dtype = q.dtype


register_op(
    "paged_attention",
    inputs=["Q", "KPool", "VPool", "PageTable", "Lengths"],
    outputs=["Out"],
    attrs={"sm_scale": 0.0, "impl": "auto"},
    lower=_lower_paged_attention,
    grad=None,  # decode-only op: no training path attends paged
    no_grad_inputs=("PageTable", "Lengths"),
    infer_shape=_paged_attention_infer_shape,
)


def _lower_paged_tree_attention(ctx, ins, attrs):
    """Speculative tree-verify attention (kernels/paged_attention.py
    paged_tree_attention): N speculation-tree nodes per slot, laid out
    linearly in the slot's write pages, each attending the committed
    prefix plus its own ancestor path — K speculated tokens verified by
    the target model in ONE dispatch."""
    from paddle_tpu.kernels.paged_attention import paged_tree_attention

    q = ins["Q"][0]  # [S, H, N, dh]
    k_pool = ins["KPool"][0]  # [P, page_size, H * dh]
    v_pool = ins["VPool"][0]
    S, H, N, dh = q.shape
    table = jnp.reshape(ins["PageTable"][0], (S, -1)).astype(jnp.int32)
    base = jnp.reshape(ins["BaseLens"][0], (-1,)).astype(jnp.int32)
    anc = jnp.reshape(ins["Anc"][0], (S, N, N)).astype(jnp.int32)
    sm_scale = attrs.get("sm_scale", 0.0) or None
    max_length = int(attrs.get("max_length", 0)) or None
    impl = attrs.get("impl", "auto")
    if impl == "auto":
        from paddle_tpu import flags

        impl = flags.get("tree_attention")
    return paged_tree_attention(
        q, k_pool, v_pool, table, base, anc, sm_scale=sm_scale,
        max_length=max_length,
        force_reference=(impl == "reference"),
        force_pallas=(impl == "pallas"),
    )


register_op(
    "paged_tree_attention",
    inputs=["Q", "KPool", "VPool", "PageTable", "BaseLens", "Anc"],
    outputs=["Out"],
    attrs={"sm_scale": 0.0, "impl": "auto", "max_length": 0},
    lower=_lower_paged_tree_attention,
    grad=None,  # decode-only op: no training path attends speculation
    no_grad_inputs=("PageTable", "BaseLens", "Anc"),
    infer_shape=_paged_attention_infer_shape,
)


def _lower_grouped_cross_attention(ctx, ins, attrs):
    """Group-indexed cross attention for the paged decode step: the
    cross K/V pools are laid out per GROUP (``[G, H, T_src, dh]`` — one
    row per admitted source, however many slots decode continuations of
    it) and each slot reaches its group's row through ``group_of[s]``.
    On a TPU target the decode kernel
    (kernels/cross_attention_decode.py) resolves ``group_of[s]`` in its
    K/V index maps, so NO per-slot copy of a row exists and N best-of-N
    slots cost one group's HBM; ``Mask`` rows are prefix-valid
    (``sequence_mask``) and reach the kernel as a length a group. The
    optional ``Live`` ([S, 1], nonzero where the slot holds a stream)
    makes a dead slot's length 0: no copy, no product, a result of
    exactly 0. The composed reference (``attention_impl`` =
    ``reference``, and every non-TPU target) gathers the rows — a real
    copy there."""
    from paddle_tpu.kernels.cross_attention_decode import (
        grouped_cross_attention)

    impl = attrs.get("impl", "auto")
    if impl == "auto":
        from paddle_tpu import flags

        impl = flags.get("attention_impl")
    return grouped_cross_attention(
        ins["Q"][0],  # [S, H, N, dh]
        ins["KPool"][0], ins["VPool"][0],  # [G, H, T_src, dh]
        ins["GroupOf"][0],  # [S] or [S, 1]
        ins["Mask"][0],  # [G, T_src] validity rows
        sm_scale=attrs.get("sm_scale", 0.0) or None,
        force_reference=(impl == "reference"),
        force_pallas=(impl == "pallas"),
        live=ins.get("Live", [None])[0],  # [S, 1] or absent
    )


register_op(
    "grouped_cross_attention",
    inputs=["Q", "KPool", "VPool", "GroupOf", "Mask", "Live"],
    outputs=["Out"],
    attrs={"sm_scale": 0.0, "impl": "auto"},
    lower=_lower_grouped_cross_attention,
    grad=None,  # decode-only op: no training path attends grouped
    no_grad_inputs=("GroupOf", "Mask", "Live"),
    infer_shape=_paged_attention_infer_shape,
)


def _lower_paged_copy_page(ctx, ins, attrs):
    """On-device page copy — the copy half of copy-on-write: duplicate
    K and V pages (``pool[dst] = pool[src]``) so a forked slot whose
    write position enters a SHARED page (refcount > 1) gets a private
    bit-identical copy before its table row repoints. ``Src`` / ``Dst``
    hold one pair or a whole window's ``[n]``: every source page is READ
    (one gather) before any destination is written (one scatter), which
    equals copying the pairs in order whenever no destination is another
    pair's source or destination and a repeated pair copies a page onto
    itself — what ``SlotDecodeSession._dispatch_cow`` holds its windows
    to. Both pools move in one op so a COW is one fused dispatch per
    layer, not two."""
    src = jnp.reshape(ins["Src"][0], (-1,)).astype(jnp.int32)  # [n]
    dst = jnp.reshape(ins["Dst"][0], (-1,)).astype(jnp.int32)

    def copy(pool):  # [P, page_size, H * dh]
        return pool.at[dst].set(pool[src])

    return {"KOut": copy(ins["KPool"][0]), "VOut": copy(ins["VPool"][0])}


def _paged_copy_page_infer_shape(block, op):
    # KOut / VOut are their pools' shapes: declared, so that appending the
    # op traces nothing to learn them
    for pool_slot, out_slot in (("KPool", "KOut"), ("VPool", "VOut")):
        pool = block._find_var_recursive(op.input(pool_slot)[0])
        out = block._find_var_recursive(op.output(out_slot)[0])
        out.shape, out.dtype = pool.shape, pool.dtype


register_op(
    "paged_copy_page",
    inputs=["KPool", "VPool", "Src", "Dst"],
    outputs=["KOut", "VOut"],
    lower=_lower_paged_copy_page,
    grad=None,
    no_grad_inputs=("Src", "Dst"),
    infer_shape=_paged_copy_page_infer_shape,
)


def _lower_paged_kv_prefill(ctx, ins, attrs):
    """Chunked-prefill KV scatter: land a whole forced prefix's per-layer
    K/V rows (``[1, H, T, dh]``, computed by ONE decoder forward) into
    the slot's pages in one op, instead of one ``paged_kv_write`` per
    token. Position ``p`` goes to ``(page_row[p // page_size],
    p % page_size)`` when ``write_from <= p < len - 1`` — positions a
    prefix-cache hit already covers (below ``write_from``) and pad/tail
    positions route to the trash page (page 0), so a hit prefills ONLY
    the uncached suffix and cached page bits are never touched."""
    from paddle_tpu.kernels.paged_attention import token_rows

    k_pool = ins["KPool"][0]  # [P, page_size, H * dh]
    v_pool = ins["VPool"][0]
    k_new = ins["KNew"][0]  # [1, H, T, dh]
    v_new = ins["VNew"][0]
    row = jnp.reshape(ins["PageRow"][0], (-1,)).astype(jnp.int32)  # [npp]
    wf = jnp.reshape(ins["WriteFrom"][0], ()).astype(jnp.int32)
    ln = jnp.reshape(ins["Len"][0], ()).astype(jnp.int32)
    ps = k_pool.shape[1]
    T = k_new.shape[2]
    p = jnp.arange(T, dtype=jnp.int32)
    live = (p >= wf) & (p < ln - 1)
    pages = jnp.where(live, row[p // ps], 0)
    offs = p % ps

    def rows(x, dtype):  # [1, H, T, dh] -> the pool's [T, H * dh]
        return token_rows(jnp.transpose(x[0], (1, 0, 2)), dtype)

    return {
        "KOut": k_pool.at[pages, offs].set(rows(k_new, k_pool.dtype)),
        "VOut": v_pool.at[pages, offs].set(rows(v_new, v_pool.dtype)),
    }


register_op(
    "paged_kv_prefill",
    inputs=["KPool", "VPool", "KNew", "VNew", "PageRow", "WriteFrom",
            "Len"],
    outputs=["KOut", "VOut"],
    lower=_lower_paged_kv_prefill,
    grad=None,
    no_grad_inputs=("PageRow", "WriteFrom", "Len"),
)


def _lower_paged_kv_write(ctx, ins, attrs):
    """O(page) KV-cache write: each slot's new K/V row lands at
    (table[s, pos // page_size], pos % page_size) — replaces the dense
    slot pool's one-hot select-and-add over the whole T axis."""
    from paddle_tpu.kernels.paged_attention import paged_kv_write

    k_pool = ins["KPool"][0]
    v_pool = ins["VPool"][0]
    k_new = ins["KNew"][0]  # [S, H, 1, dh]
    v_new = ins["VNew"][0]
    pos = jnp.reshape(ins["Pos"][0], (-1,))
    table = jnp.reshape(ins["PageTable"][0],
                        (k_new.shape[0], -1)).astype(jnp.int32)
    k_out, v_out = paged_kv_write(
        k_pool, v_pool, k_new[:, :, 0, :], v_new[:, :, 0, :], table, pos)
    return {"KOut": k_out, "VOut": v_out}


register_op(
    "paged_kv_write",
    inputs=["KPool", "VPool", "KNew", "VNew", "PageTable", "Pos"],
    outputs=["KOut", "VOut"],
    lower=_lower_paged_kv_write,
    grad=None,
    no_grad_inputs=("PageTable", "Pos"),
)


def _lower_label_smooth(ctx, ins, attrs):
    x = ins["X"][0]
    eps = attrs.get("epsilon", 0.0)
    dist = ins.get("PriorDist", [None])[0]
    if dist is not None:
        return (1.0 - eps) * x + eps * dist
    k = jnp.shape(x)[-1]
    return (1.0 - eps) * x + eps / k


register_op(
    "label_smooth",
    inputs=["X", "PriorDist"],
    outputs=["Out"],
    attrs={"epsilon": 0.0},
    lower=_lower_label_smooth,
)


def _lower_position_encoding(ctx, ins, attrs):
    """Sinusoid position table added to the input [B, T, D]."""
    x = ins["X"][0]
    T, D = jnp.shape(x)[1], jnp.shape(x)[2]
    pos = jnp.arange(T, dtype=jnp.float32)[:, None]
    i = jnp.arange(D // 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, 2.0 * i / D)
    table = jnp.concatenate(
        [jnp.sin(angle), jnp.cos(angle)], axis=-1
    ).astype(x.dtype)
    alpha = attrs.get("alpha", 1.0)
    beta = attrs.get("beta", 1.0)
    return alpha * x + beta * table[None, :, :]


register_op(
    "add_position_encoding",
    inputs=["X"],
    outputs=["Out"],
    attrs={"alpha": 1.0, "beta": 1.0},
    lower=_lower_position_encoding,
)


def _lower_rotary_embedding(ctx, ins, attrs):
    """Rotary position embedding (RoPE, rotate-half convention) applied
    to [B, H, T, d] queries/keys; beyond the reference (its models
    predate RoPE) — the relative-position encoding modern attention
    stacks expect. Optional Position input: [1] int offset (KV-cached
    decoding feeds the current step), else positions are 0..T-1."""
    q = ins["Q"][0]
    k = ins["K"][0]
    base = float(attrs.get("base", 10000.0))
    d = q.shape[-1]
    if d % 2 != 0:
        raise ValueError(
            "rotary_embedding needs an even head_dim (rotate-half "
            "pairs dimensions); got %d" % d)
    half = d // 2
    pos_in = ins.get("Position", [None])[0]
    offset = (jnp.reshape(pos_in, ()).astype(jnp.float32)
              if pos_in is not None else jnp.asarray(0.0, jnp.float32))
    inv_freq = jnp.power(
        base, -jnp.arange(0, half, dtype=jnp.float32) / half)

    def rotate(x):
        t = x.shape[2]
        pos = offset + jnp.arange(t, dtype=jnp.float32)
        ang = pos[:, None] * inv_freq[None, :]  # [T, half]
        cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
        sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
        x1, x2 = x[..., :half], x[..., half:]
        rotated = jnp.concatenate([-x2, x1], -1)
        return (x.astype(jnp.float32) * cos[None, None]
                + rotated.astype(jnp.float32) * sin[None, None]
                ).astype(x.dtype)

    return {"QOut": rotate(q), "KOut": rotate(k)}


register_op(
    "rotary_embedding",
    inputs=["Q", "K", "Position"],
    outputs=["QOut", "KOut"],
    attrs={"base": 10000.0},
    lower=_lower_rotary_embedding,
    no_grad_inputs=("Position",),
)
