"""Ops of learned sparse attention over the latent page pool
(``kernels/sparse_latent_attention.py``): the indexer's rows, the choice
of positions in decode (as positions) and in prefill (as a mask), and
latent attention over the chosen positions alone. They sit beside the
dense latent ops of ``decoder_ops.py`` and, like them, work on flat token
rows and have no gradient (``models/latent_moe_decoder.py`` is their only
user). A layer with an indexer of its own makes the choice; the layers
behind it that share it take the same variable.
"""

import jax
import jax.numpy as jnp

from paddle_tpu.core.op_registry import register_op
from paddle_tpu.ops.decoder_ops import _split_kv_b, matmul_f32, rope

_F32 = jnp.float32


def layer_norm(x, scale, shift, eps):
    x32 = x.astype(_F32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps) * scale.astype(_F32)
            + shift.astype(_F32)).astype(x.dtype)


def _lower_indexer_rows(ctx, ins, attrs):
    """The indexer's three rows a token: its heads' queries from the
    compressed query, its ONE key (LayerNorm, the cached row of the
    narrow pool) and its heads' weights from the block's normed input;
    RoPE on the first ``rope_dim`` columns of queries and key."""
    cq, x = ins["CQ"][0], ins["X"][0]
    J, dr = int(attrs["heads"]), int(attrs["rope_dim"])
    theta = float(attrs["theta"])
    if ins.get("Positions"):
        pos = jnp.reshape(ins["Positions"][0], (-1,))
    else:
        pos = jnp.arange(x.shape[0]) % int(attrs["period"])
    rotate = rope(attrs.get("interleave", True))
    q = matmul_f32(cq, ins["WQ"][0]).astype(x.dtype)
    q = q.reshape(q.shape[0], J, -1)
    q = jnp.concatenate([rotate(q[..., :dr], pos, theta), q[..., dr:]], -1)
    k = layer_norm(matmul_f32(x, ins["WK"][0]).astype(x.dtype),
                   ins["KScale"][0], ins["KShift"][0],
                   float(attrs.get("epsilon", 1e-6)))
    k = jnp.concatenate([rotate(k[:, :dr], pos, theta), k[:, dr:]], -1)
    w = matmul_f32(x, ins["WW"][0]) * (J ** -0.5 * q.shape[-1] ** -0.5)
    return {"Q": q, "K": k, "W": w}


register_op(
    "indexer_rows",
    inputs=["CQ", "X", "WQ", "WK", "KScale", "KShift", "WW", "Positions"],
    outputs=["Q", "K", "W"],
    attrs={"heads": 1, "rope_dim": 0, "theta": 10000.0, "period": 0,
           "interleave": True, "epsilon": 1e-6},
    lower=_lower_indexer_rows, grad=None)


def _lower_index_select_decode(ctx, ins, attrs):
    from paddle_tpu.kernels.sparse_latent_attention import (
        index_score_decode,
        index_select,
    )

    q = ins["Q"][0]
    S = q.shape[0]
    table = jnp.reshape(ins["PageTable"][0], (S, -1)).astype(jnp.int32)
    lengths = jnp.reshape(ins["Lengths"][0], (-1,)).astype(jnp.int32)
    scores = index_score_decode(q, ins["W"][0], ins["Pool"][0], table,
                                lengths)
    with jax.named_scope("index_select"):
        return {"Selected": index_select(scores, int(attrs["top_k"]))}


register_op(
    "index_select_decode",
    inputs=["Q", "W", "Pool", "PageTable", "Lengths"], outputs=["Selected"],
    attrs={"top_k": 1}, lower=_lower_index_select_decode, grad=None)


def _lower_sparse_latent_paged_attention(ctx, ins, attrs):
    """``latent_paged_attention`` over the ``Selected`` positions alone."""
    from paddle_tpu.kernels.sparse_latent_attention import (
        sparse_latent_decode_attention,
    )

    q, kv_b = ins["Q"][0], ins["KVB"][0]
    S, H = q.shape[0], q.shape[1]
    dn = int(attrs["nope_dim"])
    wk, wv = _split_kv_b(kv_b, H, dn)
    q_lat = jnp.einsum("shn,chn->shc", q[..., :dn], wk,
                       preferred_element_type=_F32).astype(q.dtype)
    table = jnp.reshape(ins["PageTable"][0], (S, -1)).astype(jnp.int32)
    o_lat = sparse_latent_decode_attention(
        q_lat, q[..., dn:], ins["Pool"][0], table, ins["Selected"][0],
        sm_scale=q.shape[-1] ** -0.5)
    out = jnp.einsum("shc,chv->shv", o_lat, wv,
                     preferred_element_type=_F32).astype(q.dtype)
    return {"Out": out.reshape(S, -1)}


register_op(
    "sparse_latent_paged_attention",
    inputs=["Q", "KVB", "Pool", "PageTable", "Selected"], outputs=["Out"],
    attrs={"nope_dim": 0},
    lower=_lower_sparse_latent_paged_attention, grad=None)


def _lower_index_select_prefill(ctx, ins, attrs):
    from paddle_tpu.kernels.sparse_latent_attention import (
        index_select_prefill,
    )

    q, k, w = ins["Q"][0], ins["K"][0], ins["W"][0]
    B = int(attrs["prompts"])
    T = q.shape[0] // B
    lens = jnp.reshape(ins["Lens"][0], (-1,)).astype(jnp.int32)
    with jax.named_scope("index_select_prefill"):
        return {"Mask": index_select_prefill(
            q.reshape((B, T) + q.shape[1:]), w.reshape(B, T, -1),
            k.reshape(B, T, -1), int(attrs["top_k"]), lengths=lens)}


register_op(
    "index_select_prefill", inputs=["Q", "K", "W", "Lens"], outputs=["Mask"],
    attrs={"prompts": 1, "top_k": 1},
    lower=_lower_index_select_prefill, grad=None)


def _lower_sparse_latent_prefill_attention(ctx, ins, attrs):
    """Prefill attention in the expanded form under the choice's mask
    (none: plainly causal), on token rows with the heads side by side:
    the keys' no-position part and the values are two products of the
    cached rows' compressed part with the two halves of ``kv_b`` (rounded
    once, from the float32 accumulator, as every projection here), so no
    ``[N, H * (dn + dv)]`` array is made and cut and nothing is
    transposed. ``Lens`` [B]: the prompts' lengths, past which a bucket's
    rows are padding that the kernel skips."""
    from paddle_tpu.kernels.sparse_latent_attention import (
        sparse_latent_prefill_attention,
    )

    q, rows, kv_b = ins["Q"][0], ins["Rows"][0], ins["KVB"][0]
    N, H, dq = q.shape
    B, dn = int(attrs["prompts"]), int(attrs["nope_dim"])
    C = kv_b.shape[0]
    wk, wv = _split_kv_b(kv_b, H, dn)
    ckv = rows[:, :C]
    k_nope = jnp.matmul(ckv, wk.reshape(C, -1),
                        preferred_element_type=q.dtype).reshape(N, H, dn)
    k_rope = jnp.broadcast_to(rows[:, None, C:], (N, H, dq - dn))
    k = jnp.concatenate([k_nope, k_rope.astype(q.dtype)], -1)
    v = jnp.matmul(ckv, wv.reshape(C, -1), preferred_element_type=q.dtype)
    mask = ins["Mask"][0] if ins.get("Mask") else None
    lens = jnp.reshape(ins["Lens"][0], (-1,)).astype(jnp.int32)
    out = sparse_latent_prefill_attention(
        q.reshape(B, N // B, -1), k.reshape(B, N // B, -1),
        v.reshape(B, N // B, -1), mask, sm_scale=dq ** -0.5, heads=H,
        lengths=lens)
    return {"Out": out.reshape(N, -1)}


register_op(
    "sparse_latent_prefill_attention",
    inputs=["Q", "Rows", "KVB", "Lens", "Mask"], outputs=["Out"],
    attrs={"prompts": 1, "nope_dim": 0},
    lower=_lower_sparse_latent_prefill_attention, grad=None)
