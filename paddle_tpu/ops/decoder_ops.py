"""Ops of a pre-norm decoder-only block with latent (MLA) attention:
RMSNorm, the gated (SwiGLU) feed-forward, a projection with a float32
result, and the latent attention's four pieces (RoPE and row packing, the
decode and prefill cache writes, absorbed-form decode attention over the
paged row pool, expanded-form prefill attention through the flash kernel).

Every op works on flat token rows ``[N, ...]``: a decode step's ``N`` is
the slot count, a prefill dispatch's is ``prompts x bucket length``. None
has a gradient: the serving path is their only user
(``models/latent_moe_decoder.py``). Products take operands in the
parameters' dtype and accumulate in float32; norms' statistics, RoPE and
softmax run in float32.
"""

import jax
import jax.numpy as jnp

from paddle_tpu.core.op_registry import register_op

_F32 = jnp.float32


def rms_norm(x, scale, eps):
    x32 = x.astype(_F32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * scale.astype(_F32)).astype(x.dtype)


def matmul_f32(x, w):
    """``x @ w`` accumulated (and returned) in float32."""
    return jnp.matmul(x, w, preferred_element_type=_F32)


def gated_ffn(x, w_gate, w_up, w_down):
    """``(silu(x Wg) * (x Wu)) Wd``, the result in ``x``'s dtype."""
    h = jax.nn.silu(matmul_f32(x, w_gate)) * matmul_f32(x, w_up)
    return matmul_f32(h.astype(x.dtype), w_down).astype(x.dtype)


def rope_rotate_half(x, positions, theta):
    """RoPE in the split-halves convention (``rotate_half``): ``x`` is
    ``[N, ..., d]`` with the token axis first, ``positions`` ``[N]``."""
    half = x.shape[-1] // 2
    inv_freq = jnp.power(
        _F32(theta), -jnp.arange(half, dtype=_F32) / half)
    ang = positions.astype(_F32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],)
    x32 = x.astype(_F32)
    rot = jnp.concatenate([-x32[..., half:], x32[..., :half]], -1)
    return (x32 * cos.reshape(shape) + rot * sin.reshape(shape)).astype(
        x.dtype)


def rope_interleaved(x, positions, theta):
    """RoPE over adjacent pairs ``(x[2i], x[2i+1])`` (the complex-number
    convention of the DeepSeek / GLM modelling code, ``rope_interleave``):
    shapes as ``rope_rotate_half``; the result keeps the pairs in place."""
    half = x.shape[-1] // 2
    inv_freq = jnp.power(
        _F32(theta), -jnp.arange(half, dtype=_F32) / half)
    ang = positions.astype(_F32)[:, None] * inv_freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    pairs = x.astype(_F32).reshape(x.shape[:-1] + (half, 2))
    re, im = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([re * cos - im * sin, re * sin + im * cos], -1)
    return out.reshape(x.shape).astype(x.dtype)


def rope(interleave):
    return rope_interleaved if interleave else rope_rotate_half


def _lower_rms_norm(ctx, ins, attrs):
    return {"Out": rms_norm(ins["X"][0], ins["Scale"][0],
                            float(attrs.get("epsilon", 1e-5)))}


register_op(
    "rms_norm", inputs=["X", "Scale"], outputs=["Out"],
    attrs={"epsilon": 1e-5}, lower=_lower_rms_norm, grad=None)


def _lower_gated_ffn(ctx, ins, attrs):
    return {"Out": gated_ffn(ins["X"][0], ins["WGate"][0], ins["WUp"][0],
                             ins["WDown"][0])}


register_op(
    "gated_ffn", inputs=["X", "WGate", "WUp", "WDown"], outputs=["Out"],
    lower=_lower_gated_ffn, grad=None)


def _lower_dense_projection(ctx, ins, attrs):
    out = matmul_f32(ins["X"][0], ins["W"][0])
    if attrs.get("out_dtype", "input") == "input":
        out = out.astype(ins["X"][0].dtype)
    return {"Out": out}


register_op(
    "dense_projection", inputs=["X", "W"], outputs=["Out"],
    attrs={"out_dtype": "input"},  # or "float32": the logits
    lower=_lower_dense_projection, grad=None)


def _lower_latent_rope_rows(ctx, ins, attrs):
    """The query after its up-projection and the compressed key/value
    row, made ready for the cache: per head ``[q_nope | RoPE(q_rope)]``,
    and the cached row ``[RMSNorm(ckv) | RoPE(k_rope)]``. ``q_scale``
    multiplies the whole query and ``kv_scale`` the normed compressed
    part (not the rotary key), both in float32 before the rows are
    rounded; at 1.0 the op lowers to what it lowered to without them.
    ``rotate`` false: no rotation of either rotary part, and no position
    is read."""
    q, kva = ins["Q"][0], ins["KVA"][0]          # [N, H*(dn+dr)], [N, C+R]
    H, dn = int(attrs["heads"]), int(attrs["nope_dim"])
    dr, theta = int(attrs["rope_dim"]), float(attrs["theta"])
    C = kva.shape[-1] - dr
    if not attrs.get("rotate", True):
        # no positional encoding (``mla_use_nope``): the query as it was
        # projected, the row ``[RMSNorm(ckv) | k_pe]``; the rotary lanes
        # stay in the score, unrotated
        return {"QOut": q.reshape(q.shape[0], H, dn + dr),
                "Row": jnp.concatenate(
                    [rms_norm(kva[:, :C], ins["KVNorm"][0],
                              float(attrs.get("epsilon", 1e-5))),
                     kva[:, C:]], -1)}
    if ins.get("Positions"):
        pos = jnp.reshape(ins["Positions"][0], (-1,))
    else:   # prefill: token n of the flat batch stands at n % bucket
        pos = jnp.arange(q.shape[0]) % int(attrs["period"])
    rotate = rope(attrs.get("interleave", False))
    q_scale = float(attrs.get("q_scale", 1.0))
    kv_scale = float(attrs.get("kv_scale", 1.0))
    q = q.reshape(q.shape[0], H, dn + dr)
    if q_scale != 1.0:
        q32 = q.astype(_F32) * q_scale
        q = jnp.concatenate(
            [q32[..., :dn], rotate(q32[..., dn:], pos, theta)],
            -1).astype(q.dtype)
    else:
        q = jnp.concatenate(
            [q[..., :dn], rotate(q[..., dn:], pos, theta)], -1)
    eps = float(attrs.get("epsilon", 1e-5))
    if kv_scale != 1.0:
        ckv = (rms_norm(kva[:, :C].astype(_F32), ins["KVNorm"][0], eps)
               * kv_scale).astype(kva.dtype)
    else:
        ckv = rms_norm(kva[:, :C], ins["KVNorm"][0], eps)
    row = jnp.concatenate(
        [ckv, rotate(kva[:, C:], pos, theta)], -1)
    return {"QOut": q, "Row": row}


register_op(
    "latent_rope_rows", inputs=["Q", "KVA", "KVNorm", "Positions"],
    outputs=["QOut", "Row"],
    attrs={"heads": 1, "nope_dim": 0, "rope_dim": 0, "theta": 10000.0,
           "period": 0, "epsilon": 1e-5, "interleave": False,
           "q_scale": 1.0, "kv_scale": 1.0, "rotate": True},
    lower=_lower_latent_rope_rows, grad=None)


def _lower_latent_row_write(ctx, ins, attrs):
    from paddle_tpu.kernels.latent_attention import latent_row_write

    rows = ins["Rows"][0]
    table = jnp.reshape(ins["PageTable"][0], (rows.shape[0], -1))
    return {"PoolOut": latent_row_write(
        ins["Pool"][0], rows, table, jnp.reshape(ins["Pos"][0], (-1,)))}


register_op(
    "latent_row_write", inputs=["Pool", "Rows", "PageTable", "Pos"],
    outputs=["PoolOut"], lower=_lower_latent_row_write, grad=None)


def _lower_latent_row_prefill(ctx, ins, attrs):
    from paddle_tpu.kernels.latent_attention import latent_row_prefill

    page_rows = ins["PageRows"][0]
    B = page_rows.shape[0]
    rows = ins["Rows"][0]
    return {"PoolOut": latent_row_prefill(
        ins["Pool"][0], rows.reshape(B, -1, rows.shape[-1]), page_rows,
        jnp.reshape(ins["Lens"][0], (-1,)))}


register_op(
    "latent_row_prefill", inputs=["Pool", "Rows", "PageRows", "Lens"],
    outputs=["PoolOut"], lower=_lower_latent_row_prefill, grad=None)


def _split_kv_b(kv_b, heads, nope_dim):
    """``kv_b`` ``[C, H*(dn+dv)]`` -> the key part ``[C, H, dn]`` and the
    value part ``[C, H, dv]``."""
    w = kv_b.reshape(kv_b.shape[0], heads, -1)
    return w[..., :nope_dim], w[..., nope_dim:]


def _lower_latent_paged_attention(ctx, ins, attrs):
    """Decode attention in the absorbed form: the key up-projection goes
    into the query, the kernel attends over the cached rows as they are,
    the value up-projection is applied to its latent output."""
    from paddle_tpu.kernels.latent_attention import latent_paged_attention

    q, kv_b, pool = ins["Q"][0], ins["KVB"][0], ins["Pool"][0]
    S, H = q.shape[0], q.shape[1]
    dn = int(attrs["nope_dim"])
    wk, wv = _split_kv_b(kv_b, H, dn)
    q_lat = jnp.einsum("shn,chn->shc", q[..., :dn], wk,
                       preferred_element_type=_F32).astype(q.dtype)
    table = jnp.reshape(ins["PageTable"][0], (S, -1)).astype(jnp.int32)
    lengths = jnp.reshape(ins["Lengths"][0], (-1,)).astype(jnp.int32)
    o_lat = latent_paged_attention(
        q_lat, q[..., dn:], pool, table, lengths,
        sm_scale=q.shape[-1] ** -0.5)
    out = jnp.einsum("shc,chv->shv", o_lat, wv,
                     preferred_element_type=_F32).astype(q.dtype)
    return {"Out": out.reshape(S, -1)}


register_op(
    "latent_paged_attention",
    inputs=["Q", "KVB", "Pool", "PageTable", "Lengths"], outputs=["Out"],
    attrs={"nope_dim": 0},
    lower=_lower_latent_paged_attention, grad=None)


def _lower_latent_prefill_attention(ctx, ins, attrs):
    """Prefill attention in the expanded form: every row's per-head keys
    and values are made from its compressed part, the rotary key is
    shared by all heads, and causal attention runs through the flash
    kernel at query and key width ``dn + dr`` and the values' own width
    (equal in ``glm4_moe_lite``, 192 beside 128 in the DeepSeek-V3
    shape)."""
    from paddle_tpu.kernels.flash_attention import flash_attention

    q, rows, kv_b = ins["Q"][0], ins["Rows"][0], ins["KVB"][0]
    N, H, dq = q.shape
    B, dn = int(attrs["prompts"]), int(attrs["nope_dim"])
    T = N // B
    C = kv_b.shape[0]
    kv = matmul_f32(rows[:, :C], kv_b).astype(q.dtype).reshape(N, H, -1)
    k_rope = jnp.broadcast_to(rows[:, None, C:], (N, H, dq - dn))
    k = jnp.concatenate([kv[..., :dn], k_rope.astype(q.dtype)], -1)
    v = kv[..., dn:]

    def heads_first(x):
        return jnp.transpose(x.reshape(B, T, H, x.shape[-1]), (0, 2, 1, 3))

    out = flash_attention(
        heads_first(q), heads_first(k), heads_first(v), causal=True,
        sm_scale=dq ** -0.5)
    return {"Out": jnp.transpose(out, (0, 2, 1, 3)).reshape(N, -1)}


register_op(
    "latent_prefill_attention", inputs=["Q", "Rows", "KVB"],
    outputs=["Out"], attrs={"prompts": 1, "nope_dim": 0},
    lower=_lower_latent_prefill_attention, grad=None)


def _lower_slot_rows_write(ctx, ins, attrs):
    state, values = ins["State"][0], ins["Values"][0]
    idx = jnp.reshape(ins["Index"][0], (-1,)).astype(jnp.int32)
    return {"StateOut": state.at[idx].set(
        jnp.reshape(values, (idx.shape[0],) + state.shape[1:]).astype(
            state.dtype), mode="drop")}


register_op(
    "slot_rows_write", inputs=["State", "Index", "Values"],
    outputs=["StateOut"], lower=_lower_slot_rows_write, grad=None)
