"""Ops of a hybrid decoder-only block for SERVING: the selective
state-space mixer (Mamba-1: causal depthwise convolution, selective scan)
in its prefill and one-token forms over per-slot recurrent state, and
grouped-query attention over paged K/V row pools.

Like ``decoder_ops.py``, every op works on flat token rows ``[N, ...]``
(a decode step's ``N`` is the slot count, a prefill dispatch's is
``prompts x bucket length``, one prompt a bucket row) and none has a
gradient. The recurrent state ``[slots, d_state, d_inner]`` is float32
whatever the parameters' dtype, as are ``Delta``, ``exp(Delta A)``,
``B`` and ``C``; the convolution's window ``[d_conv - 1, slots, d_inner]``
is in the activations' dtype. ``kernels/selective_scan.py`` says why the
channel axis is the minor one.
"""

import jax
import jax.numpy as jnp

from paddle_tpu.core.op_registry import register_op
from paddle_tpu.ops.decoder_ops import matmul_f32, rms_norm

_F32 = jnp.float32


def _flat(x):
    return jnp.reshape(x, (-1,))


def _a_matrix(a_log):
    return -jnp.exp(a_log.astype(_F32))                     # [n, d]


def _gate(y, z):
    """``y * silu(z)`` in float32, rounded once."""
    z32 = z.astype(_F32)
    return (y.astype(_F32) * z32 * jax.nn.sigmoid(z32)).astype(z.dtype)


def _lower_ssm_causal_conv(ctx, ins, attrs):
    """The convolution over a prefill dispatch's prompts, and each
    prompt's window: its LAST ``d_conv - 1`` real inputs (zeros where the
    prompt is shorter), whatever the bucket was padded with."""
    from paddle_tpu.kernels.selective_scan import causal_conv

    x, w = ins["X"][0], ins["W"][0]
    lens = _flat(ins["Lens"][0]).astype(jnp.int32)
    B, d, k1 = lens.shape[0], x.shape[-1], w.shape[0] - 1
    xb = x.reshape(B, -1, d)
    T = xb.shape[1]
    at = lens[None, :] - k1 + jnp.arange(k1)[:, None]       # [k1, B]
    rows = xb[jnp.arange(B)[None, :], jnp.clip(at, 0, T - 1)]
    window = jnp.where((at >= 0)[:, :, None], rows, 0).astype(x.dtype)
    return {"Out": causal_conv(xb, w, ins["Bias"][0]).reshape(-1, d),
            "Window": window}


register_op(
    "ssm_causal_conv", inputs=["X", "W", "Bias", "Lens"],
    outputs=["Out", "Window"], lower=_lower_ssm_causal_conv, grad=None)


def _lower_ssm_delta_b_c(ctx, ins, attrs):
    """``x_proj``'s output ``[dt | B | C]`` through the three inner norms,
    ``Delta = softplus(dt_proj(dt) + b_dt)``; all three in float32."""
    u = ins["X"][0]
    r, n = int(attrs["dt_rank"]), int(attrs["d_state"])
    eps = float(attrs["epsilon"])
    w = ins["DtProj"][0]
    dt = rms_norm(u[:, :r], ins["DtNorm"][0], eps)
    b = rms_norm(u[:, r:r + n], ins["BNorm"][0], eps)
    c = rms_norm(u[:, r + n:r + 2 * n], ins["CNorm"][0], eps)
    delta = jax.nn.softplus(matmul_f32(dt.astype(w.dtype), w)
                            + ins["DtBias"][0].astype(_F32))
    return {"Delta": delta, "B": b.astype(_F32), "C": c.astype(_F32)}


register_op(
    "ssm_delta_b_c",
    inputs=["X", "DtNorm", "BNorm", "CNorm", "DtProj", "DtBias"],
    outputs=["Delta", "B", "C"],
    attrs={"dt_rank": 1, "d_state": 1, "epsilon": 1e-6},
    lower=_lower_ssm_delta_b_c, grad=None)


def _lower_ssm_prefill_scan(ctx, ins, attrs):
    """The scan over a prefill dispatch's prompts: ``y * silu(z)`` for
    every token and each prompt's state after its last real token."""
    from paddle_tpu.kernels.selective_scan import prefill_scan

    x, delta = ins["X"][0], ins["Delta"][0]
    lens = _flat(ins["Lens"][0]).astype(jnp.int32)
    B, d = lens.shape[0], x.shape[-1]

    def columns(m):                                         # [B, n, T]
        return jnp.transpose(m.reshape(B, -1, m.shape[-1]), (0, 2, 1))

    y, state = prefill_scan(
        x.reshape(B, -1, d), delta.reshape(B, -1, d), columns(ins["B"][0]),
        columns(ins["C"][0]), _a_matrix(ins["ALog"][0]), ins["DSkip"][0],
        lens)
    return {"Out": _gate(y.reshape(-1, d), ins["Gate"][0]), "State": state}


register_op(
    "ssm_prefill_scan",
    inputs=["X", "Delta", "B", "C", "ALog", "DSkip", "Gate", "Lens"],
    outputs=["Out", "State"], lower=_lower_ssm_prefill_scan, grad=None)


def _lower_ssm_conv_step(ctx, ins, attrs):
    from paddle_tpu.kernels.selective_scan import conv_step

    y, window = conv_step(ins["Window"][0], ins["X"][0], ins["W"][0],
                          ins["Bias"][0], _flat(ins["Live"][0]))
    return {"Out": y, "WindowOut": window}


register_op(
    "ssm_conv_step", inputs=["Window", "X", "W", "Bias", "Live"],
    outputs=["Out", "WindowOut"], lower=_lower_ssm_conv_step, grad=None)


def _lower_ssm_state_update(ctx, ins, attrs):
    from paddle_tpu.kernels.selective_scan import state_update

    y, state = state_update(
        ins["State"][0], ins["X"][0], ins["Delta"][0], ins["B"][0],
        ins["C"][0], _a_matrix(ins["ALog"][0]), ins["DSkip"][0],
        _flat(ins["Live"][0]))
    return {"Out": _gate(y, ins["Gate"][0]), "StateOut": state}


register_op(
    "ssm_state_update",
    inputs=["State", "X", "Delta", "B", "C", "ALog", "DSkip", "Gate",
            "Live"],
    outputs=["Out", "StateOut"], lower=_lower_ssm_state_update, grad=None)


def _lower_slot_state_write(ctx, ins, attrs):
    """``state[..., index[b], ...] = values[..., b, ...]`` along ``axis``;
    an index past the last slot (a prefill batch's padding) writes
    nothing."""
    state, values = ins["State"][0], ins["Values"][0]
    idx = _flat(ins["Index"][0]).astype(jnp.int32)
    axis = int(attrs["axis"])
    # the axes before the slots' are indexed too (one scatter of whole
    # minor rows): a slice there makes the compiler transpose the state
    # to bring the slot axis first, a whole copy of it around the write
    lead = [jnp.arange(n).reshape((-1,) + (1,) * (axis - i))
            for i, n in enumerate(state.shape[:axis])]
    at = tuple(lead) + (idx.reshape((1,) * axis + (-1,)),)
    return {"StateOut": state.at[at].set(values.astype(state.dtype),
                                         mode="drop")}


register_op(
    "slot_state_write", inputs=["State", "Index", "Values"],
    outputs=["StateOut"], attrs={"axis": 0},
    lower=_lower_slot_state_write, grad=None)


def _lower_gqa_prefill_attention(ctx, ins, attrs):
    """Causal attention of ``prompts`` prompts of equal (bucket) length
    through the flash kernel, each key/value head serving its group of
    query heads; no positional encoding."""
    from paddle_tpu.kernels.flash_attention import flash_attention

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    B, H, Hkv = int(attrs["prompts"]), int(attrs["heads"]), \
        int(attrs["kv_heads"])
    N = q.shape[0]
    dh = q.shape[-1] // H

    def heads_first(x, h):
        return jnp.transpose(x.reshape(B, N // B, h, dh), (0, 2, 1, 3))

    out = flash_attention(
        heads_first(q, H), heads_first(k, Hkv), heads_first(v, Hkv),
        causal=True, sm_scale=dh ** -0.5, kv_group=H // Hkv)
    return {"Out": jnp.transpose(out, (0, 2, 1, 3)).reshape(N, -1)}


register_op(
    "gqa_prefill_attention", inputs=["Q", "K", "V"], outputs=["Out"],
    attrs={"prompts": 1, "heads": 1, "kv_heads": 1},
    lower=_lower_gqa_prefill_attention, grad=None)


def _lower_gqa_paged_attention(ctx, ins, attrs):
    from paddle_tpu.kernels.gqa_paged_attention import gqa_paged_attention

    q = ins["Q"][0]
    S, H = q.shape[0], int(attrs["heads"])
    table = jnp.reshape(ins["PageTable"][0], (S, -1)).astype(jnp.int32)
    out = gqa_paged_attention(
        q.reshape(S, H, -1), ins["KPool"][0], ins["VPool"][0], table,
        _flat(ins["Lengths"][0]).astype(jnp.int32))
    return {"Out": out.reshape(S, -1)}


register_op(
    "gqa_paged_attention",
    inputs=["Q", "KPool", "VPool", "PageTable", "Lengths"], outputs=["Out"],
    attrs={"heads": 1}, lower=_lower_gqa_paged_attention, grad=None)


def _lower_tied_vocab_projection(ctx, ins, attrs):
    """``x @ table^T`` in float32: the logits of a model whose output
    head is its embedding table ``[vocab, hidden]``, contracted on the
    table's minor axis as it is stored."""
    return {"Out": jnp.einsum("nd,vd->nv", ins["X"][0], ins["W"][0],
                              preferred_element_type=_F32)}


register_op(
    "tied_vocab_projection", inputs=["X", "W"], outputs=["Out"],
    lower=_lower_tied_vocab_projection, grad=None)
