"""Ops of a decoder-only block whose attention layers are of two kinds,
full and sliding-window, for SERVING: per-head query/key norms with RoPE
on the layers that have it, the output gate, and a window layer's cache:
a RING of pages a slot (``kernels/window_paged_attention.py``), written a
row at a time by decode and a page at a time by prefill, which keeps only
the rows the window can still see after the prompt.

Like ``decoder_ops.py``, every op works on flat token rows ``[N, ...]``
and none has a gradient. A full layer's rows go through
``latent_row_write`` / ``latent_row_prefill`` / ``gqa_paged_attention`` as
they are.
"""

import jax
import jax.numpy as jnp

from paddle_tpu.core.op_registry import register_op
from paddle_tpu.ops.decoder_ops import rms_norm, rope_rotate_half

_F32 = jnp.float32


def _flat(x):
    return jnp.reshape(x, (-1,)).astype(jnp.int32)


def _lower_qk_norm_rope(ctx, ins, attrs):
    """RMSNorm over each head's columns of the query and the key, then
    RoPE (``rotate_half``, every column) where ``rope``: the layers
    without it carry no positional encoding at all."""
    q, k = ins["Q"][0], ins["K"][0]
    H, Hkv = int(attrs["heads"]), int(attrs["kv_heads"])
    eps = float(attrs["epsilon"])
    N = q.shape[0]
    q = rms_norm(q.reshape(N, H, -1), ins["QNorm"][0], eps)
    k = rms_norm(k.reshape(N, Hkv, -1), ins["KNorm"][0], eps)
    if attrs["rope"]:
        if ins.get("Positions"):
            pos = _flat(ins["Positions"][0])
        else:   # prefill: token n of the flat batch stands at n % bucket
            pos = jnp.arange(N) % int(attrs["period"])
        theta = float(attrs["theta"])
        q, k = rope_rotate_half(q, pos, theta), rope_rotate_half(k, pos,
                                                                 theta)
    return {"QOut": q.reshape(N, -1), "KOut": k.reshape(N, -1)}


register_op(
    "qk_norm_rope", inputs=["Q", "K", "QNorm", "KNorm", "Positions"],
    outputs=["QOut", "KOut"],
    attrs={"heads": 1, "kv_heads": 1, "rope": True, "theta": 10000.0,
           "period": 0, "epsilon": 1e-5},
    lower=_lower_qk_norm_rope, grad=None)


def _lower_sigmoid_gate(ctx, ins, attrs):
    """``x * sigmoid(gate)`` in float32, rounded once."""
    x = ins["X"][0]
    return {"Out": (x.astype(_F32) * jax.nn.sigmoid(
        ins["Gate"][0].astype(_F32))).astype(x.dtype)}


register_op("sigmoid_gate", inputs=["X", "Gate"], outputs=["Out"],
            lower=_lower_sigmoid_gate, grad=None)


def ring_row_write(pool, rows, ring_table, positions):
    """Decode's write into a ring: slot ``s``'s row lands in the page of
    column ``(pos // page_size) % R`` at ``pos % page_size``. A slot whose
    table row is zero writes to the trash page (page 0)."""
    ps, R = pool.shape[1], ring_table.shape[1]
    page = ring_table[jnp.arange(ring_table.shape[0]),
                      (positions // ps) % R]
    return pool.at[page, positions % ps, :].set(rows.astype(pool.dtype))


def ring_row_prefill(pool, rows, ring_rows, lengths, window):
    """Prefill's write into a ring, a page at a time: of prompt ``b``'s
    rows ``rows[b]`` ([T, W], T a multiple of the page size) only the
    logical pages the window still needs AFTER the prompt are written,
    ``max(len - window + 1, 0) // page_size`` (the first row the next
    token's query can see) to the prompt's last, each into column ``page %
    R`` of ``ring_rows[b]``; every other column goes to the trash page."""
    B, T, W = rows.shape
    ps, R = pool.shape[1], ring_rows.shape[1]
    n = T // ps
    cols = min(n, R)
    lo = jnp.maximum(lengths - window + 1, 0) // ps               # [B]
    hi = (lengths - 1) // ps                   # -1 for a row of padding
    col = jnp.arange(cols)[None, :]
    page = lo[:, None] + (col - lo[:, None]) % R                  # [B, cols]
    kept = rows.reshape(B, n, ps, W)[
        jnp.arange(B)[:, None], jnp.minimum(page, n - 1)]
    to = jnp.where(page <= hi[:, None], ring_rows[:, :cols], 0)
    return pool.at[to.reshape(-1)].set(
        kept.reshape(B * cols, ps, W).astype(pool.dtype))


def _lower_window_row_write(ctx, ins, attrs):
    rows = ins["Rows"][0]
    table = jnp.reshape(ins["PageTable"][0], (rows.shape[0], -1))
    return {"PoolOut": ring_row_write(
        ins["Pool"][0], rows, table.astype(jnp.int32),
        _flat(ins["Pos"][0]))}


register_op(
    "window_row_write", inputs=["Pool", "Rows", "PageTable", "Pos"],
    outputs=["PoolOut"], lower=_lower_window_row_write, grad=None)


def _lower_window_row_prefill(ctx, ins, attrs):
    ring_rows = ins["PageRows"][0].astype(jnp.int32)
    B = ring_rows.shape[0]
    rows = ins["Rows"][0]
    return {"PoolOut": ring_row_prefill(
        ins["Pool"][0], rows.reshape(B, -1, rows.shape[-1]), ring_rows,
        _flat(ins["Lens"][0]), int(attrs["window"]))}


register_op(
    "window_row_prefill", inputs=["Pool", "Rows", "PageRows", "Lens"],
    outputs=["PoolOut"], attrs={"window": 1},
    lower=_lower_window_row_prefill, grad=None)


# the flash kernel's tile for a prefill bucket: its grid walks every
# (query tile, key tile) pair, visible or not, at ~0.35 us a step, and at
# its default 128 x 128 a prompt of 8192 is 131 072 steps a layer (47 ms
# of a 52 ms call, my chip run, PR 33); 512 x 512 is 8192 steps
_PREFILL_TILE = 512


def _lower_window_prefill_attention(ctx, ins, attrs):
    """``gqa_prefill_attention`` for long buckets, with a band: causal
    attention of ``prompts`` prompts of equal (bucket) length through the
    flash kernel at tiles of ``_PREFILL_TILE``, each key/value head
    serving its group of query heads; with ``window`` > 0 a query sees
    its last ``window`` positions only. An op of its own, not an
    attribute of that one: that op's lowering is traced into another
    served model's prefill programs, whose compiled modules carry its
    source lines."""
    from paddle_tpu.kernels.flash_attention import flash_attention

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    B, H, Hkv = int(attrs["prompts"]), int(attrs["heads"]), \
        int(attrs["kv_heads"])
    N = q.shape[0]
    dh = q.shape[-1] // H
    tile = min(_PREFILL_TILE, N // B)

    def heads_first(x, h):
        return jnp.transpose(x.reshape(B, N // B, h, dh), (0, 2, 1, 3))

    out = flash_attention(
        heads_first(q, H), heads_first(k, Hkv), heads_first(v, Hkv),
        causal=True, sm_scale=dh ** -0.5, kv_group=H // Hkv,
        window=int(attrs["window"]), block_q=tile, block_k=tile)
    return {"Out": jnp.transpose(out, (0, 2, 1, 3)).reshape(N, -1)}


register_op(
    "window_prefill_attention", inputs=["Q", "K", "V"], outputs=["Out"],
    attrs={"prompts": 1, "heads": 1, "kv_heads": 1, "window": 0},
    lower=_lower_window_prefill_attention, grad=None)


def _lower_window_paged_attention(ctx, ins, attrs):
    from paddle_tpu.kernels.window_paged_attention import (
        window_paged_attention,
    )

    q = ins["Q"][0]
    S, H = q.shape[0], int(attrs["heads"])
    table = jnp.reshape(ins["PageTable"][0], (S, -1)).astype(jnp.int32)
    out = window_paged_attention(
        q.reshape(S, H, -1), ins["KPool"][0], ins["VPool"][0], table,
        _flat(ins["Lengths"][0]), int(attrs["window"]))
    return {"Out": out.reshape(S, -1)}


register_op(
    "window_paged_attention",
    inputs=["Q", "KPool", "VPool", "PageTable", "Lengths"], outputs=["Out"],
    attrs={"heads": 1, "window": 1},
    lower=_lower_window_paged_attention, grad=None)
