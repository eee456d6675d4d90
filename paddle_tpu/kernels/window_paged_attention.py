"""Grouped-query paged decode attention over the last ``window`` positions
of every slot, for a layer whose cache is a RING of pages.

``gqa_paged_attention``'s grid ``(slot, page)``, online softmax, row
layout ``[pages, page_size, Hkv * dh]`` and trash page, plus a first
visible position a slot (``max(length - window, 0)``). What a window layer
changes:

* **The table is a ring**, ``[S, R]``: logical page ``j`` of a slot (the
  rows of positions ``j * page_size`` and on) is held in column ``j % R``,
  so the entries are not in position order. The host gives a page back
  the moment its last row is behind every query the next dispatch can
  hold, and ``R`` pages a slot are enough whatever the sequence's length.
* **Pages behind the window cost nothing**: the grid's step ``p`` holds
  logical page ``first // page_size + p`` (position order, whatever the
  column), steps past the slot's last page alias it (no copy, by the
  pipeline's unchanged block index) and skip their compute, and the first
  page's rows before ``first`` are masked.
* **A kernel name of its own** (``WINDOW_KERNEL_NAME``): a device trace
  tells a window layer's calls from a full layer's.

The per-page update is ``gqa_paged_attention._gqa_decode_kernel``'s,
written out again: that file's kernel is traced into two served models'
programs, and Mosaic's serialised body changes with any edit there.

``window_paged_attention_reference`` is the composed path beside it (the
explicit oracle, and the default off the TPU).
"""

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.flash_attention import _is_tpu_target
from paddle_tpu.kernels.gqa_paged_attention import (
    _GROUP_TILE,
    _MASKED_ROW_M,
    _NEG_INF,
)
from paddle_tpu.kernels.paged_attention import KernelCompileError

# no other kernel's name is a part of it: a trace's readers find a kernel by
# the names that HOLD theirs
WINDOW_KERNEL_NAME = "gqa_window_decode_attention"


def ring_pages(first, lengths, page_size):
    """(first logical page, last logical page) a slot's ring holds for the
    visible rows ``first <= position < length``."""
    return first // page_size, jnp.maximum(lengths - 1, 0) // page_size


def window_paged_attention_reference(q, k_pool, v_pool, ring_table, lengths,
                                     window, sm_scale):
    """q: [S, H, dh]; k_pool/v_pool: [P, page_size, Hkv * dh]; ring_table:
    [S, R] int (column ``j % R`` holds logical page ``j``); lengths: [S]
    resident rows. Returns [S, H, dh] in ``q``'s dtype; a slot of length 0
    returns 0."""
    S, H, dh = q.shape
    ps, R = k_pool.shape[1], ring_table.shape[1]
    Hkv = k_pool.shape[2] // dh
    first = jnp.maximum(lengths - window, 0)
    lo, _hi = ring_pages(first, lengths, ps)
    col = jnp.arange(R)[None, :]
    # the logical page column c holds: the one in lo .. lo + R - 1 that is
    # c modulo R
    page = lo[:, None] + (col - lo[:, None]) % R                  # [S, R]
    pos = (page[:, :, None] * ps + jnp.arange(ps)[None, None, :]).reshape(
        S, R * ps)

    def rows(pool):
        return pool[ring_table].astype(jnp.float32).reshape(
            S, R * ps, Hkv, dh)

    qg = q.astype(jnp.float32).reshape(S, Hkv, H // Hkv, dh)
    s = jnp.einsum("skgd,stkd->skgt", qg, rows(k_pool)) * sm_scale
    valid = (pos >= first[:, None]) & (pos < lengths[:, None])
    p = jax.nn.softmax(jnp.where(valid[:, None, None, :], s, _NEG_INF),
                       axis=-1)
    out = jnp.einsum("skgt,stkd->skgd", p, rows(v_pool)).reshape(S, H, dh)
    dead = (lengths <= 0)[:, None, None]
    return jnp.where(dead, 0.0, out).astype(q.dtype)


def _window_decode_kernel(table_ref, first_ref, len_ref, q_ref, k_ref, v_ref,
                          o_ref, acc_ref, m_ref, l_ref, *, page_size, n_cols,
                          kv_heads, group, head_dim, sm_scale):
    """One (slot, page) step: every query group absorbs its head's part
    of one resident page of the window into its online-softmax state."""
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    first, length = first_ref[s], len_ref[s]
    base = (first // page_size + p) * page_size

    def _compute():
        for h in range(kv_heads):
            rows = slice(h * group, (h + 1) * group)
            lanes = slice(h * head_dim, (h + 1) * head_dim)
            q = q_ref[0, rows, :]                         # [group, dh]
            k = k_ref[0, :, lanes]                        # [ps, dh]
            v = v_ref[0, :, lanes]
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            pos = base + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            sc = jnp.where((pos >= first) & (pos < length), sc, _NEG_INF)
            m_prev = m_ref[rows, :]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            pexp = jnp.exp(sc - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[rows, :] = l_ref[rows, :] * alpha + jnp.sum(
                pexp, axis=-1, keepdims=True)
            acc_ref[rows, :] = acc_ref[rows, :] * alpha + jax.lax.dot_general(
                pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # [group, dh]
            m_ref[rows, :] = m_new

    pl.when(base < length)(_compute)

    @pl.when(p == n_cols - 1)
    def _finish():
        dead = m_ref[...] <= _MASKED_ROW_M
        o_ref[0] = jnp.where(
            dead, 0.0,
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def _window_pallas(q, k_pool, v_pool, ring_table, first, lengths, sm_scale,
                   interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, dh = q.shape
    ps, width = k_pool.shape[1], k_pool.shape[2]
    Hkv = width // dh
    g = H // Hkv
    gp = -(-g // _GROUP_TILE) * _GROUP_TILE
    R = ring_table.shape[1]
    qg = q.reshape(S, Hkv, g, dh).astype(k_pool.dtype)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    qg = qg.reshape(S, Hkv * gp, dh)

    def page_of(s, p, table, first, lens):
        lo, hi = ring_pages(first[s], lens[s], ps)
        return (table[s, jnp.minimum(lo + p, hi) % R], 0, 0)

    q_spec = pl.BlockSpec((1, Hkv * gp, dh),
                          lambda s, p, table, first, lens: (s, 0, 0))
    kv_spec = pl.BlockSpec((1, ps, width), page_of)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(S, R),
        in_specs=[q_spec, kv_spec, kv_spec], out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((Hkv * gp, dh), jnp.float32),
                        pltpu.VMEM((Hkv * gp, 1), jnp.float32),
                        pltpu.VMEM((Hkv * gp, 1), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(
            _window_decode_kernel, page_size=ps, n_cols=R, kv_heads=Hkv,
            group=gp, head_dim=dh, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hkv * gp, dh), q.dtype),
        interpret=interpret, name=WINDOW_KERNEL_NAME,
    )(ring_table.astype(jnp.int32), first.astype(jnp.int32),
      lengths.astype(jnp.int32), qg, k_pool, v_pool)
    return out.reshape(S, Hkv, gp, dh)[:, :, :g].reshape(S, H, dh)


def window_paged_attention(q, k_pool, v_pool, ring_table, lengths, window,
                           sm_scale=None, force_reference=False,
                           force_pallas=False):
    """Grouped-query decode attention of every slot over the last
    ``window`` of its ``lengths`` resident rows, held in a ring of pages.

    q: [S, H, dh]; k_pool/v_pool: [num_pages, page_size, Hkv * dh] with
    ``H`` a multiple of ``Hkv``; ring_table: [S, R], logical page ``j`` in
    column ``j % R``, ``R`` pages enough for ``window`` rows at any
    alignment. Returns [S, H, dh]. The Pallas kernel on TPU targets, the
    reference elsewhere; a kernel the compiler refuses raises
    ``KernelCompileError``.
    """
    S, H, dh = q.shape
    ps, width = k_pool.shape[1], k_pool.shape[2]
    R = ring_table.shape[1]
    if k_pool.ndim != 3 or width % dh or H % (width // dh):
        raise ValueError(
            "a grouped-query page pool is [num_pages, page_size, Hkv * dh] "
            "with the %d query heads of %d a multiple of Hkv; got pool %s"
            % (H, dh, tuple(k_pool.shape)))
    if (R - 1) * ps < int(window) - 1:
        raise ValueError(
            "a ring of %d pages of %d rows cannot hold a window of %d at "
            "every alignment" % (R, ps, window))
    if sm_scale is None:
        sm_scale = dh ** -0.5
    use_pallas = force_pallas or (not force_reference and _is_tpu_target())
    if not use_pallas:
        return window_paged_attention_reference(
            q, k_pool, v_pool, ring_table, lengths, int(window), sm_scale)
    first = jnp.maximum(lengths - int(window), 0)
    try:
        return _window_pallas(q, k_pool, v_pool, ring_table, first, lengths,
                              sm_scale, interpret=not _is_tpu_target())
    except Exception as exc:
        raise KernelCompileError(
            WINDOW_KERNEL_NAME, (q, k_pool, v_pool, ring_table, lengths),
            exc) from exc
