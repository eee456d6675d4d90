"""Latent (MLA) paged decode attention: ONE row pool a layer.

Multi-head latent attention caches, per token and layer, one row
``[ckv | k_rope]`` (the compressed key/value after its norm, and the
rotary key after RoPE) that ALL heads read: ``kv_rank + rope_dim`` wide
(512 + 64 for the published widths), where per-head K and V pages would
be ``heads x (qk + v)`` wide. The pool is ``[num_pages, page_size,
kv_rank + rope_dim]``; the page table ``[S, pages_per_slot]`` and the
length vector ``[S]`` are ``paged_attention``'s.

Decode runs in the ABSORBED form: the caller folds the per-head key
up-projection into the query (``q_lat = q_nope @ Wkvb_K[h]^T``) and the
value up-projection into the output (``o_h = o_lat @ Wkvb_V[h]``), so the
kernel never expands a cached row:

    scores[h, t] = (q_lat[h] . ckv[t] + q_rope[h] . k_rope[t]) * sm_scale
    o_lat[h]     = softmax(scores[h]) @ ckv

* Grid ``(slot, page)``, the page table scalar-prefetched as in
  ``paged_attention``: each step DMAs one resident page once and every
  head uses it. Pages past a slot's length skip their compute and, with
  the host's last-valid-page aliasing of the table's tail, their copy.
* Rows and queries stay in the pool's dtype (bfloat16 when served); the
  two products accumulate in float32, the softmax runs in float32.
* Heads are padded to the sublane tile for the MXU; slots of length 0
  return exactly 0.
* The pool may be WIDER than the row: ``pool_width`` rounds the row up to
  the 128-lane tile (576 -> 640), the lanes past the row zero and never
  part of a product. The TPU's tiled layout pads a bfloat16 row to whole
  lanes whatever its logical width; with the logical width left at 576
  the compiler kept the pool in a layout with the PAGE axis minor and
  copied all of it into row-major order and back around every dispatch
  (3.1 GB of temporaries beside a 2.7 GB pool, compiled for a v5e).

``latent_paged_attention_reference`` is the composed ``jax.numpy`` path
beside it (the explicit oracle, and the default off the TPU), as
``paged_attention_reference`` is for the per-head pool.
"""

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.flash_attention import _is_tpu_target
from paddle_tpu.kernels.paged_attention import KernelCompileError

LATENT_KERNEL_NAME = "latent_paged_decode_attention"

_NEG_INF = -1e30
_MASKED_ROW_M = -1e29
_HEAD_TILE = 16  # bfloat16 sublane tile: heads are padded to it


LANES = 128


def pool_width(row_width):
    """The row rounded up to whole lanes: the pool's last axis."""
    return -(-int(row_width) // LANES) * LANES


def _fit(rows, pool):
    """``rows`` [..., W] zero-padded to the pool's width."""
    pad = pool.shape[-1] - rows.shape[-1]
    if not pad:
        return rows
    return jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, pad)])


def latent_paged_attention_reference(q_lat, q_rope, pool, page_table,
                                     lengths, sm_scale):
    """Gather each slot's pages into ``[S, L, W]``, mask past the length,
    softmax in float32, weighted sum of the latent part.

    q_lat: [S, H, C]; q_rope: [S, H, R]; pool: [P, page_size, >= C + R];
    page_table: [S, npp] int; lengths: [S] int. Returns [S, H, C] in
    ``q_lat``'s dtype.
    """
    S, H, C = q_lat.shape
    R = q_rope.shape[-1]
    ps = pool.shape[1]
    npp = page_table.shape[1]
    rows = pool[page_table].reshape(S, npp * ps, pool.shape[2])
    rows = rows.astype(jnp.float32)
    ckv, kr = rows[..., :C], rows[..., C:C + R]
    s = (jnp.einsum("shc,slc->shl", q_lat.astype(jnp.float32), ckv)
         + jnp.einsum("shr,slr->shl", q_rope.astype(jnp.float32), kr))
    s = s * sm_scale
    valid = jnp.arange(npp * ps)[None, None, :] < lengths[:, None, None]
    s = jnp.where(valid, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("shl,slc->shc", p, ckv)
    dead = (lengths <= 0)[:, None, None]
    return jnp.where(dead, 0.0, out).astype(q_lat.dtype)


def _latent_decode_kernel(table_ref, len_ref, q_ref, row_ref, o_ref,
                          acc_ref, m_ref, l_ref, *, page_size, n_pages,
                          kv_rank, sm_scale):
    """One (slot, page) step: absorb one resident page of latent rows
    into every head's online-softmax state."""
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[s]

    def _compute():
        q = q_ref[0]                                  # [Hp, W]
        rows = row_ref[0]                             # [ps, W]
        sc = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [Hp, ps]
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, sc.shape, 1)
        sc = jnp.where(pos < length, sc, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        pexp = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=-1,
                                                  keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pexp.astype(rows.dtype), rows[:, :kv_rank],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [Hp, C]
        m_ref[...] = m_new

    pl.when(p * page_size < length)(_compute)

    @pl.when(p == n_pages - 1)
    def _finish():
        dead = m_ref[...] <= _MASKED_ROW_M
        o_ref[0] = jnp.where(
            dead, 0.0,
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def _latent_pallas(q_lat, q_rope, pool, page_table, lengths, sm_scale,
                   interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, C = q_lat.shape
    ps, W = pool.shape[1], pool.shape[2]
    npp = page_table.shape[1]
    Hp = -(-H // _HEAD_TILE) * _HEAD_TILE
    q = _fit(jnp.concatenate([q_lat, q_rope], axis=-1), pool).astype(
        pool.dtype)
    if Hp != H:
        q = jnp.pad(q, ((0, 0), (0, Hp - H), (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, npp),
        in_specs=[
            pl.BlockSpec((1, Hp, W), lambda s, p, table, lens: (s, 0, 0)),
            pl.BlockSpec((1, ps, W),
                         lambda s, p, table, lens: (table[s, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, Hp, C), lambda s, p, table, lens: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hp, C), jnp.float32),
            pltpu.VMEM((Hp, 1), jnp.float32),
            pltpu.VMEM((Hp, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _latent_decode_kernel, page_size=ps, n_pages=npp, kv_rank=C,
            sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hp, C), q_lat.dtype),
        interpret=interpret,
        name=LATENT_KERNEL_NAME,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32), q, pool)
    return out[:, :H]


def latent_paged_attention(q_lat, q_rope, pool, page_table, lengths,
                           sm_scale, force_reference=False,
                           force_pallas=False):
    """Absorbed-form latent decode attention over a paged row pool.

    q_lat: [S, H, kv_rank] (the no-position query already multiplied by
    the key up-projection); q_rope: [S, H, rope_dim] (after RoPE); pool:
    [num_pages, page_size, pool_width(kv_rank + rope_dim)]; page_table:
    [S, pages_per_slot]; lengths: [S] resident rows a slot. Returns the
    latent output [S, H, kv_rank]; the caller applies the value
    up-projection. The Pallas kernel on TPU targets, the reference
    elsewhere; a kernel the compiler refuses raises
    ``KernelCompileError``.
    """
    use_pallas = force_pallas or (not force_reference and _is_tpu_target())
    if not use_pallas:
        return latent_paged_attention_reference(
            q_lat, q_rope, pool, page_table, lengths, sm_scale)
    try:
        return _latent_pallas(q_lat, q_rope, pool, page_table, lengths,
                              sm_scale, interpret=not _is_tpu_target())
    except Exception as exc:
        raise KernelCompileError(
            LATENT_KERNEL_NAME, (q_lat, q_rope, pool, page_table, lengths),
            exc) from exc


def _page_slots(page_table, positions, page_size):
    """(page id, offset) of each slot's row at ``positions``; a position
    past the table lands on the trash page."""
    npp = page_table.shape[1]
    pos = positions.astype(jnp.int32)
    idx = pos // page_size
    page = page_table[jnp.arange(page_table.shape[0]),
                      jnp.minimum(idx, npp - 1)]
    return jnp.where(idx < npp, page, 0), pos % page_size


def latent_row_write(pool, rows, page_table, positions):
    """Decode's cache write: slot ``s``'s new row ``rows[s]`` lands at
    ``(page_table[s, pos // page_size], pos % page_size)``. A slot whose
    table row points at the trash page (page 0) writes there."""
    page, off = _page_slots(page_table.astype(jnp.int32), positions,
                            pool.shape[1])
    return pool.at[page, off, :].set(_fit(rows, pool).astype(pool.dtype))


def latent_row_prefill(pool, rows, page_rows, lengths):
    """Prefill's cache write, a page at a time: prompt ``b``'s rows
    ``rows[b]`` ([T, W], T a multiple of the page size) land in the pages
    ``page_rows[b]`` names, in order. A page that begins at or past
    ``lengths[b]`` goes to the trash page; the tail of the prompt's last
    page holds rows of padding that no length ever reaches and decode
    overwrites."""
    B, T, W = rows.shape
    ps = pool.shape[1]
    n = T // ps
    pages = page_rows.astype(jnp.int32)[:, :n]
    live = (jnp.arange(n)[None, :] * ps) < lengths.astype(
        jnp.int32)[:, None]
    pages = jnp.where(live, pages, 0).reshape(B * n)
    return pool.at[pages].set(
        _fit(rows.reshape(B * n, ps, W), pool).astype(pool.dtype))
