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

* Grid ``(slot,)``, the page table and the lengths scalar-prefetched and
  the pool left in HBM, as in ``paged_attention``: a grid step is a slot,
  and its RESIDENT pages, ``ceil(length / page_size)`` of them, are walked
  inside the body by ``paged_attention._walk_resident_pages``, several
  pages a step of the walk (``_pages_per_step``): the step's pages are
  copied together into one half of a VMEM buffer while the half before it
  is absorbed, every head using the rows once, and the next slot's first
  pages are started under this slot's last. A page past a slot's length
  is never copied or computed on and its table entry never read (the
  kernel does not need the host's last-valid-page aliasing of the table's
  tail); a slot's last step may hold fewer resident pages than the
  others, and the rows of the half that no copy filled are zeroed, so
  their weights of exactly 0 meet no stale NaN. A (slot, page) grid paid
  ~0.5 us a resident 164 KB page and ~0.1 us a page that held nothing
  (PERF.md section 6, PR 50).
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

from paddle_tpu.kernels.flash_attention import (
    _VMEM_BUDGET, _is_tpu_target, _mosaic_params)
from paddle_tpu.kernels.paged_attention import (
    KernelCompileError, _start_slot, _walk_resident_pages, _walk_scratch)

LATENT_KERNEL_NAME = "latent_paged_decode_attention"

_NEG_INF = -1e30
_MASKED_ROW_M = -1e29
_HEAD_TILE = 16  # bfloat16 sublane tile: heads are padded to it


LANES = 128

# Pages a step of the walk. One constant serves both served geometries
# (256 slots x 32 padded heads x tables of 12 pages with ~5 resident, 64 x
# 64 x 40 with ~16; 164 KB pages): the kernel alone read 1.21-1.23 / 0.90-
# 0.95 ms a call on the (slot, page) grid and 0.97-1.00 / 0.76-0.81, 0.72-
# 0.74 / 0.53-0.56, 0.60-0.62 / 0.41-0.43, 0.57-0.59 / 0.36-0.37 at 1, 2,
# 4, 8 pages a step; 12 and 16 read 0.67 / 0.35 and 0.81 / 0.36 (a ragged
# last step computes the whole step's rows). What a step buys is ROWS A
# PRODUCT, not copies in flight: eight pages copied together and absorbed
# a page at a time read what one page a step reads (0.998 / 0.753; my
# chip runs, PR 50; PERF.md section 6).
_PAGES_PER_STEP = 8


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


def _absorb_rows(q, rows, first, length, acc_ref, m_ref, l_ref, *, kv_rank,
                 sm_scale):
    """Absorb ``rows`` [n, W], a slot's cached rows from position
    ``first`` on, into every head's online-softmax state (``q`` [Hp, W];
    the running max and sum ``m``, ``l`` [Hp, 1] and ``acc`` [Hp, C] in
    float32 VMEM scratch). Rows at or past ``length`` are masked in the
    scores; their weights are exactly 0, so they must be FINITE."""
    sc = jax.lax.dot_general(
        q, rows, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale       # [Hp, n]
    pos = first + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    sc = jnp.where(pos < length, sc, _NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
    pexp = jnp.exp(sc - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        pexp.astype(rows.dtype), rows[:, :kv_rank],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [Hp, C]
    m_ref[...] = m_new


def _finish_heads(o_ref, acc_ref, m_ref, l_ref):
    """The slot's output from its online-softmax state; exactly 0 where no
    row was seen."""
    dead = m_ref[...] <= _MASKED_ROW_M
    o_ref[0] = jnp.where(
        dead, 0.0,
        acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _latent_decode_kernel(table_ref, len_ref, q_ref, pool_hbm, o_ref,
                          row_buf, sem, ahead_ref, acc_ref, m_ref, l_ref,
                          *, page_size, group, kv_rank, sm_scale):
    """One grid step is one SLOT: every head's online-softmax state
    absorbs the slot's resident pages, ``group`` of them a step of the
    walk (``_walk_resident_pages``). ``table_ref`` and ``len_ref`` are
    the scalar-prefetch operands; the pool stays in HBM."""
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    # a step's rows past the table's last page are not the slot's,
    # whatever length a caller hands in
    length = jnp.minimum(len_ref[s], table_ref.shape[1] * page_size)
    _start_slot(acc_ref, m_ref, l_ref)
    q = q_ref[0]                                      # [Hp, W]

    def absorb(g, row_ref):
        _absorb_rows(q, row_ref[0], g * group * page_size, length, acc_ref,
                     m_ref, l_ref, kv_rank=kv_rank, sm_scale=sm_scale)

    # the ragged bound: ceil(length / page_size) pages, 0 for an empty slot
    _walk_resident_pages(
        table_ref, s,
        lambda slot: (len_ref[slot] + page_size - 1) // page_size,
        (pool_hbm,), (row_buf,), sem, ahead_ref, absorb, group=group)
    _finish_heads(o_ref, acc_ref, m_ref, l_ref)


def _pages_per_step(page_size, width, itemsize, heads, pages_per_slot):
    """How many pages a step of the walk copies and absorbs together:
    ``_PAGES_PER_STEP``, fewer where the table is narrower or where both
    halves of the walk's buffer with a step's float32 scores and weights
    would not fit ``flash_attention._VMEM_BUDGET``."""
    def held(pages):
        rows = pages * page_size
        return 2 * rows * width * itemsize + 2 * heads * rows * 4

    pages = max(1, min(_PAGES_PER_STEP, pages_per_slot))
    while pages > 1 and held(pages) > _VMEM_BUDGET:
        pages //= 2
    return pages


# jitted so that a program's many calls (one a layer and token step: 24
# and 32 in the served step programs) trace and lower the kernel ONCE:
# untraced it was 7.6 and 11.2 s of set-up over the (slot, page) kernel's
# (``trace_lower_s``, my chip runs, PR 50)
@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "interpret", "group"))
def _latent_pallas(q_lat, q_rope, pool, page_table, lengths, sm_scale,
                   interpret, group=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, C = q_lat.shape
    ps, W = pool.shape[1], pool.shape[2]
    npp = page_table.shape[1]
    Hp = -(-H // _HEAD_TILE) * _HEAD_TILE
    if group is None:
        group = _pages_per_step(ps, W, pool.dtype.itemsize, Hp, npp)
    q = _fit(jnp.concatenate([q_lat, q_rope], axis=-1), pool).astype(
        pool.dtype)
    if Hp != H:
        q = jnp.pad(q, ((0, 0), (0, Hp - H), (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, Hp, W), lambda s, table, lens: (s, 0, 0)),
            # the pool stays where it is: the body copies its pages
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hp, C), lambda s, table, lens: (s, 0, 0)),
        scratch_shapes=_walk_scratch(pool, group=group) + [
            pltpu.VMEM((Hp, C), jnp.float32),
            pltpu.VMEM((Hp, 1), jnp.float32),
            pltpu.VMEM((Hp, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _latent_decode_kernel, page_size=ps, group=group, kv_rank=C,
            sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hp, C), q_lat.dtype),
        interpret=interpret,
        name=LATENT_KERNEL_NAME,
        **_mosaic_params(interpret, ("arbitrary",)),
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
