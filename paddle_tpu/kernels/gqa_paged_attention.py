"""Grouped-query paged decode attention: ``H`` query heads over ``Hkv``
key/value heads (``H = group x Hkv``; multi-query is ``Hkv`` = 1), one
query token a slot, over K and V page pools of whole token rows ``[pages,
page_size, Hkv * dh]``.

A sibling of ``paged_attention.paged_attention``, not an extension of it:
that kernel keeps every head on the lanes of one row and reduces a head's
lanes on the vector unit through a 0/1 indicator, which is right for
``H * dh`` = 512 lanes of 8 heads and wrong here, where 20 query heads
share ONE 128-lane row: the whole group's scores against a page are one
``[group, dh] x [dh, page_size]`` product on the matrix unit and the row
is read once for all of them (``latent_attention``'s shape, with K and V
in pools of their own). The page table ``[S, pages_per_slot]``, the length
vector ``[S]`` and the trash page are ``paged_attention``'s; the
Transformer's pools are the group-of-1 case and stay with their own kernel,
whose traced body this file does not touch.

* Grid ``(slot,)``, table and lengths scalar-prefetched, the K and V pools
  left in HBM (``memory_space=pl.ANY``): a grid step is a slot, and its
  RESIDENT pages, ``ceil(length / page_size)`` of them, are walked inside
  the body by ``paged_attention._walk_resident_pages``, several pages a
  step of the walk (``_pages_per_step``): the kernel's own copies bring a
  step's K and V pages into one half of a VMEM buffer a pool while the half
  before it is absorbed, and the next slot's first pages start under this
  slot's last. A page past a slot's length is never copied or computed on
  and its table entry never read (the kernel does not need the host's
  last-valid-page aliasing of a table's tail); a slot's last step may hold
  fewer resident pages than the others, and the rows no copy filled are
  zeroed by the walk and masked by their positions here. The ``(slot,
  page)`` grid this replaces paid a grid step for every page of every
  table (PERF.md section 6, PR 52).
* A key/value head a step is ``[group, dh] x [dh, pages * page_size]`` and
  ``[group, pages * page_size] x [pages * page_size, dh]`` with one max /
  exp / rescale. Queries and rows stay in the pool's dtype; both products
  accumulate in float32 and the softmax runs in float32.
* A group is padded to the sublane tile; slots of length 0 return 0.
* ``_attend_slot`` is the body of a grid step and ``_slot_walk_call`` the
  ``pallas_call`` for this kernel AND for the ring kernel of
  ``window_paged_attention``: this one is the ring kernel with a first
  visible position of 0 and the table's columns in page order.

``gqa_paged_attention_reference`` is the composed path beside it (the
explicit oracle, and the default off the TPU).
"""

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.flash_attention import (
    _VMEM_BUDGET, _is_tpu_target, _mosaic_params)
from paddle_tpu.kernels.latent_attention import _finish_heads
from paddle_tpu.kernels.paged_attention import (
    KernelCompileError, _start_slot, _walk_resident_pages, _walk_scratch)

GQA_KERNEL_NAME = "gqa_paged_decode_attention"

_NEG_INF = -1e30
_GROUP_TILE = 16  # bfloat16 sublane tile: a query group is padded to it

# The most pages a step of the walk holds. The kernels alone, ms a call at
# the four served geometries (Solar 96 slots x 64 heads on 8 x tables of 80
# pages / Trinity 96 x 32 on 4 x 68 / Granite 64 x 32 on 8 x 40 / Jamba 256
# x 20 on 1 x 12; 256 KB, 128 KB, 256 KB and 32 KB a page a pool) and the
# ring kernel at Trinity's (a ring of 18 under a window of 2048): the (slot,
# page) grid 4.15-4.20 / 2.65 / 1.51-1.61 / 1.06-1.07 and 1.51-1.52; the
# walk at 1 page a step 3.43-3.48 / 2.10 / 1.24-1.35 / 0.82-0.83 and
# 1.24-1.25; at 2 3.34-3.39 / 1.92-1.93 / 1.24-1.35 / 0.59-0.60 and
# 1.19-1.21; at 4 2.07-2.10 / 1.22-1.24 / 0.79-0.86 / 0.51-0.52 and 0.74;
# at 6 2.01-2.05 / 1.08 / 0.77-0.84 / 0.39-0.40 and 0.62; at 8 2.00-2.02 /
# 1.07 / 0.76-0.82 / 0.39 and 0.69-0.70 (a ring holds 17 pages: 8 + 8 + 1
# computes 24). What a step buys is rows a product, as in
# ``latent_attention`` (my chip runs, PR 52; PERF.md section 6).
_PAGES_PER_STEP = 8


def gqa_paged_attention_reference(q, k_pool, v_pool, page_table, lengths,
                                  sm_scale):
    """q: [S, H, dh]; k_pool/v_pool: [P, page_size, Hkv * dh]; page_table:
    [S, npp] int; lengths: [S] int. Returns [S, H, dh] in ``q``'s dtype;
    a slot of length 0 returns 0."""
    S, H, dh = q.shape
    ps, npp = k_pool.shape[1], page_table.shape[1]
    Hkv = k_pool.shape[2] // dh

    def rows(pool):
        return pool[page_table].astype(jnp.float32).reshape(
            S, npp * ps, Hkv, dh)

    qg = q.astype(jnp.float32).reshape(S, Hkv, H // Hkv, dh)
    s = jnp.einsum("skgd,stkd->skgt", qg, rows(k_pool)) * sm_scale
    valid = jnp.arange(npp * ps)[None, None, None, :] \
        < lengths[:, None, None, None]
    p = jax.nn.softmax(jnp.where(valid, s, _NEG_INF), axis=-1)
    out = jnp.einsum("skgt,stkd->skgd", p, rows(v_pool)).reshape(S, H, dh)
    dead = (lengths <= 0)[:, None, None]
    return jnp.where(dead, 0.0, out).astype(q.dtype)


def _pages_per_step(page_size, width, itemsize, rows, pages_per_slot):
    """How many pages a step of the walk copies and absorbs together: the
    table's pages spread evenly over the fewest steps of at most
    ``_PAGES_PER_STEP`` (a ring of 18 walks its 17 resident pages as 6 +
    6 + 5, not 8 + 8 + 1: a ragged last step computes the whole step's
    rows), and the most under that where both
    halves of both pools' buffers with one head's float32 scores and
    weights (``rows`` padded query rows) would not fit
    ``flash_attention._VMEM_BUDGET`` (multi-head rows of 3840 lanes: 3
    pages of 128 rows, 11.8 MB; 4 pass Mosaic's 16 MB by 40 KB)."""
    def held(pages):
        n = pages * page_size
        return 4 * n * width * itemsize + 2 * rows * n * 4

    steps = -(-pages_per_slot // _PAGES_PER_STEP)
    pages = -(-pages_per_slot // steps)
    while pages > 1 and held(pages) > _VMEM_BUDGET:
        pages -= 1
    return pages


def _attend_slot(table_ref, slot, first, length, pages_of, column_of, q_ref,
                 k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, ahead_ref, acc_ref,
                 m_ref, l_ref, *, page_size, pages, kv_heads, rows, head_dim,
                 sm_scale):
    """One grid step of either grouped-query decode kernel: ``slot``'s
    query groups (``rows`` padded rows a key/value head) absorb the slot's
    resident pages into their online-softmax state, ``pages`` of them a
    step of the walk (``_walk_resident_pages``; ``pages_of`` and
    ``column_of`` are its). The slot's ``p``-th resident page holds the
    positions from ``(first // page_size + p) * page_size`` on; a row is
    visible where ``first <= position < length`` (``first`` None: from 0).
    A key/value head a step is ``[rows, dh] x [dh, pages * page_size]`` and
    ``[rows, pages * page_size] x [pages * page_size, dh]`` with ONE max /
    exp / rescale; the rows of a ragged last step that no copy filled are
    zero (the walk's) and masked here by their positions."""
    _start_slot(acc_ref, m_ref, l_ref)
    lo = 0 if first is None else first // page_size

    def absorb(g, k_rows, v_rows):
        base = (lo + g * pages) * page_size
        for h in range(kv_heads):
            group = slice(h * rows, (h + 1) * rows)
            lanes = slice(h * head_dim, (h + 1) * head_dim)
            q = q_ref[0, group, :]                        # [rows, dh]
            k = k_rows[0, :, lanes]                       # [pages * ps, dh]
            v = v_rows[0, :, lanes]
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            pos = base + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            seen = pos < length
            if first is not None:
                seen &= pos >= first
            sc = jnp.where(seen, sc, _NEG_INF)
            m_prev = m_ref[group, :]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            pexp = jnp.exp(sc - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[group, :] = l_ref[group, :] * alpha + jnp.sum(
                pexp, axis=-1, keepdims=True)
            acc_ref[group, :] = acc_ref[group, :] * alpha \
                + jax.lax.dot_general(
                    pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)   # [rows, dh]
            m_ref[group, :] = m_new

    _walk_resident_pages(
        table_ref, slot, pages_of, (k_hbm, v_hbm), (k_buf, v_buf), sem,
        ahead_ref, absorb, group=pages, column_of=column_of)
    _finish_heads(o_ref, acc_ref, m_ref, l_ref)


def _gqa_decode_kernel(table_ref, len_ref, q_ref, *refs, page_size, **dims):
    """One grid step is one SLOT over a table in page order: the ring
    kernel's step (``window_paged_attention``) with ``first`` 0 and the
    identity column."""
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    # a step's rows past the table's last page are not the slot's,
    # whatever length a caller hands in
    length = jnp.minimum(len_ref[s], table_ref.shape[1] * page_size)
    _attend_slot(
        table_ref, s, None, length,
        lambda slot: (len_ref[slot] + page_size - 1) // page_size, None,
        q_ref, *refs, page_size=page_size, **dims)


def _slot_walk_call(kernel, name, scalars, q, k_pool, v_pool, sm_scale,
                    interpret, group):
    """The ``pallas_call`` of both grouped-query decode kernels: grid
    ``(S,)``, ``scalars`` (the table first) scalar-prefetched, the pools
    left in HBM for the body's own copies, ``group`` pages a step of the
    walk (None: ``_pages_per_step``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, dh = q.shape
    ps, width = k_pool.shape[1], k_pool.shape[2]
    Hkv = width // dh
    g = H // Hkv
    gp = -(-g // _GROUP_TILE) * _GROUP_TILE
    if group is None:
        group = _pages_per_step(ps, width, k_pool.dtype.itemsize, gp,
                                scalars[0].shape[1])
    qg = q.reshape(S, Hkv, g, dh).astype(k_pool.dtype)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    qg = qg.reshape(S, Hkv * gp, dh)
    q_spec = pl.BlockSpec((1, Hkv * gp, dh), lambda s, *_: (s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars), grid=(S,),
        in_specs=[q_spec,
                  # the pools stay where they are: the body copies a page
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec,
        scratch_shapes=_walk_scratch(k_pool, v_pool, group=group) + [
            pltpu.VMEM((Hkv * gp, dh), jnp.float32),
            pltpu.VMEM((Hkv * gp, 1), jnp.float32),
            pltpu.VMEM((Hkv * gp, 1), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(
            kernel, page_size=ps, pages=group, kv_heads=Hkv, rows=gp,
            head_dim=dh, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hkv * gp, dh), q.dtype),
        interpret=interpret, name=name,
        **_mosaic_params(interpret, ("arbitrary",)),
    )(*[x.astype(jnp.int32) for x in scalars], qg, k_pool, v_pool)
    return out.reshape(S, Hkv, gp, dh)[:, :, :g].reshape(S, H, dh)


# jitted so that a program's call sites (one a layer and token step) share
# ONE trace and ONE lowered function, as ``latent_attention._latent_pallas``
# is (PERF.md section 6, PR 50: 8-11 s of ``trace_lower_s`` otherwise)
@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "interpret", "group"))
def _gqa_pallas(q, k_pool, v_pool, page_table, lengths, sm_scale,
                interpret, group=None):
    return _slot_walk_call(
        _gqa_decode_kernel, GQA_KERNEL_NAME, (page_table, lengths), q,
        k_pool, v_pool, sm_scale, interpret, group)


def gqa_paged_attention(q, k_pool, v_pool, page_table, lengths,
                        sm_scale=None, force_reference=False,
                        force_pallas=False):
    """Grouped-query decode attention over paged K/V row pools.

    q: [S, H, dh]; k_pool/v_pool: [num_pages, page_size, Hkv * dh] with
    ``H`` a multiple of ``Hkv``; page_table: [S, pages_per_slot];
    lengths: [S] resident rows a slot. Returns [S, H, dh]. The Pallas
    kernel on TPU targets, the reference elsewhere; a kernel the compiler
    refuses raises ``KernelCompileError``.
    """
    S, H, dh = q.shape
    width = k_pool.shape[2]
    if k_pool.ndim != 3 or width % dh or H % (width // dh):
        raise ValueError(
            "a grouped-query page pool is [num_pages, page_size, Hkv * dh] "
            "with the %d query heads of %d a multiple of Hkv; got pool %s"
            % (H, dh, tuple(k_pool.shape)))
    if sm_scale is None:
        sm_scale = dh ** -0.5
    use_pallas = force_pallas or (not force_reference and _is_tpu_target())
    if not use_pallas:
        return gqa_paged_attention_reference(
            q, k_pool, v_pool, page_table, lengths, sm_scale)
    try:
        return _gqa_pallas(q, k_pool, v_pool, page_table, lengths, sm_scale,
                           interpret=not _is_tpu_target())
    except Exception as exc:
        raise KernelCompileError(
            GQA_KERNEL_NAME, (q, k_pool, v_pool, page_table, lengths),
            exc) from exc
