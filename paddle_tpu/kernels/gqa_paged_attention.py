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
vector ``[S]``, the trash page and the last-valid-page aliasing of a
table's tail are ``paged_attention``'s; the Transformer's pools are the
group-of-1 case and stay with their own kernel, whose compiled program
this file does not touch.

* Grid ``(slot, page)``, table and lengths scalar-prefetched; pages past a
  slot's length skip their compute and, by the aliasing, their copy.
* Queries and rows stay in the pool's dtype; both products accumulate in
  float32 and the softmax runs in float32.
* A group is padded to the sublane tile; slots of length 0 return 0.

``gqa_paged_attention_reference`` is the composed path beside it (the
explicit oracle, and the default off the TPU).
"""

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.flash_attention import _is_tpu_target
from paddle_tpu.kernels.paged_attention import KernelCompileError

GQA_KERNEL_NAME = "gqa_paged_decode_attention"

_NEG_INF = -1e30
_MASKED_ROW_M = -1e29
_GROUP_TILE = 16  # bfloat16 sublane tile: a query group is padded to it


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


def _gqa_decode_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                       acc_ref, m_ref, l_ref, *, page_size, n_pages,
                       kv_heads, group, head_dim, sm_scale):
    """One (slot, page) step: every query group absorbs its head's part
    of one resident page into its online-softmax state."""
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
        for h in range(kv_heads):
            rows = slice(h * group, (h + 1) * group)
            lanes = slice(h * head_dim, (h + 1) * head_dim)
            q = q_ref[0, rows, :]                         # [group, dh]
            k = k_ref[0, :, lanes]                        # [ps, dh]
            v = v_ref[0, :, lanes]
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            pos = p * page_size + jax.lax.broadcasted_iota(
                jnp.int32, sc.shape, 1)
            sc = jnp.where(pos < length, sc, _NEG_INF)
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

    pl.when(p * page_size < length)(_compute)

    @pl.when(p == n_pages - 1)
    def _finish():
        dead = m_ref[...] <= _MASKED_ROW_M
        o_ref[0] = jnp.where(
            dead, 0.0,
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def _gqa_pallas(q, k_pool, v_pool, page_table, lengths, sm_scale,
                interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, dh = q.shape
    ps, width = k_pool.shape[1], k_pool.shape[2]
    Hkv = width // dh
    g = H // Hkv
    gp = -(-g // _GROUP_TILE) * _GROUP_TILE
    npp = page_table.shape[1]
    qg = q.reshape(S, Hkv, g, dh).astype(k_pool.dtype)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    qg = qg.reshape(S, Hkv * gp, dh)
    q_spec = pl.BlockSpec((1, Hkv * gp, dh),
                          lambda s, p, table, lens: (s, 0, 0))
    kv_spec = pl.BlockSpec((1, ps, width),
                           lambda s, p, table, lens: (table[s, p], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(S, npp),
        in_specs=[q_spec, kv_spec, kv_spec], out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((Hkv * gp, dh), jnp.float32),
                        pltpu.VMEM((Hkv * gp, 1), jnp.float32),
                        pltpu.VMEM((Hkv * gp, 1), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(
            _gqa_decode_kernel, page_size=ps, n_pages=npp, kv_heads=Hkv,
            group=gp, head_dim=dh, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hkv * gp, dh), q.dtype),
        interpret=interpret, name=GQA_KERNEL_NAME,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32), qg, k_pool,
      v_pool)
    return out.reshape(S, Hkv, gp, dh)[:, :, :g].reshape(S, H, dh)


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
