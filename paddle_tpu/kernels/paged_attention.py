"""Ragged paged-attention decode: block-paged KV pool + Pallas kernel.

The serving decode residual (ROADMAP item 3, PAPERS.md "Ragged Paged
Attention", arxiv 2604.15464): ``SlotDecodeSession``'s dense slot pool
attends over all ``max_length`` positions for every slot regardless of
how many tokens a slot actually holds, so decode FLOPs/HBM traffic
scale with ``num_slots x max_length``. Here the KV cache is a PAGE
POOL — fixed-size pages ``[num_pages, page_size, H * dh]``, a token's
row of all heads contiguous — plus a per-slot page-index table
``[S, pages_per_slot]`` and a length vector ``[S]`` — and the decode
kernel is ragged over it:

* Grid ``(slot, page)`` with the page table scalar-prefetched
  (``pltpu.PrefetchScalarGridSpec``): the K/V block index maps resolve
  ``table[s, p]`` BEFORE the kernel body runs, so each grid step DMAs
  exactly one resident page — the classic TPU paged-attention shape.
* Per-slot lengths bound the scan: pages at ``p * page_size >=
  length[s]`` skip their compute entirely (``pl.when``), and the host
  fills a slot's unprovisioned table tail with its LAST valid page id,
  so the skipped steps' index maps repeat the previous block and the
  Pallas pipeline elides the copy (revolving-buffer rule: a repeated
  block index issues no new DMA). Decode traffic is proportional to
  pages actually RESIDENT, not ``S x max_length`` —
  ``grid_accounting`` models exactly that contract and the bench/CI
  legs pin it.
* Empty slots (length 0) produce exactly 0 (the flash kernel's
  fully-masked-row contract extended to decode); an unoccupied slot is
  never NaN bait.

The pool is stored as whole token rows because that is the one shape
the chip keeps, the row scatter writes and a Pallas operand reads in
the SAME layout: ``H * dh`` fills the 128 lanes whatever the head
width, so the compiled step holds no copy of a pool (the per-head
``[P, H, page_size, dh]`` pool at ``dh`` 64 was stored page-minor,
scattered head-width-minor and read row-major: six whole-pool copies a
dispatch). The kernels keep heads on the lanes throughout: a head's
scores are ``(k * q_row) @ E`` with ``E`` the ``[H * dh, H]`` 0/1 head
indicator, and its weights are spread back over its lanes by ``E^T``.

``interpret=True`` runs the same kernel on CPU for tests; the composed
XLA reference (gather pages through the table, masked softmax) is the
explicit oracle behind ``FLAGS_paged_attention=reference`` and the
default on CPU targets, mirroring ``flash_attention``'s routing.
"""

import functools

import jax
import jax.numpy as jnp

# Pinned-Place-aware backend test, shared with the flash kernel so the
# two kernels' impl routing can never diverge.
from paddle_tpu.kernels.flash_attention import _is_tpu_target


class KernelCompileError(RuntimeError):
    """A Pallas kernel was refused at trace/lowering time. Carries the
    kernel's name and operand shapes: a kernel the compiler refuses is
    an error to fix, never a reason to serve the reference in its place
    (``FLAGS_paged_attention=reference`` / ``FLAGS_tree_attention=
    reference`` remain the explicit oracle)."""

    def __init__(self, kernel, operands, cause):
        self.kernel = kernel
        self.shapes = tuple(
            (tuple(x.shape), str(x.dtype)) for x in operands)
        super(KernelCompileError, self).__init__(
            "Pallas kernel %r failed to compile for operands %s: %s: %s"
            % (kernel, list(self.shapes), type(cause).__name__, cause))


# pallas_call names: what a KernelCompileError, a Mosaic error, a
# profiler trace and the compiled text (op_name) call these kernels
PAGED_KERNEL_NAME = "paged_decode_attention"
TREE_KERNEL_NAME = "paged_tree_attention"

_NEG_INF = -1e30


def pages_for(length, page_size):
    """Pages a slot with ``length`` resident tokens occupies."""
    return -(-int(length) // int(page_size))


def _gather_slot_rows(pool, page_table, num_heads):
    """Each slot's pages through the table as a dense
    ``[S, pages_per_slot * page_size, H, dh]`` float32 view."""
    S, npp = page_table.shape
    ps, width = pool.shape[1], pool.shape[2]
    return pool[page_table].astype(jnp.float32).reshape(
        S, npp * ps, num_heads, width // num_heads)


def paged_attention_reference(q, k_pool, v_pool, page_table, lengths,
                              sm_scale=None):
    """Composed XLA path: gather each slot's pages through the table
    into a dense ``[S, pages_per_slot * page_size, H, dh]`` view, mask
    positions past the slot's length, softmax, weighted sum. Empty
    slots (length 0) return 0, matching the kernel.

    q: [S, H, dh]; k_pool/v_pool: [P, page_size, H * dh];
    page_table: [S, npp] int; lengths: [S] int. Returns [S, H, dh].
    """
    S, H, dh = q.shape
    if sm_scale is None:
        sm_scale = dh ** -0.5
    ks = _gather_slot_rows(k_pool, page_table, H)
    vs = _gather_slot_rows(v_pool, page_table, H)
    s = jnp.einsum("shd,sthd->sht", q.astype(jnp.float32) * sm_scale,
                   ks, preferred_element_type=jnp.float32)
    pos = jnp.arange(ks.shape[1])[None, None, :]
    valid = pos < lengths[:, None, None]
    s = jnp.where(valid, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("sht,sthd->shd", p, vs)
    dead = (lengths <= 0)[:, None, None]
    return jnp.where(dead, 0.0, out).astype(q.dtype)


def _head_indicator(num_heads, head_dim):
    """``E [H * dh, H]``, ``E[j, h] = 1`` where lane ``j`` of a token's
    row belongs to head ``h``, and its transpose."""
    e = (jnp.arange(num_heads * head_dim)[:, None] // head_dim
         == jnp.arange(num_heads)[None, :]).astype(jnp.float32)
    return e, e.T


def _head_dot(x, e):
    """``x @ e`` for a 0/1 head indicator (or its transpose), at
    HIGHEST: the product is exact float32 sums of float32 terms, as a
    per-head reduction on the vector unit would be (DEFAULT would round
    ``x`` to bfloat16 first). Rows pad to whole sublane tiles."""
    rows = x.shape[0]
    pad = -rows % 8
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad, x.shape[1]), x.dtype)], axis=0)
    out = jnp.dot(x, e, preferred_element_type=jnp.float32,
                  precision=jax.lax.Precision.HIGHEST)
    return out[:rows] if pad else out


def _start_slot(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _absorb_page(q, k_ref, v_ref, visible, e_ref, et_ref, acc_ref, m_ref,
                 l_ref):
    """Absorb one resident K/V page into N query rows' online-softmax
    state: the running max a head ``m [N, 1, H]``, the running sum and
    acc a lane ``l``, ``acc [N, H*dh]``, in VMEM scratch persisting
    across the page dimension. ``q`` [N, H*dh] is scaled already;
    ``visible`` [N, ps, 1] says which of the page's rows a query row
    sees. A row that sees no key of the page adds nothing (its
    exp(-inf - -inf) would otherwise count every key), so a slot or node
    with no visible key at all ends at acc 0."""
    N, width = q.shape
    k = k_ref[0].astype(jnp.float32)                     # [ps, H*dh]
    v = v_ref[0].astype(jnp.float32)
    ps = k.shape[0]
    H = e_ref.shape[-1]
    prod = k[None, :, :] * q[:, None, :]                 # [N, ps, H*dh]
    sc = _head_dot(prod.reshape(N * ps, width),
                   e_ref[...]).reshape(N, ps, H)
    sc = jnp.where(visible, sc, _NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
    pexp = jnp.where(visible, jnp.exp(sc - m_new), 0.0)
    w = _head_dot(pexp.reshape(N * ps, H),
                  et_ref[...]).reshape(N, ps, width)
    alpha = _head_dot(jnp.exp(m_prev - m_new)[:, 0, :], et_ref[...])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(w, axis=1)
    acc_ref[...] = acc_ref[...] * alpha + jnp.sum(w * v[None], axis=1)
    m_ref[...] = m_new


def _finish_slot(o_ref, acc_ref, l_ref):
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                ).astype(o_ref.dtype)


def _paged_decode_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, e_ref,
                         et_ref, o_ref, acc_ref, m_ref, l_ref, *,
                         page_size, n_pages, sm_scale):
    """One (slot, page) grid step of the decode: the slot's one query
    row absorbs one resident page (``_absorb_page``). ``table_ref`` and
    ``len_ref`` are the scalar-prefetch operands — the page table
    already steered the K/V index maps; the kernel only needs the length
    for the validity test and the empty-page skip."""
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    p = pl.program_id(1)
    pl.when(p == 0)(lambda: _start_slot(acc_ref, m_ref, l_ref))
    length = len_ref[s]

    def _compute():
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size, 1), 1)
        _absorb_page(q_ref[0].astype(jnp.float32) * sm_scale, k_ref,
                     v_ref, pos < length, e_ref, et_ref, acc_ref, m_ref,
                     l_ref)

    # the ragged bound: a page past the slot's resident length runs NO
    # compute (and, with the host's last-valid-page table aliasing, no
    # fresh DMA either — the repeated index elides the copy)
    pl.when(p * page_size < length)(_compute)
    pl.when(p == n_pages - 1)(
        lambda: _finish_slot(o_ref, acc_ref, l_ref))


def _paged_pallas(q, k_pool, v_pool, page_table, lengths, sm_scale,
                  interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, dh = q.shape
    ps, width = k_pool.shape[1], k_pool.shape[2]
    npp = page_table.shape[1]
    e, et = _head_indicator(H, dh)
    kv_spec = pl.BlockSpec(
        (1, ps, width), lambda s, p, table, lens: (table[s, p], 0, 0))
    row_spec = pl.BlockSpec(
        (1, 1, width), lambda s, p, table, lens: (s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, npp),
        in_specs=[
            row_spec,
            kv_spec,
            kv_spec,
            # whole in VMEM for the call: no index map a grid step
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((1, width), jnp.float32),
            pltpu.VMEM((1, 1, H), jnp.float32),
            pltpu.VMEM((1, width), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, page_size=ps, n_pages=npp,
            sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, 1, width), q.dtype),
        interpret=interpret,
        name=PAGED_KERNEL_NAME,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      q.reshape(S, 1, width), k_pool, v_pool, e, et)
    return out.reshape(S, H, dh)


def _check_pool(q, k_pool):
    if k_pool.ndim != 3 or k_pool.shape[2] != q.shape[1] * q.shape[-1]:
        raise ValueError(
            "a self-attention page pool is [num_pages, page_size, H * dh] "
            "(whole token rows); got pool %s for %d heads of %d"
            % (tuple(k_pool.shape), q.shape[1], q.shape[-1]))


def paged_attention(q, k_pool, v_pool, page_table, lengths, sm_scale=None,
                    force_reference=False, force_pallas=False):
    """Ragged paged-attention decode over a block-paged KV pool.

    q: [S, H, dh] (one query token per slot); k_pool/v_pool:
    [num_pages, page_size, H * dh]; page_table: [S, pages_per_slot] int
    page ids into the pool; lengths: [S] int resident tokens per slot.
    Returns [S, H, dh]. Slots with length 0 return exactly 0.

    Routing mirrors ``flash_attention``: the Pallas kernel on TPU
    targets (``interpret=True`` when forced on CPU), the composed
    gather+softmax reference elsewhere or under
    ``FLAGS_paged_attention=reference``. A kernel the compiler refuses
    raises ``KernelCompileError``; nothing stands in for it.
    """
    _check_pool(q, k_pool)
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    use_pallas = force_pallas or (not force_reference and _is_tpu_target())
    if not use_pallas:
        return paged_attention_reference(
            q, k_pool, v_pool, page_table, lengths, sm_scale=sm_scale)
    try:
        return _paged_pallas(q, k_pool, v_pool, page_table, lengths,
                             sm_scale, interpret=not _is_tpu_target())
    except Exception as exc:
        raise KernelCompileError(
            PAGED_KERNEL_NAME, (q, k_pool, v_pool, page_table, lengths),
            exc) from exc


def token_rows(x, dtype):
    """``[..., H, dh]`` per-head rows as the pool's ``[..., H * dh]``."""
    return x.reshape(x.shape[:-2] + (-1,)).astype(dtype)


def paged_kv_write(k_pool, v_pool, k_new, v_new, page_table, positions):
    """O(page) cache write: scatter each slot's new K/V row into its
    resident page at ``positions[s]`` — page id resolved through the
    table (``table[s, pos // page_size]``), offset ``pos % page_size``.
    Replaces the dense path's one-hot select-and-add over the whole T
    axis. k_new/v_new: [S, H, dh]; returns the updated pools.

    Slots whose table row points at the reserved trash page (page 0 by
    the session's convention) scatter harmlessly there — an unoccupied
    slot's write can never corrupt a live slot's page.
    """
    ps = k_pool.shape[1]
    S = k_new.shape[0]
    pos = positions.astype(jnp.int32)
    page_ids = page_table[jnp.arange(S), pos // ps]
    offsets = pos % ps
    k_pool = k_pool.at[page_ids, offsets].set(
        token_rows(k_new, k_pool.dtype))
    v_pool = v_pool.at[page_ids, offsets].set(
        token_rows(v_new, v_pool.dtype))
    return k_pool, v_pool


def paged_tree_attention_reference(q, k_pool, v_pool, page_table,
                                   base_lens, anc, sm_scale=None,
                                   max_length=None):
    """Composed XLA path for speculative tree verify: each slot holds
    ``base_lens[s]`` committed rows at storage positions ``0..base-1``
    plus N speculation-tree nodes laid out LINEARLY in its write pages
    at storage positions ``base..base+N-1`` (node 0 is the anchor
    token). Query node ``n`` attends every committed row plus exactly
    the tree rows on its own root path — ``anc[s, n, j]`` nonzero
    (``anc`` includes the diagonal: a node sees its own just-written
    row, the decode-step contract).

    q: [S, H, N, dh]; k_pool/v_pool: [P, page_size, H * dh];
    page_table: [S, npp] int; base_lens: [S] int (-1 marks a dead/done
    slot — no visible key, output exactly 0); anc: [S, N, N] 0/1.
    Tree rows whose storage position falls at/after ``max_length``
    were trash-routed at write time and are masked here. Returns
    [S, H, N, dh].
    """
    S, H, N, dh = q.shape
    if sm_scale is None:
        sm_scale = dh ** -0.5
    ks = _gather_slot_rows(k_pool, page_table, H)
    vs = _gather_slot_rows(v_pool, page_table, H)
    L = ks.shape[1]
    if max_length is None:
        max_length = L
    s = jnp.einsum("shnd,sthd->shnt", q.astype(jnp.float32) * sm_scale,
                   ks, preferred_element_type=jnp.float32)  # [S,H,N,L]
    t = jnp.arange(L)[None, :]                              # [1, L]
    base = base_lens.astype(jnp.int32)[:, None]             # [S, 1]
    committed = (t < base)                                  # [S, L]
    tj = t - base                                           # [S, L]
    in_tree = (tj >= 0) & (tj < N) & (t < int(max_length)) & (base >= 0)
    tj_c = jnp.clip(tj, 0, N - 1)
    anc_g = (anc.astype(jnp.int32) > 0)[
        jnp.arange(S)[:, None, None],
        jnp.arange(N)[None, :, None],
        tj_c[:, None, :]]                                   # [S, N, L]
    visible = committed[:, None, :] | (in_tree[:, None, :] & anc_g)
    vis4 = visible[:, None, :, :]                           # [S,1,N,L]
    s = jnp.where(vis4, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("shnt,sthd->shnd", p, vs)
    dead = jnp.logical_not(jnp.any(vis4, axis=-1))[..., None]
    return jnp.where(dead, 0.0, out).astype(q.dtype)


def _tree_decode_kernel(table_ref, blen_ref, q_ref, k_ref, v_ref,
                        anc_ref, e_ref, et_ref, o_ref, acc_ref, m_ref,
                        l_ref, *, page_size, n_pages, n_nodes, max_len,
                        sm_scale):
    """One (slot, page) grid step of the tree verify: N query rows (one
    per tree node) absorb one resident page. Same ragged discipline as
    ``_paged_decode_kernel`` — the scan bound is ``base + N`` (capped at
    ``max_len``), pages past it skip compute and (via table tail
    aliasing) DMA. A node sees the in-tree storage position ``base + j``
    where ``anc[n, j]`` is set: a compare of ``t - base`` against ``j``
    on the lanes, no gather."""
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    p = pl.program_id(1)
    N, ps = n_nodes, page_size
    pl.when(p == 0)(lambda: _start_slot(acc_ref, m_ref, l_ref))
    base = blen_ref[s]
    scan_len = jnp.where(base >= 0,
                         jnp.minimum(base + n_nodes, max_len), 0)

    def _compute():
        # page positions on the sublanes, as the scores have them
        tcol = p * ps + jax.lax.broadcasted_iota(
            jnp.int32, (N, ps, N), 1)                    # t
        jlane = jax.lax.broadcasted_iota(jnp.int32, (N, ps, N), 2)
        on_path = ((tcol - base == jlane)
                   & (anc_ref[0].astype(jnp.int32) > 0)[:, None, :])
        treevis = jnp.max(on_path.astype(jnp.float32), axis=-1,
                          keepdims=True) > 0.5           # [N, ps, 1]
        t1 = tcol[:, :, :1]
        visible = (t1 < base) | (treevis & (t1 < max_len))
        _absorb_page(q_ref[0].astype(jnp.float32) * sm_scale, k_ref,
                     v_ref, visible, e_ref, et_ref, acc_ref, m_ref, l_ref)

    pl.when(p * page_size < scan_len)(_compute)
    pl.when(p == n_pages - 1)(
        lambda: _finish_slot(o_ref, acc_ref, l_ref))


def _tree_pallas(q, k_pool, v_pool, page_table, base_lens, anc,
                 sm_scale, max_length, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, N, dh = q.shape
    ps, width = k_pool.shape[1], k_pool.shape[2]
    npp = page_table.shape[1]
    e, et = _head_indicator(H, dh)
    kv_spec = pl.BlockSpec(
        (1, ps, width), lambda s, p, table, lens: (table[s, p], 0, 0))
    rows_spec = pl.BlockSpec(
        (1, N, width), lambda s, p, table, lens: (s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, npp),
        in_specs=[
            rows_spec,
            kv_spec,
            kv_spec,
            pl.BlockSpec((1, N, N), lambda s, p, table, lens: (s, 0, 0)),
            # whole in VMEM for the call: no index map a grid step
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=rows_spec,
        scratch_shapes=[
            pltpu.VMEM((N, width), jnp.float32),
            pltpu.VMEM((N, 1, H), jnp.float32),
            pltpu.VMEM((N, width), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _tree_decode_kernel, page_size=ps, n_pages=npp, n_nodes=N,
            max_len=int(max_length), sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, N, width), q.dtype),
        interpret=interpret,
        name=TREE_KERNEL_NAME,
    )(page_table.astype(jnp.int32), base_lens.astype(jnp.int32),
      jnp.transpose(q, (0, 2, 1, 3)).reshape(S, N, width), k_pool, v_pool,
      anc.astype(jnp.int32), e, et)
    return jnp.transpose(out.reshape(S, N, H, dh), (0, 2, 1, 3))


def paged_tree_attention(q, k_pool, v_pool, page_table, base_lens, anc,
                         sm_scale=None, max_length=None,
                         force_reference=False, force_pallas=False):
    """Speculative tree verify over the paged pool: one dispatch scores
    all N tree nodes of every slot against its committed rows plus the
    node's own root path (see ``paged_tree_attention_reference`` for
    the full layout contract). Routing mirrors ``paged_attention``:
    Pallas on TPU targets, composed reference on CPU or under
    ``FLAGS_tree_attention=reference``; a kernel the compiler refuses
    raises ``KernelCompileError``."""
    _check_pool(q, k_pool)
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if max_length is None:
        max_length = page_table.shape[1] * k_pool.shape[1]
    use_pallas = force_pallas or (not force_reference and _is_tpu_target())
    if not use_pallas:
        return paged_tree_attention_reference(
            q, k_pool, v_pool, page_table, base_lens, anc,
            sm_scale=sm_scale, max_length=max_length)
    try:
        return _tree_pallas(q, k_pool, v_pool, page_table, base_lens,
                            anc, sm_scale, max_length,
                            interpret=not _is_tpu_target())
    except Exception as exc:
        raise KernelCompileError(
            TREE_KERNEL_NAME,
            (q, k_pool, v_pool, page_table, base_lens, anc), exc) from exc


def paged_kv_write_block(k_pool, v_pool, k_new, v_new, page_table,
                         positions):
    """Speculative tree write: scatter N K/V rows per slot into its
    resident pages — row ``i`` of slot ``s`` lands at storage position
    ``positions[s, i]`` through the table. Rows whose position falls
    outside the table's coverage (``pos >= npp * page_size``) route to
    the reserved trash page instead of clobbering a live row, the same
    safety valve as a done slot's all-trash table row.

    k_new/v_new: [S, H, N, dh]; positions: [S, N]. Returns the updated
    pools.
    """
    ps = k_pool.shape[1]
    S = k_new.shape[0]
    npp = page_table.shape[1]
    pos = positions.astype(jnp.int32)
    in_range = pos < npp * ps
    page_idx = jnp.clip(pos // ps, 0, npp - 1)
    page_ids = jnp.where(in_range,
                         page_table[jnp.arange(S)[:, None], page_idx], 0)
    offsets = jnp.where(in_range, pos % ps, 0)
    k_rows = token_rows(jnp.transpose(k_new, (0, 2, 1, 3)), k_pool.dtype)
    v_rows = token_rows(jnp.transpose(v_new, (0, 2, 1, 3)), v_pool.dtype)
    k_pool = k_pool.at[page_ids, offsets].set(k_rows)
    v_pool = v_pool.at[page_ids, offsets].set(v_rows)
    return k_pool, v_pool


def paged_kv_compact(k_pool, v_pool, page_table, base, path, accept_len):
    """Survivor commit of the accepted tree path: after the accept walk
    picks node ``path[s, j]`` as the backer of committed token ``j``,
    its K/V row moves from storage ``base + path[j]`` to the canonical
    position ``base + j`` (an in-page row gather; page identity itself
    is handled by the host's refcount rebinds). Rows at/after
    ``accept_len`` and the anchor (j=0, already canonical) are
    untouched — their writes route to the trash page. All gathers read
    the pre-compaction pool (functional scatter), so an overlapping
    src/dst pattern can never read a clobbered row.

    base: [S] int (committed rows; -1 for dead slots), path: [S, N]
    node indices, accept_len: [S] int. Returns the updated pools.
    """
    ps = k_pool.shape[1]
    S, N = path.shape
    npp = page_table.shape[1]
    L = npp * ps
    j_idx = jnp.arange(N)[None, :]
    base_i = base.astype(jnp.int32)[:, None]
    src_pos = base_i + path.astype(jnp.int32)
    dst_pos = base_i + j_idx
    active = ((j_idx >= 1) & (j_idx < accept_len.astype(jnp.int32)[:, None])
              & (dst_pos < L) & (src_pos < L) & (base_i >= 0)
              & (path.astype(jnp.int32) != j_idx))
    sp = jnp.clip(src_pos, 0, L - 1)
    s_page = page_table[jnp.arange(S)[:, None], sp // ps]
    s_off = sp % ps
    k_rows = k_pool[s_page, s_off]                          # [S,N,H*dh]
    v_rows = v_pool[s_page, s_off]
    dp = jnp.clip(dst_pos, 0, L - 1)
    d_page = jnp.where(active,
                       page_table[jnp.arange(S)[:, None], dp // ps], 0)
    d_off = jnp.where(active, dp % ps, 0)
    k_pool = k_pool.at[d_page, d_off].set(k_rows)
    v_pool = v_pool.at[d_page, d_off].set(v_rows)
    return k_pool, v_pool


def grid_accounting(lengths, page_size, num_heads, head_dim,
                    max_length, itemsize=4, num_groups=None,
                    n_layer=1, src_length=None):
    """Model the decode kernel's HBM traffic from its own grid
    semantics: one K page + one V page DMA'd per RESIDENT page (the
    ``pl.when`` skip + last-valid-page table aliasing elide both
    compute and copy for pages past a slot's length), plus the
    [S, H, dh] query/output blocks. ``dense_hbm_bytes`` is what the
    dense slot pool moves for the same step — every slot's full
    ``[H, max_length, dh]`` K and V regardless of occupancy — so the
    ratio IS the raggedness: bytes proportional to tokens actually
    resident, not ``S x max_length``.

    With ``num_groups`` set, the dict also models the GROUP-POOLED
    cross-attention K/V (PR 12's cross-request reuse): cross state is
    ``[G, H, T_src, dh]`` per layer, priced per GROUP
    (``cross_hbm_bytes``) against the per-slot dense layout
    (``cross_dense_hbm_bytes`` — what ``S`` unshared rows cost), so
    the accounted bytes scale with admitted SOURCES, not decoding
    slots. ``n_layer`` multiplies both cross terms (each decoder layer
    holds its own pools); ``src_length`` defaults to ``max_length``.
    """
    lengths = [int(x) for x in lengths]
    S = len(lengths)
    page_bytes = num_heads * int(page_size) * head_dim * itemsize
    valid_pages = sum(pages_for(ln, page_size) for ln in lengths)
    total_page_slots = S * pages_for(max_length, page_size)
    qo_bytes = 2 * S * num_heads * head_dim * itemsize
    kv_bytes = 2 * valid_pages * page_bytes
    dense_kv = 2 * S * num_heads * int(max_length) * head_dim * itemsize
    out = {
        "valid_pages": valid_pages,
        "total_page_slots": total_page_slots,
        "page_bytes": page_bytes,
        "hbm_bytes": kv_bytes + qo_bytes,
        "dense_hbm_bytes": dense_kv + qo_bytes,
        "resident_tokens": sum(lengths),
        "dense_tokens": S * int(max_length),
    }
    if num_groups is not None:
        t_src = int(src_length if src_length is not None else max_length)
        cross_row = 2 * num_heads * t_src * head_dim * itemsize
        out["cross_hbm_bytes"] = int(n_layer) * int(num_groups) * cross_row
        out["cross_dense_hbm_bytes"] = int(n_layer) * S * cross_row
    return out
