"""Ragged paged-attention decode: block-paged KV pool + Pallas kernel.

The serving decode step's attention (PAPERS.md "Ragged Paged
Attention", arxiv 2604.15464): ``SlotDecodeSession``'s dense slot pool
attends over all ``max_length`` positions for every slot regardless of
how many tokens a slot actually holds, so decode FLOPs/HBM traffic
scale with ``num_slots x max_length``. Here the KV cache is a PAGE
POOL — fixed-size pages ``[num_pages, page_size, H * dh]``, a token's
row of all heads contiguous — plus a per-slot page-index table
``[S, pages_per_slot]`` and a length vector ``[S]`` — and the decode
kernel is ragged over it:

* Grid ``(slot,)`` with the page table and the lengths scalar-prefetched
  (``pltpu.PrefetchScalarGridSpec``) and the pools left in HBM
  (``memory_space=pl.ANY``): a grid step is a slot, and the slot's
  RESIDENT pages, ``ceil(length[s] / page_size)`` of them, are walked by
  a loop inside the body (``_walk_resident_pages``), each fetched by the
  kernel's own double-buffered copy of ``pool[table[s, p]]`` while the
  page before it is absorbed; a slot's first page is started under the
  last page of the slot before it.
* Per-slot lengths bound the walk: a page at ``p * page_size >=
  length[s]`` is never copied, never computed on and its table entry
  never read, and a slot of length 0 costs its grid step and nothing
  else (a grid step costs ~0.11 us even when it skips, so a grid over
  all ``S x pages_per_slot`` pairs is ~0.46 ms of a call at the served
  shapes whatever is resident: PERF.md section 6, PR 30 and PR 41).
  Decode traffic AND grid steps follow pages actually RESIDENT, not
  ``S x max_length``: ``grid_accounting`` models exactly that contract
  and the bench/CI legs pin it. The kernels do not need the host's fill
  of a slot's unprovisioned table tail with its LAST valid page id.
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
from paddle_tpu.kernels.flash_attention import _is_tpu_target, _mosaic_params


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


def _walk_resident_pages(table_ref, slot, pages_of, pools, bufs, sem,
                         ahead_ref, absorb, group=1, column_of=None):
    """Walk one slot's resident pages INSIDE a kernel body whose grid is
    ``(slots,)``, ``group`` pages a step of the walk. ``pages_of(slot)``
    is how many of the slot's pages are resident; page ``p`` of the slot
    is ``table_ref[slot, p]`` of every pool in ``pools`` (refs left in
    HBM, ``[P, page_size, width]``), or ``table_ref[slot, column_of(slot,
    p)]`` where the table is not in page order (the one hook: a RING's
    ``p``-th resident page sits in column ``(first page + p) % R``),
    fetched by the kernel's own
    copy into one half of the matching ``bufs`` scratch (``[2, group *
    page_size, width]``) while the pages before it are absorbed from the
    other half; ``sem`` is a DMA semaphore a pool and half
    (``[len(pools), 2]``), which a half's ``group`` copies share.
    ``absorb(g, *group_refs)`` gets the step's index in the slot (its
    first page is ``g * group``) and a ``[1, group * page_size, width]``
    ref a pool. The copies of a step are started together; a slot's last
    step may hold fewer than ``group`` resident pages: the others are
    not copied and their rows of the half are set to ZERO before
    ``absorb`` sees them (what the half held there is whatever an
    earlier step left, and ``0 x NaN`` is NaN in a product that masks
    only its scores).

    The halves alternate over the whole CALL, not a slot: ``ahead_ref``
    (SMEM ``int32[2]``, kept from grid step to grid step, which
    therefore run in order) holds the steps walked so far and whether
    this slot's first pages are in flight already, started by the slot
    before it under its own last step. Above the knee every slot is live
    with a page or two, and a first page fetched with nothing to hide
    behind was a quarter of the call (0.316 -> 0.234 ms at 256 live, my
    chip run, PR 41).

    Only resident table entries are ever read: a slot with none issues
    no copy and runs no ``absorb``, and what the table holds past a
    slot's resident pages is never looked at."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G = int(group)
    ps = bufs[0].shape[1] // G

    @pl.when(slot == 0)
    def _first_slot():
        ahead_ref[0] = 0
        ahead_ref[1] = 0

    def resident(of_slot):
        # never past the table's row, whatever length a caller hands in
        return jnp.minimum(pages_of(of_slot), table_ref.shape[1])

    # at one page a step the walk traces to what it was before it took a
    # group (PR 50): no arithmetic on the page's index, whole halves
    n = resident(slot)
    steps = n if G == 1 else (n + G - 1) // G

    def for_copies(of_slot, pages, g, half, act):
        """``act`` on the copy of every resident page of step ``g`` of a
        slot with ``pages`` resident: the step's first page is resident
        or the step would not be walked."""
        for j in range(G):
            p = g if G == 1 else g * G + j

            def one(p=p, j=j):
                page = table_ref[
                    of_slot, p if column_of is None else column_of(of_slot, p)]
                for i, (pool, buf) in enumerate(zip(pools, bufs)):
                    dst = (buf.at[half] if G == 1
                           else buf.at[half, pl.ds(j * ps, ps)])
                    act(pltpu.make_async_copy(pool.at[page], dst,
                                              sem.at[i, half]))

            if j == 0:
                one()
            else:
                pl.when(p < pages)(one)

    def start(of_slot, pages, g, half):
        for_copies(of_slot, pages, g, half, lambda c: c.start())

    @pl.when(n > 0)
    def _walk():
        walked = ahead_ref[0]
        n_slots = pl.num_programs(0)
        nxt = jnp.minimum(slot + 1, n_slots - 1)
        n_next = jnp.where(slot + 1 < n_slots, resident(nxt), 0)

        @pl.when(ahead_ref[1] == 0)
        def _first_pages():
            start(slot, n, 0, walked % 2)

        def body(g, carry):
            half = (walked + g) % 2

            @pl.when(g + 1 < steps)
            def _next_pages():
                start(slot, n, g + 1, 1 - half)

            @pl.when((g + 1 == steps) & (n_next > 0))
            def _next_slot():
                start(nxt, n_next, 0, 1 - half)

            for_copies(slot, n, g, half, lambda c: c.wait())
            for j in range(1, G):
                @pl.when(g * G + j >= n)
                def _not_resident(j=j):
                    for buf in bufs:
                        buf[pl.ds(half, 1), pl.ds(j * ps, ps), :] = (
                            jnp.zeros((1, ps, buf.shape[2]), buf.dtype))

            absorb(g, *[buf.at[pl.ds(half, 1)] for buf in bufs])
            return carry

        jax.lax.fori_loop(0, steps, body, 0)
        ahead_ref[0] = walked + steps
        ahead_ref[1] = (n_next > 0).astype(jnp.int32)


def _walk_scratch(*pools, group=1):
    """The scratch ``_walk_resident_pages`` needs for ``pools`` (a K and
    a V pool, or one pool of latent rows): two halves of ``group`` pages
    a pool, a DMA semaphore a pool and half, its state."""
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM((2, group * pool.shape[1], pool.shape[2]),
                       pool.dtype) for pool in pools] + [
        pltpu.SemaphoreType.DMA((len(pools), 2)),
        pltpu.SMEM((2,), jnp.int32),
    ]


def _paged_decode_kernel(table_ref, len_ref, q_ref, k_hbm, v_hbm, e_ref,
                         et_ref, o_ref, k_buf, v_buf, sem, ahead_ref,
                         acc_ref, m_ref, l_ref, *, page_size, sm_scale):
    """One grid step is one SLOT of the decode: its one query row absorbs
    the slot's resident pages (``_absorb_page``) in table order, walked
    by ``_walk_resident_pages``. ``table_ref`` and ``len_ref`` are the
    scalar-prefetch operands; the pools stay in HBM."""
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    length = len_ref[s]
    _start_slot(acc_ref, m_ref, l_ref)
    q = q_ref[0].astype(jnp.float32) * sm_scale

    def absorb(p, k_ref, v_ref):
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size, 1), 1)
        _absorb_page(q, k_ref, v_ref, pos < length, e_ref, et_ref, acc_ref,
                     m_ref, l_ref)

    # the ragged bound: ceil(length / page_size) pages, 0 for an empty slot
    _walk_resident_pages(
        table_ref, s,
        lambda slot: (len_ref[slot] + page_size - 1) // page_size,
        (k_hbm, v_hbm), (k_buf, v_buf), sem, ahead_ref, absorb)
    _finish_slot(o_ref, acc_ref, l_ref)


def _paged_pallas(q, k_pool, v_pool, page_table, lengths, sm_scale,
                  interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, dh = q.shape
    ps, width = k_pool.shape[1], k_pool.shape[2]
    e, et = _head_indicator(H, dh)
    row_spec = pl.BlockSpec(
        (1, 1, width), lambda s, table, lens: (s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[
            row_spec,
            # the pools stay where they are: the body copies a page
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            # whole in VMEM for the call: no index map a grid step
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=row_spec,
        scratch_shapes=_walk_scratch(k_pool, v_pool) + [
            pltpu.VMEM((1, width), jnp.float32),
            pltpu.VMEM((1, 1, H), jnp.float32),
            pltpu.VMEM((1, width), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, page_size=ps, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, 1, width), q.dtype),
        interpret=interpret,
        name=PAGED_KERNEL_NAME,
        **_mosaic_params(interpret, ("arbitrary",)),
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


def _tree_decode_kernel(table_ref, blen_ref, q_ref, k_hbm, v_hbm,
                        anc_ref, e_ref, et_ref, o_ref, k_buf, v_buf, sem,
                        ahead_ref, acc_ref, m_ref, l_ref, *, page_size,
                        n_nodes, max_len, sm_scale):
    """One grid step is one slot of the tree verify: N query rows (one
    per tree node) absorb the slot's resident pages. Same ragged
    discipline as ``_paged_decode_kernel``: the scan bound is
    ``base + N`` (capped at ``max_len``) and only the pages under it are
    walked. A node sees the in-tree storage position ``base + j`` where
    ``anc[n, j]`` is set: a compare of ``t - base`` against ``j`` on the
    lanes, no gather."""
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    N, ps = n_nodes, page_size
    _start_slot(acc_ref, m_ref, l_ref)
    base = blen_ref[s]
    q = q_ref[0].astype(jnp.float32) * sm_scale

    def pages_of(slot):
        committed = blen_ref[slot]
        scan_len = jnp.where(
            committed >= 0, jnp.minimum(committed + n_nodes, max_len), 0)
        return (scan_len + ps - 1) // ps

    def absorb(p, k_ref, v_ref):
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
        _absorb_page(q, k_ref, v_ref, visible, e_ref, et_ref, acc_ref,
                     m_ref, l_ref)

    _walk_resident_pages(
        table_ref, s, pages_of, (k_hbm, v_hbm), (k_buf, v_buf), sem,
        ahead_ref, absorb)
    _finish_slot(o_ref, acc_ref, l_ref)


def _tree_pallas(q, k_pool, v_pool, page_table, base_lens, anc,
                 sm_scale, max_length, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, N, dh = q.shape
    ps, width = k_pool.shape[1], k_pool.shape[2]
    e, et = _head_indicator(H, dh)
    rows_spec = pl.BlockSpec(
        (1, N, width), lambda s, table, lens: (s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[
            rows_spec,
            # the pools stay where they are: the body copies a page
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, N, N), lambda s, table, lens: (s, 0, 0)),
            # whole in VMEM for the call: no index map a grid step
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=rows_spec,
        scratch_shapes=_walk_scratch(k_pool, v_pool) + [
            pltpu.VMEM((N, width), jnp.float32),
            pltpu.VMEM((N, 1, H), jnp.float32),
            pltpu.VMEM((N, width), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _tree_decode_kernel, page_size=ps, n_nodes=N,
            max_len=int(max_length), sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, N, width), q.dtype),
        interpret=interpret,
        name=TREE_KERNEL_NAME,
        **_mosaic_params(interpret, ("arbitrary",)),
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
    """Model the decode kernel's work from its own grid semantics:
    ``grid_steps`` grid steps, one a slot, and ``page_walks`` turns of
    the walk inside them, one a RESIDENT page, each copying one K page
    and one V page (a page past a slot's length is never copied or
    computed on; ``total_page_slots`` is what a (slot, page) grid
    would step through), plus the
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
        "grid_steps": S,
        "page_walks": valid_pages,
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
