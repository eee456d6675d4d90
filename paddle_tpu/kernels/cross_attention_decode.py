"""Group-indexed cross attention for the decode step, as a kernel of its
own: the flash forward's online softmax at a few query rows a slot.

The paged decode session keeps the encoder's K/V per GROUP
(``[G, H, T_src, dh]``: one row per admitted source, however many slots
decode continuations of it) and each slot reaches its group's row
through ``group_of[s]``. Until PR 28 the op gathered ``k_pool[group_of]``
whole (a Pallas custom call cannot take a fused operand, so two
``[S, H, T_src, dh]`` copies were made a call) and walked them with the
training flash kernel at ``block_q = 1``: a grid of ``S x H x T_src /
128`` one-row tiles. Here:

* Grid ``(slot, source block)`` with ``group_of`` and every slot's source
  length scalar-prefetched (``pltpu.PrefetchScalarGridSpec``): the K/V
  index maps resolve ``group_of[s]`` and the block ``j`` straight into
  the pools, so no gathered copy exists, and one grid step takes ALL
  heads of a slot (as the paged kernel takes a page of whole token rows).
* The query keeps its row axis, ``[S, H, N, dh]``: N = 1 in the step
  program, the tree's node count in the tree-verify program. The kernel
  adapts on the shapes it sees; ``source_block`` picks the block of the
  source axis from them so that K and V, double-buffered, stay inside
  ``VMEM_BUDGET_BYTES``.
* The blocks are taken the way the chip STORES the pools. A
  ``[G, H, T_src, dh]`` float32 array whose ``dh`` is not a whole number
  of 128-lane tiles (64 at the published widths) is kept by the TPU with
  the source axis minor (no padded lanes), so a row-major ``[H, block,
  dh]`` operand would cost a transposing copy of the whole pool a call
  (what the old path's ``copy`` after its gather was). ``lanes_hold_source``
  says which it is from ``dh`` alone; then the kernel takes the
  ``[G, H, dh, T_src]`` view (a bitcast, compiled for a v5e) and blocks
  of ``[H, dh, block]``. At ``dh`` a multiple of 128 the pools are
  row-major and so are the blocks.
* Validity is a length a group (the mask rows are ``sequence_mask``
  rows, prefix-valid): blocks past a slot's length skip their compute
  (``pl.when``) and repeat the last valid block's index, so the pipeline
  issues no copy for them, and neither for a slot whose predecessor in
  the grid read the same block of the same group (N best-of-N slots in a
  row cost one group's row). A slot with no valid key returns exactly 0.
* A DEAD slot (``live`` 0: its stream has ended, or it never held one)
  has length 0 whatever its stale group says, and a slot of length 0
  copies nothing either: ``steer_dead_slots`` rewrites its K/V index to
  the block the last live slot before it ended on, so the pipeline sees
  an unchanged index (4-8 of 256 slots hold a stream below the knee).
  Its step does not touch the softmax state either: it stores zeros.
* float32 rows and accumulators, the two products at the ambient matmul
  precision, as ``_flash_kernel`` has them.

The ``pallas_call`` is named ``flash_attention_fwd_decode``: it IS the
flash forward at query length N, and the trace's readers find it by that
stem and its ``[S, H, N, dh]`` result. ``grid_accounting`` is the twin of
``paged_attention.grid_accounting``: what one call's grid reads, from the
host's view of ``group_of`` and the lengths.
"""

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.flash_attention import (
    FWD_KERNEL_NAME, _is_tpu_target, _mosaic_params,
    flash_attention_reference)
from paddle_tpu.kernels.paged_attention import (
    KernelCompileError, pages_for)

CROSS_DECODE_KERNEL_NAME = FWD_KERNEL_NAME + "_decode"

_NEG_INF = -1e30
_MASKED_ROW_M = -1e29
_LANES = 128
_SUBLANES = 8
# K and V blocks, two buffers each, as VMEM holds them (the head width
# padded to whole lanes): a quarter of a v5e core's 16 MiB scoped limit,
# the rest is the query, the scores and the compiler's
VMEM_BUDGET_BYTES = 4 << 20


def lanes_hold_source(head_dim):
    """Whether the chip keeps a ``[G, H, T_src, dh]`` pool with the
    source axis on the lanes: its default layout avoids padded lanes, so
    a head width that is not whole lane tiles goes second-minor."""
    return int(head_dim) % _LANES != 0


def _tiles(n, tile):
    return -(-int(n) // tile) * tile


def source_block(num_heads, src_length, head_dim, itemsize=4):
    """The block of the source axis one grid step takes: the largest
    divisor of ``src_length`` that is whole tiles of the axis it lies on
    (lanes or sublanes, by ``lanes_hold_source``), or the whole axis,
    whose K and V blocks, double-buffered, fit ``VMEM_BUDGET_BYTES``.
    Raises ``ValueError`` where none fits."""
    if lanes_hold_source(head_dim):
        tile, row = _LANES, _tiles(head_dim, _SUBLANES)
    else:
        tile, row = _SUBLANES, _tiles(head_dim, _LANES)
    row *= 4 * int(num_heads) * int(itemsize)  # K and V, two buffers each
    T = int(src_length)
    for n in range(1, T + 1):
        block = T // n
        if T % n or (block % tile and n > 1):
            continue
        if _tiles(block, tile) * row <= VMEM_BUDGET_BYTES:
            return block
    raise ValueError(
        "no block of a %d-position source axis with %d heads of %d fits "
        "%d bytes of VMEM" % (T, num_heads, head_dim, VMEM_BUDGET_BYTES))


def grouped_cross_attention_reference(q, k_pool, v_pool, group_of, mask,
                                      sm_scale=None, live=None):
    """Composed XLA path: gather each slot's group row and mask row,
    then the flash reference. A slot whose keys are all masked returns
    the uniform average here and 0 from the kernel (meaningless either
    way, as ``flash_attention`` has it); a slot that ``live`` calls dead
    returns 0 here too."""
    gof = group_of.astype(jnp.int32)
    m = mask[gof][:, None, None, :].astype(bool)  # [S, 1, 1, T_src]
    out = flash_attention_reference(
        q, k_pool[gof], v_pool[gof], sm_scale=sm_scale, mask=m)
    if live is not None:
        out = jnp.where(live[:, None, None, None] > 0, out, 0.0)
    return out


def steer_dead_slots(group_of, slot_len, block):
    """The three vectors the K/V index maps read, all [S] int32:
    ``(group, lo, hi)``, grid step ``(s, j)`` taking block
    ``clip(j, lo[s], hi[s])`` of ``group[s]``'s row. A slot with keys
    keeps its group and walks ``0 .. its last valid block`` (that block
    again past it: no copy). A slot of length 0 takes the group of the
    last slot before it that has keys and ``lo = hi =`` that slot's last
    valid block, which is the index the grid step before it had, so no
    copy is issued for it. Slots ahead of the first one with keys hold
    its first block, the copy it needs anyway (the pipeline always
    copies at its first step). Computed here, on whole vectors, so that
    an index map is two loads, a max and a min."""
    S = slot_len.shape[0]
    has_keys = slot_len > 0
    at = jnp.arange(S, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(has_keys, at, -1))
    donor = jnp.where(before >= 0, before,
                      jnp.argmax(has_keys).astype(jnp.int32))
    last = jnp.maximum((slot_len + block - 1) // block - 1, 0)
    hi = jnp.where(before >= 0, last[donor], 0)
    return group_of[donor], jnp.where(has_keys, 0, hi), hi


def kv_block_index(s, j, lo, hi):
    """The source block grid step ``(s, j)`` takes (``steer_dead_slots``)."""
    return jnp.minimum(jnp.maximum(j, lo[s]), hi[s])


def _cross_decode_kernel(gof_ref, lo_ref, hi_ref, len_ref, q_ref, k_ref,
                         v_ref, o_ref, acc_ref, m_ref, l_ref, *, block,
                         n_blocks, sm_scale, source_minor):
    """One (slot, source block) grid step: absorb one block of the slot's
    group row into the online-softmax state of its N query rows, every
    head at once. ``gof_ref``, ``lo_ref`` and ``hi_ref`` already steered
    the K/V index maps; the body needs the slot's length for the
    validity test and the skip."""
    from jax.experimental import pallas as pl

    del gof_ref, lo_ref, hi_ref
    s = pl.program_id(0)
    j = pl.program_id(1)

    length = len_ref[s]

    @pl.when(jnp.logical_and(j == 0, length > 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute():
        q = q_ref[0].astype(jnp.float32) * sm_scale      # [H, N, dh]
        k = k_ref[0].astype(jnp.float32)   # [H, dh, block] | [H, block, dh]
        v = v_ref[0].astype(jnp.float32)
        kv = "hdt" if source_minor else "htd"
        sc = jnp.einsum("hnd,%s->hnt" % kv, q, k,
                        preferred_element_type=jnp.float32)
        pos = j * block + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 2)
        sc = jnp.where(pos < length, sc, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        pexp = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=-1,
                                                  keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
            "hnt,%s->hnd" % kv, pexp, v,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    pl.when(j * block < length)(_compute)

    @pl.when(jnp.logical_and(j == n_blocks - 1, length > 0))
    def _finish():
        dead = m_ref[...] <= _MASKED_ROW_M
        o_ref[0] = jnp.where(
            dead, 0.0,
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)

    @pl.when(jnp.logical_and(j == n_blocks - 1, length == 0))
    def _no_keys():
        o_ref[0] = jnp.zeros_like(o_ref[0])


def _cross_decode_pallas(q, k_pool, v_pool, group_of, slot_len, sm_scale,
                         interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, N, dh = q.shape
    T = k_pool.shape[2]
    block = source_block(H, T, dh, k_pool.dtype.itemsize)
    n_blocks = T // block

    source_minor = lanes_hold_source(dh)
    if source_minor:
        # the view the chip already holds: a bitcast, no copy
        k_pool = jnp.swapaxes(k_pool, 2, 3)
        v_pool = jnp.swapaxes(v_pool, 2, 3)

    group_of, lo, hi = steer_dead_slots(group_of, slot_len, block)

    def kv_map(s, j, gof, lo, hi, lens):
        jb = kv_block_index(s, j, lo, hi)
        return (gof[s], 0, 0, jb) if source_minor else (gof[s], 0, jb, 0)

    kv_spec = pl.BlockSpec(
        (1, H, dh, block) if source_minor else (1, H, block, dh), kv_map)
    q_spec = pl.BlockSpec((1, H, N, dh),
                          lambda s, j, gof, lo, hi, lens: (s, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S, n_blocks),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((H, N, dh), jnp.float32),
            pltpu.VMEM((H, N, 1), jnp.float32),
            pltpu.VMEM((H, N, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_cross_decode_kernel, block=block,
                          n_blocks=n_blocks, sm_scale=sm_scale,
                          source_minor=source_minor),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, N, dh), q.dtype),
        interpret=interpret,
        name=CROSS_DECODE_KERNEL_NAME,
        **_mosaic_params(interpret, ("parallel", "arbitrary")),
    )(group_of, lo, hi, slot_len, q, k_pool, v_pool)


def grouped_cross_attention(q, k_pool, v_pool, group_of, mask,
                            sm_scale=None, force_reference=False,
                            force_pallas=False, live=None):
    """Each slot's N query rows over its group's source row.

    q: [S, H, N, dh]; k_pool/v_pool: [G, H, T_src, dh]; group_of: [S]
    int group ids; mask: [G, T_src] validity rows, PREFIX-valid (the
    kernel reads a row as its count of valid positions); live: [S] (or
    [S, 1]), nonzero where the slot holds a stream -- a dead slot
    returns exactly 0 and costs neither a copy nor a product, whatever
    group its stale ``group_of`` names. Without it every slot is live.
    Returns [S, H, N, dh].

    Routing is ``flash_attention``'s: the Pallas kernel on TPU targets
    (interpreted when forced on the CPU), the composed reference
    elsewhere or when forced. A kernel the compiler refuses raises
    ``KernelCompileError``; nothing stands in for it.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    gof = jnp.reshape(group_of, (-1,)).astype(jnp.int32)
    if live is not None:
        live = jnp.reshape(live, (-1,))
    use_pallas = force_pallas or (not force_reference and _is_tpu_target())
    if not use_pallas:
        return grouped_cross_attention_reference(
            q, k_pool, v_pool, gof, mask, sm_scale=sm_scale, live=live)
    slot_len = jnp.sum(mask > 0, axis=-1, dtype=jnp.int32)[gof]  # [S]
    if live is not None:
        slot_len = jnp.where(live > 0, slot_len, 0)
    try:
        return _cross_decode_pallas(q, k_pool, v_pool, gof, slot_len,
                                    sm_scale,
                                    interpret=not _is_tpu_target())
    except Exception as exc:
        raise KernelCompileError(
            CROSS_DECODE_KERNEL_NAME, (q, k_pool, v_pool, gof, mask),
            exc) from exc


def grid_accounting(group_of, lengths, num_heads, src_length, head_dim,
                    n_rows=1, itemsize=4, live=None):
    """What one call's grid does, from ``group_of`` ([S] group a slot),
    ``lengths`` ([G] valid positions a group) and ``live`` ([S], nonzero
    where the slot holds a stream; all of them without it): the pipeline
    copies a K and a V block at a grid step whose block index differs
    from the step before it, a step past its slot's length repeats the
    last valid index, and a slot with no key (dead, or of an empty
    group) holds the index ``steer_dead_slots`` gives it. ``blocks_read``
    counts those copies (a K and a V block as one; the first grid step
    always copies), ``blocks_skipped`` the grid steps whose compute did
    not run, ``hbm_bytes`` the blocks read plus the query and output
    rows."""
    block = source_block(num_heads, src_length, head_dim, itemsize)
    n_blocks = int(src_length) // block
    block_bytes = 2 * int(num_heads) * block * int(head_dim) * int(itemsize)
    S = len(group_of)
    valid = [pages_for(lengths[int(g)], block)
             if live is None or live[s] else 0
             for s, g in enumerate(group_of)]
    first = next((s for s in range(S) if valid[s]), None)
    read = skipped = 0
    # the slots ahead of the first one with keys hold ITS first block
    prev, held = None, (int(group_of[first or 0]), 0)
    for s, g in enumerate(int(x) for x in group_of):
        for j in range(n_blocks):
            idx = (g, min(j, valid[s] - 1)) if valid[s] else held
            read += idx != prev
            skipped += j >= valid[s]
            prev = idx
        held = prev
    qo_bytes = 2 * S * int(num_heads) * int(n_rows) * int(head_dim) * int(
        itemsize)
    return {
        "block": block,
        "grid_steps": S * n_blocks,
        "blocks_read": read,
        "blocks_skipped": skipped,
        "block_bytes": block_bytes,
        "hbm_bytes": read * block_bytes + qo_bytes,
    }
