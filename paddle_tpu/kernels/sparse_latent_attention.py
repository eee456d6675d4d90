"""Learned sparse attention over the latent (MLA) page pool: an indexer
chooses, for every query, the ``index_topk`` cached positions it attends,
and the attention reads those rows alone.

Beside each latent row pool of ``latent_attention.py`` a layer that has an
indexer of its own keeps a NARROW pool ``[num_pages, page_size,
index_head_dim]`` under the same page table: one key ``kI`` a position,
read by all the indexer's heads.

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (s <= t)
    S_t     = the index_topk positions of largest I[t, s]    (exact)
    o[t, h] = sum_{s in S_t} softmax_s(q[t, h] . k[s, h] * scale) v[s, h]

Decode (one query a slot):

* ``index_score_decode`` (Pallas, grid ``(slot, pages / g)``, the page
  table scalar-prefetched, ``g`` narrow pages a step through ``g`` block
  specs over the one pool): ``I`` of a slot's query against its whole
  narrow pool, float32, ``-inf`` past the slot's length.
* ``index_select``: ``jax.lax.top_k``, exact; ties go to the lower
  position. Entries past a slot's length come out as ``-1``.
* ``sparse_latent_decode_attention``: the chosen rows are gathered from
  the paged pool (``index_topk`` rows of ``pool_width`` a slot a layer,
  whatever the slot holds) and the absorbed-form kernel of
  ``latent_attention.py`` runs over the gathered rows in chunks, under a
  name of its own.

Prefill (``T`` queries a prompt):

* ``index_select_prefill``: in query blocks, the causal scores of a block
  against the prompt's keys and the exact top-``k`` of every row as a
  MASK ``[B, T, T]`` int8 (the k-th largest found by bisection over the
  float's bits, ties to the lower position: no sort, no ``[T, T]`` float).
* ``sparse_latent_prefill_attention`` (Pallas): flash attention in the
  expanded form under that mask (or, for a bucket of at most ``index_topk``
  rows, plainly causal), on token rows with the heads side by side (no
  transposed copy of q, k, v), bfloat16 operands on the MXU, float32
  softmax, two heads a grid step under one tile of the mask; tiles above
  the diagonal and tiles of queries past the prompt's length (the
  bucket's padding) are skipped, in the selection too.

Every function has its ``jax.numpy`` reference beside it (the default off
the TPU); a kernel the compiler refuses raises ``KernelCompileError``.
"""

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.flash_attention import _is_tpu_target, _mosaic_params
from paddle_tpu.kernels.latent_attention import (
    _HEAD_TILE,
    _MASKED_ROW_M,
    _NEG_INF,
    _absorb_rows,
    _finish_heads,
    _fit,
    latent_paged_attention_reference,
)
from paddle_tpu.kernels.paged_attention import (
    KernelCompileError, _start_slot)

INDEX_SCORE_KERNEL_NAME = "index_score_decode"
SPARSE_DECODE_KERNEL_NAME = "sparse_latent_decode_attention"
SPARSE_PREFILL_KERNEL_NAME = "sparse_latent_prefill_attention"

_DECODE_CHUNK = 512      # gathered rows a step of the decode kernel
_QUERY_BLOCK = 128       # queries a block of the prefill's selection
_HEAD_GROUP = 2          # heads a grid step of the prefill's attention


def _use_pallas(force_reference, force_pallas):
    return force_pallas or (not force_reference and _is_tpu_target())


# -- decode: index scores ------------------------------------------------------

def index_score_decode_reference(q_idx, w, pool, page_table, lengths):
    """q_idx: [S, J, dI]; w: [S, J] float32; pool: [P, ps, dI];
    page_table: [S, npp]; lengths: [S]. Returns ``I`` [S, npp * ps]
    float32, ``-inf`` at and past a slot's length."""
    S = q_idx.shape[0]
    keys = pool[page_table].reshape(S, -1, pool.shape[-1])
    dots = jnp.einsum("sjd,sld->sjl", q_idx, keys,
                      preferred_element_type=jnp.float32)
    score = jnp.sum(jax.nn.relu(dots) * w.astype(jnp.float32)[:, :, None],
                    axis=1)
    live = jnp.arange(keys.shape[1])[None, :] < lengths[:, None]
    return jnp.where(live, score, -jnp.inf)


def _index_score_kernel(table_ref, len_ref, q_ref, w_ref, *rest, page_size,
                        group):
    from jax.experimental import pallas as pl

    key_refs, o_ref = rest[:group], rest[group]
    s = pl.program_id(0)
    p = pl.program_id(1)
    length = len_ref[s]
    q = q_ref[0]                                      # [J, dI]
    w = w_ref[0]                                      # [J, 1]
    for g, key_ref in enumerate(key_refs):
        base = (p * group + g) * page_size

        @pl.when(base < length)
        def _score(g=g, key_ref=key_ref, base=base):
            dots = jax.lax.dot_general(
                q, key_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # [J, ps]
            row = jnp.sum(jnp.maximum(dots, 0.0) * w, axis=0,
                          keepdims=True)              # [1, ps]
            pos = base + jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
            o_ref[0, 0, g:g + 1, :] = jnp.where(pos < length, row,
                                                -jnp.inf)

        @pl.when(base >= length)
        def _past(g=g):
            o_ref[0, 0, g:g + 1, :] = jnp.full((1, page_size), -jnp.inf,
                                               jnp.float32)


def _index_score_pallas(q_idx, w, pool, page_table, lengths, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, J, dI = q_idx.shape
    ps = pool.shape[1]
    npp = page_table.shape[1]
    group = next(g for g in (8, 4, 2, 1) if npp % g == 0)

    def key_spec(g):
        return pl.BlockSpec(
            (1, ps, dI),
            lambda s, p, table, lens: (table[s, p * group + g], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, npp // group),
        in_specs=[
            pl.BlockSpec((1, J, dI), lambda s, p, table, lens: (s, 0, 0)),
            pl.BlockSpec((1, J, 1), lambda s, p, table, lens: (s, 0, 0)),
        ] + [key_spec(g) for g in range(group)],
        out_specs=pl.BlockSpec(
            (1, 1, group, ps), lambda s, p, table, lens: (s, p, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_index_score_kernel, page_size=ps, group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, npp // group, group, ps),
                                       jnp.float32),
        interpret=interpret,
        name=INDEX_SCORE_KERNEL_NAME,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      q_idx.astype(pool.dtype), w.astype(jnp.float32)[:, :, None],
      *([pool] * group))
    return out.reshape(S, npp * ps)


def index_score_decode(q_idx, w, pool, page_table, lengths,
                       force_reference=False, force_pallas=False):
    """The indexer's scores of one query a slot against the slot's whole
    narrow key pool (module docstring): [S, pages_per_slot * page_size]
    float32, ``-inf`` at and past the slot's length."""
    if not _use_pallas(force_reference, force_pallas):
        return index_score_decode_reference(q_idx, w, pool, page_table,
                                            lengths)
    try:
        return _index_score_pallas(q_idx, w, pool, page_table, lengths,
                                   interpret=not _is_tpu_target())
    except Exception as exc:
        raise KernelCompileError(
            INDEX_SCORE_KERNEL_NAME, (q_idx, w, pool, page_table, lengths),
            exc) from exc


def index_select(scores, top_k):
    """The ``top_k`` positions of largest score a row, exact, ties to the
    lower position, in descending order of score: [S, top_k] int32 with
    ``-1`` where the row has fewer than ``top_k`` finite scores (those
    come last)."""
    k = min(int(top_k), scores.shape[-1])
    values, chosen = jax.lax.top_k(scores, k)
    chosen = jnp.where(values > -jnp.inf, chosen, -1).astype(jnp.int32)
    if k < top_k:
        chosen = jnp.pad(chosen, ((0, 0), (0, int(top_k) - k)),
                         constant_values=-1)
    return chosen


# -- decode: attention over the chosen rows ------------------------------------

def gather_selected_rows(pool, page_table, selected):
    """The pool's rows at each slot's ``selected`` positions [S, k] (``-1``:
    none, the trash page's first row is read and never used): [S, k, W]."""
    ps = pool.shape[1]
    pos = jnp.maximum(selected, 0)
    page = jnp.take_along_axis(page_table.astype(jnp.int32), pos // ps,
                               axis=1)
    flat = page * ps + pos % ps
    return pool.reshape(-1, pool.shape[-1])[flat]


def _sparse_decode_kernel(table_ref, len_ref, q_ref, row_ref, o_ref,
                          acc_ref, m_ref, l_ref, *, chunk, n_chunks,
                          kv_rank, sm_scale):
    """One (slot, chunk) grid step: absorb one chunk of the slot's
    gathered rows into every head's online-softmax state (the absorbed
    latent kernel's arithmetic, ``latent_attention._absorb_rows``)."""
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    p = pl.program_id(1)
    pl.when(p == 0)(lambda: _start_slot(acc_ref, m_ref, l_ref))
    length = len_ref[s]

    @pl.when(p * chunk < length)
    def _compute():
        _absorb_rows(q_ref[0], row_ref[0], p * chunk, length, acc_ref,
                     m_ref, l_ref, kv_rank=kv_rank, sm_scale=sm_scale)

    pl.when(p == n_chunks - 1)(
        lambda: _finish_heads(o_ref, acc_ref, m_ref, l_ref))


def _sparse_decode_pallas(q_lat, q_rope, rows, counts, sm_scale, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, C = q_lat.shape
    k, W = rows.shape[1], rows.shape[2]
    chunk = min(_DECODE_CHUNK, k)
    if k % chunk:
        raise ValueError("index_topk %d is not a multiple of %d" % (k, chunk))
    n = k // chunk
    Hp = -(-H // _HEAD_TILE) * _HEAD_TILE
    q = _fit(jnp.concatenate([q_lat, q_rope], axis=-1), rows).astype(
        rows.dtype)
    if Hp != H:
        q = jnp.pad(q, ((0, 0), (0, Hp - H), (0, 0)))
    # slot s's chunks are "pages" s * n .. s * n + n - 1 of a pool of
    # their own
    table = jnp.arange(S * n, dtype=jnp.int32).reshape(S, n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, n),
        in_specs=[
            pl.BlockSpec((1, Hp, W), lambda s, p, table, lens: (s, 0, 0)),
            pl.BlockSpec((1, chunk, W),
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
        functools.partial(_sparse_decode_kernel, chunk=chunk, n_chunks=n,
                          kv_rank=C, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hp, C), q_lat.dtype),
        interpret=interpret,
        name=SPARSE_DECODE_KERNEL_NAME,
    )(table, counts.astype(jnp.int32), q, rows.reshape(S * n, chunk, W))
    return out[:, :H]


def sparse_latent_decode_attention(q_lat, q_rope, pool, page_table,
                                   selected, sm_scale, force_reference=False,
                                   force_pallas=False):
    """Absorbed-form latent decode attention of every slot over its
    ``selected`` rows alone.

    q_lat: [S, H, kv_rank]; q_rope: [S, H, rope_dim]; pool: [num_pages,
    page_size, pool_width]; page_table: [S, pages_per_slot]; selected:
    [S, k] int32 positions, the ``-1`` entries (no position) LAST in every
    row. Returns the latent output [S, H, kv_rank]; a slot with nothing
    selected returns 0."""
    rows = gather_selected_rows(pool, page_table, selected)
    counts = jnp.sum(selected >= 0, axis=-1).astype(jnp.int32)
    if not _use_pallas(force_reference, force_pallas):
        S, k = selected.shape
        # the gathered rows as a pool of one-row pages of their own
        return latent_paged_attention_reference(
            q_lat, q_rope, rows.reshape(S * k, 1, rows.shape[-1]),
            jnp.arange(S * k, dtype=jnp.int32).reshape(S, k), counts,
            sm_scale)
    try:
        return _sparse_decode_pallas(q_lat, q_rope, rows, counts, sm_scale,
                                     interpret=not _is_tpu_target())
    except Exception as exc:
        raise KernelCompileError(
            SPARSE_DECODE_KERNEL_NAME,
            (q_lat, q_rope, pool, page_table, selected), exc) from exc


# -- prefill: the selection as a mask -------------------------------------------

def _sortable(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def top_k_mask(scores, top_k, visible):
    """[..., T] bool: the ``top_k`` largest ``visible`` entries a row,
    exact, ties to the lower position; all of them where a row has fewer.
    The k-th largest is found by bisection over the bits of the float
    (32 counts a row), so nothing is sorted."""
    keys = jnp.where(visible, _sortable(scores), jnp.uint32(0))
    k = int(top_k)

    def bit(i, kth):
        cand = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(keys >= cand[..., None], axis=-1) >= k
        return jnp.where(enough, cand, kth)

    kth = jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(keys.shape[:-1], jnp.uint32))[..., None]
    above = keys > kth
    tied = keys == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    take = tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= room)
    return (above | take) & visible


def index_scores_block(q_idx, w, keys):
    """``I`` of a block of queries against all of a prompt's keys.
    q_idx: [Q, J, dI]; w: [Q, J] float32; keys: [T, dI] -> [Q, T] float32
    (no mask)."""
    dots = jnp.einsum("qjd,sd->jqs", q_idx, keys,
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots) * jnp.transpose(w)[:, :, None], axis=0)


def index_select_prefill(q_idx, w, keys, top_k, lengths=None,
                         block=_QUERY_BLOCK):
    """q_idx: [B, T, J, dI]; w: [B, T, J] float32; keys: [B, T, dI].
    Returns the mask [B, T, T] int8: ``mask[b, t, s]`` = 1 where position
    ``s <= t`` is among query ``t``'s ``top_k`` of largest index score
    (every ``s <= t`` while ``t < top_k``). The scores exist a block of
    ``block`` queries at a time; with ``lengths`` [B] a block that begins
    at or past its prompt's length (the bucket's padding) is not scored
    and chooses nothing."""
    B, T, J, dI = q_idx.shape
    Q = min(int(block), T)
    if T % Q:
        raise ValueError("the bucket %d is not a multiple of the query "
                         "block %d" % (T, Q))
    n = T // Q

    def choose(i):
        b, at = i // n, (i % n) * Q
        q = jax.lax.dynamic_slice(q_idx, (b, at, 0, 0), (1, Q, J, dI))[0]
        wq = jax.lax.dynamic_slice(w, (b, at, 0), (1, Q, J))[0]
        score = index_scores_block(q, wq.astype(jnp.float32), keys[b])
        visible = (jnp.arange(T)[None, :]
                   <= (at + jnp.arange(Q))[:, None])
        return top_k_mask(score, top_k, visible).astype(jnp.int8)

    def one(i):
        if lengths is None:
            return choose(i)
        return jax.lax.cond(
            (i % n) * Q < lengths[i // n], choose,
            lambda _i: jnp.zeros((Q, T), jnp.int8), i)

    mask = jax.lax.map(one, jnp.arange(B * n))             # [B*n, Q, T]
    return mask.reshape(B, T, T)


# -- prefill: masked attention ----------------------------------------------------

def sparse_prefill_attention_reference(q, k, v, mask, sm_scale, heads):
    """q, k, v: [B, T, H * d] (token rows, the heads side by side); mask:
    [B, T, T] (nonzero = attend) or None (causal)."""
    B, T = q.shape[:2]

    def split(x):
        return x.reshape(B, T, heads, -1)

    s = jnp.einsum("bthd,bshd->bhts", split(q), split(k),
                   preferred_element_type=jnp.float32) * sm_scale
    if mask is None:
        mask = (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])[None]
    s = jnp.where(mask[:, None] != 0, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhts,bshd->bthd", p, split(v),
                     preferred_element_type=jnp.float32)
    return out.reshape(B, T, -1).astype(q.dtype)


def _sparse_prefill_kernel(len_ref, q_ref, k_ref, v_ref, *rest, sm_scale,
                           block, n_kv, has_mask, group, dq, dv):
    from jax.experimental import pallas as pl

    length = len_ref[pl.program_id(0)]
    if has_mask:
        mask_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # the choice is causal: a tile wholly above the diagonal holds nothing;
    # nor does a tile of queries past the prompt's length (the bucket's
    # padding: those rows come out 0 and nobody reads them)
    @pl.when((kj <= qi) & (qi * block < length))
    def _compute():
        # what a pair may see is the same for every head: made once a step
        # and used by the ``group`` heads whose lanes the blocks hold
        if has_mask:
            see = mask_ref[0].astype(jnp.int32) != 0
        else:
            shape = (block, block)
            see = (kj * block + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                   <= qi * block + jax.lax.broadcasted_iota(jnp.int32,
                                                            shape, 0))
        for g in range(group):
            s = jax.lax.dot_general(
                q_ref[0, :, g * dq:(g + 1) * dq],
                k_ref[0, :, g * dq:(g + 1) * dq], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [bq, bk]
            if sm_scale != 1.0:
                s = s * sm_scale
            s = jnp.where(see, s, _NEG_INF)
            m_prev = m_ref[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            v = v_ref[0, :, g * dv:(g + 1) * dv]
            acc_ref[g] = acc_ref[g] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[g] = m_new

    @pl.when(kj == n_kv - 1)
    def _finish():
        for g in range(group):
            dead = m_ref[g] <= _MASKED_ROW_M
            o_ref[0, :, g * dv:(g + 1) * dv] = jnp.where(
                dead, 0.0, acc_ref[g] / jnp.maximum(l_ref[g], 1e-30)
            ).astype(o_ref.dtype)


def _sparse_prefill_pallas(q, k, v, mask, lengths, sm_scale, heads, block,
                           interpret):
    import math

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T = q.shape[:2]
    dq, dv = q.shape[2] // heads, v.shape[2] // heads
    blk = min(int(block), T)
    if T % blk:
        raise ValueError("the bucket %d is not a multiple of the "
                         "attention block %d" % (T, blk))
    n_kv = T // blk
    has_mask = mask is not None
    # heads a grid step: a tile of the mask is read and unpacked once for
    # all of them
    group = _HEAD_GROUP if heads % _HEAD_GROUP == 0 else 1
    # a scale that is a power of two goes into the queries exactly, and
    # the kernel saves a pass over every tile of scores
    if math.frexp(sm_scale)[0] == 0.5:
        q, sm_scale = (q.astype(jnp.float32) * sm_scale).astype(q.dtype), 1.0
    # token rows with the heads side by side: a head is a block of lanes,
    # so nothing is transposed on the way in or out. A tile above the
    # diagonal is not computed: it is pointed at the diagonal's, which is
    # resident, so it is not copied either
    def last(lens, b, i):
        """The last tile of queries (and so of keys) that query tile ``i``
        of prompt ``b`` computes with: its own, or the prompt's last."""
        return jnp.minimum(i, jnp.maximum(lens[b] - 1, 0) // blk)

    in_specs = [
        pl.BlockSpec((1, blk, group * dq),
                     lambda b, h, i, j, lens: (b, last(lens, b, i), h)),
        pl.BlockSpec(
            (1, blk, group * dq),
            lambda b, h, i, j, lens: (b, jnp.minimum(j, last(lens, b, i)),
                                      h)),
        pl.BlockSpec(
            (1, blk, group * dv),
            lambda b, h, i, j, lens: (b, jnp.minimum(j, last(lens, b, i)),
                                      h)),
    ]
    operands = [q, k, v]
    if has_mask:
        in_specs.append(pl.BlockSpec(
            (1, blk, blk),
            lambda b, h, i, j, lens: (b, last(lens, b, i),
                                      jnp.minimum(j, last(lens, b, i)))))
        operands.append(mask)
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, heads // group, T // blk, n_kv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, blk, group * dv),
                               lambda b, h, i, j, lens: (b, i, h)),
        scratch_shapes=[
            pltpu.VMEM((group, blk, dv), jnp.float32),
            pltpu.VMEM((group, blk, 1), jnp.float32),
            pltpu.VMEM((group, blk, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_sparse_prefill_kernel, sm_scale=sm_scale,
                          block=blk, n_kv=n_kv, has_mask=has_mask,
                          group=group, dq=dq, dv=dv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, heads * dv), q.dtype),
        interpret=interpret,
        name=SPARSE_PREFILL_KERNEL_NAME,
        **_mosaic_params(interpret, ("parallel",) * 3 + ("arbitrary",)),
    )(lengths.astype(jnp.int32), *operands)


def sparse_latent_prefill_attention(q, k, v, mask, sm_scale, heads,
                                    lengths=None, block=512,
                                    force_reference=False,
                                    force_pallas=False):
    """Attention of ``T`` queries a prompt over the positions ``mask``
    [B, T, T] int8 gives each (a causal mask: nothing above the diagonal;
    None: every earlier position). q, k: [B, T, heads * dq]; v: [B, T,
    heads * dv] -> [B, T, heads * dv], bfloat16 operands on the MXU. With
    ``lengths`` [B] the tiles of queries past a prompt's length (the
    bucket's padding) are skipped and their rows come out 0 from the
    kernel (the reference computes them: nobody reads them)."""
    if not _use_pallas(force_reference, force_pallas):
        return sparse_prefill_attention_reference(q, k, v, mask, sm_scale,
                                                  heads)
    try:
        return _sparse_prefill_pallas(q, k, v, mask, lengths, sm_scale,
                                      heads, block,
                                      interpret=not _is_tpu_target())
    except Exception as exc:
        raise KernelCompileError(
            SPARSE_PREFILL_KERNEL_NAME, (q, k, v, mask), exc) from exc
