"""Flash attention: blocked online-softmax attention as a Pallas TPU kernel.

The reference framework has no attention kernel at all (SURVEY.md §5.7 —
Transformer is composed from matmul/softmax ops, tests/unittests/
dist_transformer.py); this is the TPU-first upgrade that sets the
long-context ceiling. Per-core memory is O(tile), independent of
sequence length — the full [T, S] score matrix never exists in HBM.

**What a grid step holds** is decided from the arguments' shape and
dtype by ``_choose_tiles`` and by nothing else (no flag, no attribute):

* *The tile.* A sequence of up to 512 is ONE tile, so the kv axis of the
  grid is 1 and the forward is a plain softmax with no running state;
  longer sequences take tiles of up to 512 with the kv tiles innermost,
  Pallas pipelining each K/V tile HBM->VMEM while the previous one
  computes, the running (max, sum, acc) in VMEM scratch. A grid step
  costs ~0.36 us whatever it computes (PERF.md, PR 33), which tiles of
  128 at T = 256 spent four times a head.
* *Several heads a step.* A step carries ``heads_per_step`` heads of one
  batch row (blocks ``(1, Hb, tile, d)``, ``lse`` / ``delta``
  ``(1, Hb, 1, tile)``), as many as ``_VMEM_BUDGET`` holds by
  ``_step_vmem_bytes``' count; the masks of a step are built once for
  all of them. Under grouped-query attention the ``Hb`` query heads of a
  step share one kv head (``Hb`` divides ``kv_group``).
* *The products' dtype.* ``q``, ``k``, ``v`` and ``dO`` go to the MXU as
  they arrive (bfloat16 under the AMP rewrite: one pass; float32 when the
  caller passes float32), ``p`` and ``dS`` are rounded to that dtype for
  the products that consume them, exactly as
  ``flash_attention_reference`` rounds ``p``; every product accumulates
  in float32, and max, exp, sum, ``lse``, ``delta`` and the accumulators
  are float32 whatever the operands.

Forward and backward are both Pallas: the forward emits the per-row
log-sum-exp residual, and the backward is the FlashAttention-2 recipe —
delta = rowsum(dO*O) precomputed in XLA, a dK/dV kernel scanning Q tiles
innermost, and a dQ kernel scanning K/V tiles innermost — so neither
direction ever materializes the [T, S] score matrix
(FLAGS_flash_backward=reference restores the recompute-through-XLA
fallback). On CPU (tests) the kernels run with ``interpret=True``; the
public entry point picks the best path per backend.
"""

import functools
import math

import jax
import jax.numpy as jnp

# ``block_q`` / ``block_k`` left at these are chosen from the shape by
# ``_choose_tiles`` (parallel/ring_attention.py hands them through)
_DEFAULT_BLOCK_Q = None
_DEFAULT_BLOCK_K = None
_NEG_INF = -1e30
# rows whose running max never rose above this saw no visible key:
# forward zeroes them, backward skips them (must stay > _NEG_INF and
# below any reachable finite score)
_MASKED_ROW_LSE = -1e29
# pallas_call names: what a Mosaic error, a profiler trace and the
# compiled text show for these kernels
FWD_KERNEL_NAME = "flash_attention_fwd"
BWD_DKV_KERNEL_NAME = "flash_attention_bwd_dkv"
BWD_DQ_KERNEL_NAME = "flash_attention_bwd_dq"

_LANES = 128
# the tiles of a sequence too long for one, largest first (PERF.md,
# PR 33: tiles of 512 took a T = 8192 call from 47 to 7 ms)
_LONG_TILES = (512, 384, 256, 128)
_MAX_TILE = _LONG_TILES[0]
# what one grid step may hold by ``_step_vmem_bytes``' count: Mosaic
# scopes 16 MiB of VMEM to a kernel on a v5e unless told otherwise, and
# the count leaves out the masks and the compiler's own temporaries
_VMEM_BUDGET = 12 << 20


def _round_up(n, m):
    return -(-n // m) * m


def _tile_for(n):
    """The tile of a sequence of ``n``: the whole sequence while one tile
    of ``_MAX_TILE`` covers it (in whole lane rows once it is longer than
    one), else the largest of ``_LONG_TILES`` that pads it least."""
    if n <= _LANES:
        return n
    if n <= _MAX_TILE:
        return _round_up(n, _LANES)
    return min(_LONG_TILES, key=lambda b: (_round_up(n, b), -b))


def _step_vmem_bytes(block_q, block_k, heads, kv_heads, d, itemsize,
                     backward):
    """The VMEM a grid step holds, as ``_choose_tiles`` counts it: every
    pipelined block twice (Pallas double-buffers them; a minor dim under
    128 is padded to the lanes, the 1 of an ``lse`` row to 8 sublanes),
    the float32 accumulators, and the float32 score arrays of the step's
    heads: s and p forward; s, p, dP and dS backward, where the larger of
    the dK/dV and the dQ kernel counts."""
    lane_d = _round_up(d, _LANES)
    q_blk = heads * block_q * lane_d * itemsize
    kv_blk = kv_heads * block_k * lane_d * itemsize
    row_blk = heads * 8 * block_q * 4
    scores = heads * block_q * block_k * 4
    q_acc = heads * block_q * lane_d * 4
    if not backward:
        # q, k, v in; o, lse out; acc, m, l (a lane row each) scratch
        return (2 * (2 * q_blk + 2 * kv_blk + row_blk)
                + q_acc + 2 * heads * block_q * _LANES * 4 + 2 * scores)
    kv_acc = kv_heads * block_k * lane_d * 4
    # q, k, v, dO, lse, delta in; (dK, dV | dQ) out, each with its
    # accumulator
    return (2 * (2 * q_blk + 2 * kv_blk + 2 * row_blk)
            + max(2 * (2 * kv_blk) + 2 * kv_acc, 2 * q_blk + q_acc)
            + 4 * scores)


def _choose_tiles(T, S, d, itemsize, heads, kv_group=1, block_q=None,
                  block_k=None, backward=False):
    """``(block_q, block_k, heads_per_step)`` for q ``[B, heads, T, d]``
    against k/v ``[B, heads // kv_group, S, d]``: the ONE rule for what a
    grid step of the three kernels holds. A ``block_q`` / ``block_k`` the
    caller passes is kept (clipped to the sequence); ``heads_per_step``
    is the largest divisor of ``heads`` (of ``kv_group`` under
    grouped-query attention: a step's query heads share ONE kv head)
    whose step fits ``_VMEM_BUDGET``."""
    block_q = _tile_for(T) if block_q is None else min(block_q, T)
    block_k = _tile_for(S) if block_k is None else min(block_k, S)
    pool = heads if kv_group == 1 else kv_group
    for hb in range(pool, 1, -1):
        if pool % hb == 0 and _step_vmem_bytes(
                block_q, block_k, hb, hb if kv_group == 1 else 1, d,
                itemsize, backward) <= _VMEM_BUDGET:
            return block_q, block_k, hb
    return block_q, block_k, 1


def _mosaic_params(interpret, dimension_semantics):
    """compiler_params kwargs for a pallas_call: declare which grid dims
    are order-independent ("parallel") vs reductions ("arbitrary") so
    Mosaic can pipeline independent tiles. Omitted in interpret mode
    (the CPU interpreter has no Mosaic compiler to parameterize)."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=dimension_semantics)}


def _is_tpu_target():
    """Pinned-Place-aware backend test (core/lowering.is_tpu_target);
    falls back to default_backend for standalone (non-executor) use."""
    try:
        from paddle_tpu.core.lowering import is_tpu_target

        return is_tpu_target()
    except Exception:
        return jax.default_backend() != "cpu"


def flash_attention_reference(q, k, v, causal=False, sm_scale=None,
                              mask=None):
    """XLA reference path. q:[B,H,T,d] k:[B,H,S,d] v:[B,H,S,dv];
    mask:[B,1|H,T,S]."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum(
        "bhtd,bhsd->bhts", q, k, preferred_element_type=jnp.float32
    ) * sm_scale
    if causal:
        t, ss = s.shape[-2], s.shape[-1]
        idx_t = jnp.arange(t)[:, None]
        idx_s = jnp.arange(ss)[None, :]
        s = jnp.where(idx_s <= idx_t, s, _NEG_INF)
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bhsd->bhtd", p, v)


def _window_band(T, S, window, causal):
    """[T, S] sliding-window visibility band (q - w < k <= q when causal,
    |q - k| < w otherwise) for the reference/backward paths."""
    qi = jnp.arange(T)[:, None]
    ki = jnp.arange(S)[None, :]
    band = (qi - ki) < window
    if not causal:
        band = band & ((ki - qi) < window)
    return band


def _scores(q, k, sm_scale):
    """[bq, bk] float32 scaled scores of one head's tile, the product in
    the operands' dtype. ``sm_scale`` goes where it costs no rounding
    that the operands' precision does not already have: on ``q`` when
    that is exact (float32 operands, as ever; a power of two, as
    ``d ** -0.5`` is at d = 64 or 256, in a narrower dtype), else on the
    float32 scores."""
    on_q = (q.dtype == jnp.float32
            or math.frexp(float(sm_scale))[0] == 0.5)
    if on_q:
        q = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return s if on_q else s * sm_scale


def _visible(q_base, k_base, block_q, block_k, *, seq_q, seq_k, kvm_ref,
             causal, window):
    """[block_q, block_k] bool, which keys of the tile at ``k_base`` the
    queries of the tile at ``q_base`` see: built once a grid step for
    all its heads. None when all do. ``seq_q`` / ``seq_k`` are the real
    lengths where the tiles pad them (else None), ``kvm_ref`` the
    key-validity block of the batch row ([1, 1, block_k] float, 1 =
    keep; None without a mask)."""
    shape = (block_q, block_k)
    terms = []
    if seq_k is not None or causal or window:
        k_idx = k_base + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if seq_q is not None or causal or window:
        q_idx = q_base + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    if seq_q is not None:
        terms.append(q_idx < seq_q)
    if seq_k is not None:
        terms.append(k_idx < seq_k)
    if kvm_ref is not None:
        terms.append(kvm_ref[0, 0, :][None, :] > 0)
    if causal:
        terms.append(k_idx <= q_idx)
    if window:
        # sliding window: only the last `window` positions are visible
        # (causal: q - w < k <= q; else |q - k| < w)
        terms.append(q_idx - k_idx < window)
        if not causal:
            terms.append(k_idx - q_idx < window)
    return functools.reduce(jnp.logical_and, terms) if terms else None


def _each_head(heads, body):
    """``body(h)`` for every head of a grid step, as a loop that is
    traced ONCE and unrolled when the kernel is lowered. Unrolled it has
    to be: the compiler overlaps one head's products with another's
    exp and sums, and a loop it must keep in order runs a step of eight
    heads no faster than eight steps (PERF.md, PR 44: 0.80 against 0.61
    ms a forward call). Traced once it has to be too: the executor
    traces a program's kernels several times over, and eight heads
    unrolled in Python took a training cell's set-up from 86 to 121 s."""
    if heads == 1:
        body(0)
        return

    def step(h, carry):
        body(h)
        return carry

    jax.lax.fori_loop(0, heads, step, 0, unroll=True)


def _flash_kernel(q_ref, k_ref, v_ref, kvm_ref, o_ref, lse_ref, *scratch,
                  sm_scale, causal, seq_k, block_q, block_k, n_kv,
                  has_mask, window=0):
    """One (b, head block, qi, kj) grid step: the step's heads absorb one
    K/V tile each. With one kv tile (``n_kv`` 1, no scratch) that is the
    whole softmax; else the running online-softmax state is held in VMEM
    scratch across the kv steps. ``seq_k`` is the real key length where
    the tiles pad it (else None); ``kvm_ref`` is the per-batch
    key-validity mask tile ([1, block_k] float, 1 = keep) when has_mask,
    else an unused dummy. The K/V blocks carry the step's heads, or ONE
    head that all of them share (grouped-query attention)."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    kj = pl.program_id(3)
    heads, kv_heads = q_ref.shape[1], k_ref.shape[1]
    q_base = qi * block_q
    k_base = kj * block_k
    acc_ref, m_ref, l_ref = scratch or (None,) * 3

    def _finish(h, m, l, acc):
        # A row with NO visible key keeps m at _NEG_INF: inside a
        # computed tile its p = exp(-1e30 - (-1e30)) = 1 per entry, so
        # acc holds a garbage mean-of-V — zero those rows explicitly to
        # honor the fully-masked-rows-return-0 contract.
        dead = m <= _MASKED_ROW_LSE
        o_ref[0, h, :, :] = jnp.where(
            dead, 0.0, acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # log-sum-exp per query row, the backward pass's softmax residual;
        # fully-masked / padded rows yield ~-1e30 (backward zeroes them).
        # Layout is [B, H, 1, T]: a trailing dim of 1 would be tile-padded
        # to 128 (a 128x HBM expansion, enough to OOM a 6-layer model).
        lse_ref[0, h, 0, :] = (m + jnp.log(jnp.maximum(l, 1e-30)))[:, 0]

    def _compute():
        visible = _visible(
            q_base, k_base, block_q, block_k, seq_q=None, seq_k=seq_k,
            kvm_ref=kvm_ref if has_mask else None,
            causal=causal, window=window)

        def head(h):
            kh = h if kv_heads > 1 else 0
            s = _scores(q_ref[0, h, :, :], k_ref[0, kh, :, :], sm_scale)
            if visible is not None:
                s = jnp.where(visible, s, _NEG_INF)
            v = v_ref[0, kh, :, :]
            m_tile = jnp.max(s, axis=-1, keepdims=True)
            if n_kv == 1:
                p = jnp.exp(s - m_tile)
                _finish(h, m_tile, jnp.sum(p, axis=-1, keepdims=True),
                        jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
            else:
                m_prev = m_ref[h]
                m_new = jnp.maximum(m_prev, m_tile)
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_ref[h] = l_ref[h] * alpha + jnp.sum(
                    p, axis=-1, keepdims=True)
                acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[h] = m_new

        _each_head(heads, head)

    if n_kv == 1:
        # nothing to skip and nothing to carry: a tile no query of which
        # sees a key (a window past the keys' end) comes out dead
        _compute()
        return

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = None
    if causal:
        # Tiles strictly above the diagonal contribute nothing — skip.
        run = k_base <= q_base + block_q - 1
    if window:
        # Tiles entirely OUTSIDE the window contribute nothing either:
        # the real FLOP saving of local attention (compute per query is
        # O(window), not O(S))
        behind = k_base + block_k - 1 > q_base - window
        run = behind if run is None else (run & behind)
        if not causal:
            ahead = k_base - (q_base + block_q - 1) < window
            run = run & ahead
    if run is not None:
        pl.when(run)(_compute)
    else:
        _compute()

    @pl.when(kj == n_kv - 1)
    def _finish_all():
        _each_head(heads, lambda h: _finish(h, m_ref[h], l_ref[h],
                                            acc_ref[h]))


def _padded(x, axis, pad):
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _kv_mask_rows(kv_mask, batch, block_k, S_pad):
    """The kernels' key-validity operand: [B, 1, S + S_pad] float32 (the
    block's last two dims are then (1, block_k): dim -2 equals the array
    dim, dim -1 divides 128, Mosaic's tiling rule), or a dummy of ones
    when there is no mask."""
    if kv_mask is None:
        return jnp.ones((batch, 1, block_k), jnp.float32)
    return _padded(kv_mask.astype(jnp.float32), 1, S_pad)[:, None, :]


# The pallas_calls sit under a jit of their own: a model's layers call
# them with the same shapes and statics, the executor traces every op at
# program build and again (twice over, through the backward's vjp) at
# lowering, and a jitted function is traced once a signature and lowered
# once a module, where a bare pallas_call's body is walked every time.
_STATICS = ("causal", "sm_scale", "block_q", "block_k", "interpret",
            "kv_group", "window")


@functools.partial(jax.jit, static_argnames=_STATICS)
def _flash_forward(q, k, v, kv_mask, causal, sm_scale, block_q, block_k,
                   interpret, kv_group=1, window=0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, d = q.shape
    S = k.shape[2]
    # the value width is the values' own (latent attention: queries and
    # keys of 192 beside values of 128); the second product, the
    # accumulator and the output are that wide. The tiles are chosen by
    # the query width, the larger of the two where they differ
    dv = v.shape[-1]
    # grouped-query attention: K/V carry H // kv_group heads and each
    # serves kv_group query heads THROUGH THE INDEX MAP — the repeated
    # K/V never materializes (a custom call can't fold a broadcast
    # operand the way XLA fuses one)
    g = int(kv_group)
    if g < 1 or k.shape[1] * g != H:
        raise ValueError(
            "flash_attention: kv heads (%d) * kv_group (%d) must "
            "equal query heads (%d)" % (k.shape[1], g, H))
    block_q, block_k, hb = _choose_tiles(
        T, S, d, q.dtype.itemsize, H, g, block_q, block_k)
    kvb = hb if g == 1 else 1

    # Pad T/S to block multiples; padded keys are masked inside the kernel
    # via seq_k, padded queries are sliced off after.
    T_pad = -T % block_q
    S_pad = -S % block_k
    qp = _padded(q, 2, T_pad)
    kp = _padded(k, 2, S_pad)
    vp = _padded(v, 2, S_pad)
    Tp, Sp = T + T_pad, S + S_pad
    n_kv = Sp // block_k

    has_mask = kv_mask is not None
    kvm = _kv_mask_rows(kv_mask, B, block_k, S_pad)

    kernel = functools.partial(
        _flash_kernel,
        sm_scale=sm_scale,
        causal=causal,
        seq_k=S if S_pad else None,
        block_q=block_q,
        block_k=block_k,
        n_kv=n_kv,
        has_mask=has_mask,
        window=int(window),
    )
    q_spec = pl.BlockSpec((1, hb, block_q, d), lambda b, h, i, j: (b, h, i, 0))
    # the kv head (block) of query-head block h: itself, or the ONE head
    # its hb heads share
    kv_map = ((lambda b, h, i, j: (b, h, j, 0)) if g == 1
              else (lambda b, h, i, j: (b, h * hb // g, j, 0)))
    kv_spec = pl.BlockSpec((1, kvb, block_k, d), kv_map)
    v_spec = kv_spec if dv == d else pl.BlockSpec(
        (1, kvb, block_k, dv), kv_map)
    o_spec = q_spec if dv == d else pl.BlockSpec(
        (1, hb, block_q, dv), lambda b, h, i, j: (b, h, i, 0))
    out = pl.pallas_call(
        kernel,
        grid=(B, H // hb, Tp // block_q, n_kv),
        in_specs=[
            q_spec, kv_spec, v_spec,
            pl.BlockSpec(
                (1, 1, block_k),
                (lambda b, h, i, j: (b, 0, j)) if has_mask
                else (lambda b, h, i, j: (b, 0, 0)),
            ),
        ],
        out_specs=[
            o_spec,
            pl.BlockSpec(
                (1, hb, 1, block_q), lambda b, h, i, j: (b, h, 0, i)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tp, dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, Tp), jnp.float32),
        ],
        scratch_shapes=[] if n_kv == 1 else [
            pltpu.VMEM((hb, block_q, dv), jnp.float32),
            pltpu.VMEM((hb, block_q, 1), jnp.float32),
            pltpu.VMEM((hb, block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name=FWD_KERNEL_NAME,
        # (b, h, qi) tiles are independent — only the kj reduction is
        # order-dependent. Declaring that lets Mosaic pipeline/reorder
        # the independent tiles instead of running the grid serially.
        **_mosaic_params(interpret, ("parallel",) * 3 + ("arbitrary",)),
    )(qp, kp, vp, kvm)
    out, lse = out
    return out[:, :, :T, :], lse


def _bwd_tile_grads(q, k, v, do, lse, delta, visible, sm_scale):
    """Shared per-tile backward math of one head. q/do: [bq, d]; k/v:
    [bk, d]; lse/delta: [bq, 1]; visible: [bq, bk] bool or None (key
    validity + causal + window + padding). Returns (dS_scaled, p), both
    [bq, bk] and rounded to the operands' dtype for the products that
    consume them."""
    # row validity: padded / fully-masked rows have lse ~ -1e30 and
    # must contribute nothing (exp(s - lse) would blow up there)
    valid = lse > _MASKED_ROW_LSE
    if visible is not None:
        valid = visible & valid
    p = jnp.where(valid, jnp.exp(_scores(q, k, sm_scale) - lse), 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta) * sm_scale
    return ds.astype(q.dtype), p.astype(q.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          kvm_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                          sm_scale, causal, seq_q, seq_k, block_q, block_k,
                          n_q, has_mask, n_group=1, window=0):
    """Grid (b, kv-head block, kj, gi, qi), q innermost: accumulate dK/dV
    for one K/V tile across all Q tiles — and, under grouped-query
    attention, across the query heads this kv head serves, a step's
    worth at a time over the ``n_group`` steps of the gi axis; VMEM
    accumulators persist over the (gi, qi) steps. ``seq_q`` / ``seq_k``
    are the real lengths where the tiles pad them (else None)."""
    from jax.experimental import pallas as pl

    kj = pl.program_id(2)
    gi = pl.program_id(3)
    qi = pl.program_id(4)
    heads, kv_heads = q_ref.shape[1], k_ref.shape[1]

    @pl.when((gi == 0) & (qi == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_base = qi * block_q
    k_base = kj * block_k

    def _compute():
        visible = _visible(
            q_base, k_base, block_q, block_k, seq_q=seq_q, seq_k=seq_k,
            kvm_ref=kvm_ref if has_mask else None,
            causal=causal, window=window)

        def head(h):
            kh = h if kv_heads > 1 else 0
            q = q_ref[0, h, :, :]
            do = do_ref[0, h, :, :]
            ds, p = _bwd_tile_grads(
                q, k_ref[0, kh, :, :], v_ref[0, kh, :, :], do,
                lse_ref[0, h, 0, :][:, None], delta_ref[0, h, 0, :][:, None],
                visible, sm_scale)
            dv_acc[kh] += jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dk_acc[kh] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        _each_head(heads, head)

    run = None
    if causal:
        # Q tiles entirely above the diagonal see only masked positions.
        run = q_base + block_q - 1 >= k_base
    if window:
        behind = q_base - (k_base + block_k - 1) < window
        run = behind if run is None else (run & behind)
        if not causal:
            run = run & (k_base - (q_base + block_q - 1) < window)
    if run is not None:
        pl.when(run)(_compute)
    else:
        _compute()

    @pl.when((gi == n_group - 1) & (qi == n_q - 1))
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         kvm_ref, dq_ref, dq_acc, *, sm_scale, causal,
                         seq_q, seq_k, block_q, block_k, n_kv, has_mask,
                         window=0):
    """Grid (b, head block, qi, kj), kv innermost: accumulate dQ for one
    Q tile of the step's heads."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    kj = pl.program_id(3)
    heads, kv_heads = q_ref.shape[1], k_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_base = qi * block_q
    k_base = kj * block_k

    def _compute():
        visible = _visible(
            q_base, k_base, block_q, block_k, seq_q=seq_q, seq_k=seq_k,
            kvm_ref=kvm_ref if has_mask else None,
            causal=causal, window=window)

        def head(h):
            kh = h if kv_heads > 1 else 0
            k = k_ref[0, kh, :, :]
            ds, _ = _bwd_tile_grads(
                q_ref[0, h, :, :], k, v_ref[0, kh, :, :], do_ref[0, h, :, :],
                lse_ref[0, h, 0, :][:, None], delta_ref[0, h, 0, :][:, None],
                visible, sm_scale)
            dq_acc[h] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        _each_head(heads, head)

    run = None
    if causal:
        run = k_base <= q_base + block_q - 1
    if window:
        behind = k_base + block_k - 1 > q_base - window
        run = behind if run is None else (run & behind)
        if not causal:
            run = run & (k_base - (q_base + block_q - 1) < window)
    if run is not None:
        pl.when(run)(_compute)
    else:
        _compute()

    @pl.when(kj == n_kv - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=_STATICS)
def _flash_backward(q, k, v, kv_mask, out, lse, dout, causal, sm_scale,
                    block_q, block_k, interpret, kv_group=1, window=0):
    """FlashAttention-2-style backward: delta precomputed in XLA, then a
    dK/dV kernel (q innermost) and a dQ kernel (kv innermost). O(block)
    memory — the [T, S] score matrix never materializes, matching the
    forward's long-context contract. Under grouped-query attention
    (kv_group > 1) the index maps serve each kv head to its query group
    and dK/dV accumulate across the group — the memory contract holds
    for GQA training too."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, d = q.shape
    grp = int(kv_group)
    Hkv = H // grp
    S = k.shape[2]
    # the forward's tiles (its lse comes back padded to them), fewer
    # heads a step: four score arrays a head stand where it had two
    block_q, block_k, hb = _choose_tiles(
        T, S, d, q.dtype.itemsize, H, grp, block_q, block_k, backward=True)
    kvb = hb if grp == 1 else 1
    # steps of the dK/dV grid's gi axis: the query-head blocks a kv head
    # (block) serves
    n_group = 1 if grp == 1 else grp // hb
    T_pad = -T % block_q
    S_pad = -S % block_k
    Tp, Sp = T + T_pad, S + S_pad
    n_q, n_kv = Tp // block_q, Sp // block_k

    qp = _padded(q, 2, T_pad)
    kp = _padded(k, 2, S_pad)
    vp = _padded(v, 2, S_pad)
    # dO goes to the kernels as it arrives: the products take it in its
    # own dtype, and a float32 copy would be read at twice the bytes, twice
    dop = _padded(dout, 2, T_pad)
    # delta_i = rowsum(dO * O): one cheap fused elementwise+reduce in XLA;
    # [B, H, 1, T] layout like lse (trailing-1 dims tile-pad 128x)
    delta = _padded(
        jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                axis=-1)[:, :, None, :], 3, T_pad)

    has_mask = kv_mask is not None
    kvm = _kv_mask_rows(kv_mask, B, block_k, S_pad)
    tile_kw = dict(
        sm_scale=sm_scale, causal=causal, seq_q=T if T_pad else None,
        seq_k=S if S_pad else None, block_q=block_q, block_k=block_k,
        has_mask=has_mask, window=int(window))

    # dkv grid: (b, kv-head block, kv-block, group step, q-block); q-side
    # tensors index the query-head block hk * n_group + gi
    q_spec = pl.BlockSpec(
        (1, hb, block_q, d),
        lambda b, hk, j, gi, i: (b, hk * n_group + gi, i, 0))
    kv_spec = pl.BlockSpec(
        (1, kvb, block_k, d), lambda b, hk, j, gi, i: (b, hk, j, 0))
    row_spec = pl.BlockSpec(
        (1, hb, 1, block_q),
        lambda b, hk, j, gi, i: (b, hk * n_group + gi, 0, i))
    kvm_spec = pl.BlockSpec(
        (1, 1, block_k),
        (lambda b, hk, j, gi, i: (b, 0, j)) if has_mask
        else (lambda b, hk, j, gi, i: (b, 0, 0)),
    )
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, n_q=n_q, n_group=n_group, **tile_kw),
        grid=(B, Hkv // kvb, n_kv, n_group, n_q),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec,
                  kvm_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, Sp, d), k.dtype),
            jax.ShapeDtypeStruct((B, Hkv, Sp, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((kvb, block_k, d), jnp.float32),
            pltpu.VMEM((kvb, block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name=BWD_DKV_KERNEL_NAME,
        # dk/dv accumulate over the (gi, qi) inner dims; (b, hk, kj)
        # tiles are independent
        **_mosaic_params(interpret,
                         ("parallel",) * 3 + ("arbitrary",) * 2),
    )(qp, kp, vp, dop, lse, delta, kvm)

    q_spec2 = pl.BlockSpec(
        (1, hb, block_q, d), lambda b, h, i, j: (b, h, i, 0))
    kv_spec2 = pl.BlockSpec(
        (1, kvb, block_k, d),
        (lambda b, h, i, j: (b, h, j, 0)) if grp == 1
        else (lambda b, h, i, j: (b, h * hb // grp, j, 0)))
    row_spec2 = pl.BlockSpec(
        (1, hb, 1, block_q), lambda b, h, i, j: (b, h, 0, i))
    kvm_spec2 = pl.BlockSpec(
        (1, 1, block_k),
        (lambda b, h, i, j: (b, 0, j)) if has_mask
        else (lambda b, h, i, j: (b, 0, 0)),
    )
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, n_kv=n_kv, **tile_kw),
        grid=(B, H // hb, n_q, n_kv),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2,
                  row_spec2, kvm_spec2],
        out_specs=q_spec2,
        out_shape=jax.ShapeDtypeStruct((B, H, Tp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((hb, block_q, d), jnp.float32)],
        interpret=interpret,
        name=BWD_DQ_KERNEL_NAME,
        # dq accumulates over kj only; (b, h, qi) tiles independent
        **_mosaic_params(interpret, ("parallel",) * 3 + ("arbitrary",)),
    )(qp, kp, vp, dop, lse, delta, kvm)

    return dq[:, :, :T, :], dk[:, :, :S, :], dv[:, :, :S, :]


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, kv_mask, has_mask, causal, sm_scale, block_q, block_k,
           interpret, kv_group=1, window=0):
    out, _ = _flash_forward(q, k, v, kv_mask if has_mask else None, causal,
                            sm_scale, block_q, block_k, interpret,
                            kv_group=kv_group, window=window)
    return out


def _flash_fwd(q, k, v, kv_mask, has_mask, causal, sm_scale, block_q,
               block_k, interpret, kv_group=1, window=0):
    if v.shape[-1] != q.shape[-1]:
        raise NotImplementedError(
            "flash_attention backward: the dK/dV and dQ kernels run at one "
            "head width; values of %d beside queries of %d are served "
            "forward only" % (v.shape[-1], q.shape[-1]))
    out, lse = _flash_forward(q, k, v, kv_mask if has_mask else None,
                              causal, sm_scale, block_q, block_k, interpret,
                              kv_group=kv_group, window=window)
    return out, (q, k, v, kv_mask, out, lse)


def _flash_bwd(has_mask, causal, sm_scale, block_q, block_k, interpret,
               kv_group, window, res, g):
    q, k, v, kv_mask, out, lse = res
    if _backward_impl() == "reference":
        mask = kv_mask[:, None, None, :].astype(bool) if has_mask else None
        if window:
            band = _window_band(q.shape[2], k.shape[2], window, causal)
            band = band[None, None]
            mask = band if mask is None else (mask & band)

        def ref(q_, k_, v_):
            k_r = jnp.repeat(k_, kv_group, axis=1) if kv_group != 1 else k_
            v_r = jnp.repeat(v_, kv_group, axis=1) if kv_group != 1 else v_
            return flash_attention_reference(
                q_, k_r, v_r, causal=causal, sm_scale=sm_scale, mask=mask)

        _, vjp = jax.vjp(ref, q, k, v)
        return vjp(g) + (jnp.zeros_like(kv_mask),)
    dq, dk, dv = _flash_backward(
        q, k, v, kv_mask if has_mask else None, out, lse, g, causal,
        sm_scale, block_q, block_k, interpret, kv_group=kv_group,
        window=window,
    )
    return dq, dk, dv, jnp.zeros_like(kv_mask)


def _backward_impl():
    """FLAGS_flash_backward: 'pallas' (default) or 'reference' — the
    escape hatch mirrors FLAGS_attention_impl for the whole op."""
    try:
        from paddle_tpu import flags

        return flags.get("flash_backward")
    except Exception:  # flags unavailable in standalone kernel use
        return "pallas"


_flash.defvjp(_flash_fwd, _flash_bwd)


def _key_mask(mask):
    """The [B, S] key-validity view of ``mask`` when it has that form
    ([B, S], or [B, 1, 1, S] as the sdpa op normalizes it), else None."""
    if mask is None:
        return None
    if mask.ndim == 2:
        return mask
    if mask.ndim == 4 and mask.shape[1] == 1 and mask.shape[2] == 1:
        return mask[:, 0, 0, :]
    return None


def uses_kernel(mask=None, force_reference=False, force_pallas=False):
    """Whether ``flash_attention`` with these arguments runs the Pallas
    kernel (else the XLA reference): key-validity masks go through the
    kernel, full [B, H, T, S] masks never do."""
    return (mask is None or _key_mask(mask) is not None) and (
        force_pallas or (not force_reference and _is_tpu_target()))


def flash_attention(
    q,
    k,
    v,
    causal=False,
    sm_scale=None,
    mask=None,
    block_q=_DEFAULT_BLOCK_Q,
    block_k=_DEFAULT_BLOCK_K,
    force_reference=False,
    force_pallas=False,
    kv_group=1,
    window=0,
):
    """Fused attention. q:[B,H,T,d], k:[B,H,S,d], v:[B,H,S,dv] ->
    [B,H,T,dv]. ``dv`` may differ from ``d`` in the forward (latent
    attention's 192-wide queries and keys beside 128-wide values: the
    second product and the output are ``dv`` wide, nothing is padded);
    the backward kernels refuse that by name.

    ``kv_group`` > 1 is grouped-query attention: k/v carry H/kv_group
    heads, each serving kv_group query heads through the kernel's index
    map — the repeated K/V never materializes.

    Pallas kernel on TPU (interpret-mode when forced on CPU); XLA reference
    elsewhere. Key-validity masks — [B, S], or [B, 1, 1, S] as the sdpa op
    normalizes them — run through the kernel (the tile test absorbs them);
    only full [B, H, T, S] masks fall back to the reference path. A query
    row whose keys are ALL masked returns 0 from the kernel (the reference
    path returns the uniform-softmax average; such rows are meaningless
    either way).
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if int(window) < 0:
        raise ValueError(
            "flash_attention: window must be >= 0 (0 disables the "
            "sliding window); got %d" % window)
    kv_mask = _key_mask(mask)
    if not uses_kernel(mask, force_reference, force_pallas):
        # normalize a [B, S] key mask to [B, 1, 1, S] for the reference
        # einsum path (raw 2-D would broadcast B against the T axis)
        ref_mask = (kv_mask[:, None, None, :] if kv_mask is not None
                    else mask)
        if kv_group != 1:
            k = jnp.repeat(k, kv_group, axis=1)
            v = jnp.repeat(v, kv_group, axis=1)
        if window:
            band = _window_band(q.shape[2], k.shape[2], window,
                                causal)[None, None]
            ref_mask = band if ref_mask is None else (
                ref_mask.astype(bool) & band)
        return flash_attention_reference(
            q, k, v, causal=causal, sm_scale=sm_scale, mask=ref_mask
        )
    interpret = not _is_tpu_target()
    has_mask = kv_mask is not None
    if not has_mask:
        # static dummy so the custom_vjp signature stays array-only
        kv_mask = jnp.ones((q.shape[0], 1), jnp.float32)
    return _flash(q, k, v, kv_mask.astype(jnp.float32), has_mask, causal,
                  sm_scale, block_q, block_k, interpret, kv_group,
                  int(window))
