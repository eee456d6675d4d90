"""Flash attention: blocked online-softmax attention as a Pallas TPU kernel.

The reference framework has no attention kernel at all (SURVEY.md §5.7 —
Transformer is composed from matmul/softmax ops, tests/unittests/
dist_transformer.py); this is the TPU-first upgrade that sets the
long-context ceiling. Canonical TPU flash blocking: grid =
(batch, heads, q_blocks, kv_blocks) with the kv dimension innermost, so
Pallas pipelines each (block_k, d) K/V tile HBM->VMEM while the previous
tile computes; running (max, sum, acc) live in VMEM scratch that persists
across the kv grid steps. Per-core memory is O(block), independent of
sequence length — the full [T, S] score matrix never exists.

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

import jax
import jax.numpy as jnp

_DEFAULT_BLOCK_Q = 128
_DEFAULT_BLOCK_K = 128
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
    """XLA reference path. q:[B,H,T,d] k,v:[B,H,S,d]; mask:[B,1|H,T,S]."""
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


def _flash_kernel(q_ref, k_ref, v_ref, kvm_ref, o_ref, lse_ref, acc_ref,
                  m_ref, l_ref, *, sm_scale, causal, seq_k, block_q,
                  block_k, n_kv, has_mask, window=0):
    """One (b, h, qi, kj) grid step: absorb one K/V tile into the running
    online-softmax state held in VMEM scratch. ``kvm_ref`` is the
    per-batch key-validity mask tile ([1, block_k] float, 1 = keep) when
    has_mask, else an unused dummy."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:, :] = jnp.zeros_like(acc_ref)
        m_ref[:, :] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:, :] = jnp.zeros_like(l_ref)

    q_base = qi * block_q
    k_base = kj * block_k

    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32) * sm_scale
        k = k_ref[0, 0, :, :].astype(jnp.float32)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        k_idx = k_base + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        valid = k_idx < seq_k
        if has_mask:
            valid = jnp.logical_and(valid, kvm_ref[0, 0, :][None, :] > 0)
        if causal or window:
            q_idx = q_base + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            if causal:
                valid = jnp.logical_and(valid, k_idx <= q_idx)
            if window:
                # sliding window: only the last `window` positions are
                # visible (causal: q - w < k <= q; else |q - k| < w)
                valid = jnp.logical_and(valid, q_idx - k_idx < window)
                if not causal:
                    valid = jnp.logical_and(valid, k_idx - q_idx < window)
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_ref[:, :]
        l_prev = l_ref[:, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:, :] = acc_ref[:, :] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:, :] = m_new

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
    def _finish():
        # A row with NO visible key keeps m at _NEG_INF: inside a
        # computed tile its p = exp(-1e30 - (-1e30)) = 1 per entry, so
        # acc holds a garbage mean-of-V — zero those rows explicitly to
        # honor the fully-masked-rows-return-0 contract.
        dead = m_ref[:, :] <= _MASKED_ROW_LSE
        o_ref[0, 0, :, :] = jnp.where(
            dead, 0.0,
            acc_ref[:, :] / jnp.maximum(l_ref[:, :], 1e-30)
        ).astype(o_ref.dtype)
        # log-sum-exp per query row, the backward pass's softmax residual;
        # fully-masked / padded rows yield ~-1e30 (backward zeroes them).
        # Layout is [B, H, 1, T]: a trailing dim of 1 would be tile-padded
        # to 128 (a 128x HBM expansion, enough to OOM a 6-layer model).
        lse_ref[0, 0, 0, :] = (
            m_ref[:, :] + jnp.log(jnp.maximum(l_ref[:, :], 1e-30))
        )[:, 0]


def _flash_forward(q, k, v, kv_mask, causal, sm_scale, block_q, block_k,
                   interpret, kv_group=1, window=0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, d = q.shape
    S = k.shape[2]
    # grouped-query attention: K/V carry H // kv_group heads and each
    # serves kv_group query heads THROUGH THE INDEX MAP — the repeated
    # K/V never materializes (a custom call can't fold a broadcast
    # operand the way XLA fuses one)
    g = int(kv_group)
    if g < 1 or k.shape[1] * g != H:
        raise ValueError(
            "flash_attention: kv heads (%d) * kv_group (%d) must "
            "equal query heads (%d)" % (k.shape[1], g, H))
    block_q = min(block_q, T)
    block_k = min(block_k, S)

    # Pad T/S to block multiples; padded keys are masked inside the kernel
    # via seq_k, padded queries are sliced off after.
    T_pad = -T % block_q
    S_pad = -S % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, T_pad), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, S_pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, S_pad), (0, 0)))
    Tp, Sp = T + T_pad, S + S_pad
    n_kv = Sp // block_k

    has_mask = kv_mask is not None
    if has_mask:
        # [B, S] validity -> [B, 1, S] so the block's last two dims are
        # (1, block_k): dim -2 equals the array dim, dim -1 divides 128
        # (Mosaic tiling rule).
        kvm = jnp.pad(kv_mask.astype(jnp.float32), ((0, 0), (0, S_pad)))
        kvm = kvm[:, None, :]
    else:
        kvm = jnp.ones((B, 1, block_k), jnp.float32)

    kernel = functools.partial(
        _flash_kernel,
        sm_scale=sm_scale,
        causal=causal,
        seq_k=S,
        block_q=block_q,
        block_k=block_k,
        n_kv=n_kv,
        has_mask=has_mask,
        window=int(window),
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, H, Tp // block_q, n_kv),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda b, h, i, j: (b, h // g, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda b, h, i, j: (b, h // g, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k),
                (lambda b, h, i, j: (b, 0, j)) if has_mask
                else (lambda b, h, i, j: (b, 0, 0)),
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0)
            ),
            pl.BlockSpec(
                (1, 1, 1, block_q), lambda b, h, i, j: (b, h, 0, i)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tp, d), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, Tp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
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


def _bwd_tile_grads(q, k, v, do, lse, delta, valid, sm_scale):
    """Shared per-tile backward math. q/do: [bq, d]; k/v: [bk, d];
    lse/delta: [bq, 1]; valid: [bq, bk] bool (key validity + causal +
    row validity). Returns (dS_scaled [bq, bk], p [bq, bk])."""
    s = jax.lax.dot_general(
        q * sm_scale, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    p = jnp.where(valid, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta) * sm_scale
    return ds, p


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          kvm_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                          sm_scale, causal, seq_q, seq_k, block_q, block_k,
                          n_q, has_mask, n_group=1, window=0):
    """Grid (b, hkv, kj, gi, qi), q innermost: accumulate dK/dV for one
    K/V tile across all Q tiles — and, under grouped-query attention,
    across the n_group query heads this kv head serves (the gi axis);
    VMEM accumulators persist over the (gi, qi) steps."""
    from jax.experimental import pallas as pl

    kj = pl.program_id(2)
    gi = pl.program_id(3)
    qi = pl.program_id(4)

    @pl.when((gi == 0) & (qi == 0))
    def _init():
        dk_acc[:, :] = jnp.zeros_like(dk_acc)
        dv_acc[:, :] = jnp.zeros_like(dv_acc)

    q_base = qi * block_q
    k_base = kj * block_k

    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32)
        k = k_ref[0, 0, :, :].astype(jnp.float32)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        do = do_ref[0, 0, :, :].astype(jnp.float32)
        lse = lse_ref[0, 0, 0, :][:, None]
        delta = delta_ref[0, 0, 0, :][:, None]
        q_idx = q_base + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_idx = k_base + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        # row validity: padded / fully-masked rows have lse ~ -1e30 and
        # must contribute nothing (exp(s - lse) would blow up there)
        valid = (q_idx < seq_q) & (k_idx < seq_k) & (lse > _MASKED_ROW_LSE)
        if has_mask:
            valid &= kvm_ref[0, 0, :][None, :] > 0
        if causal:
            valid &= k_idx <= q_idx
        if window:
            valid &= q_idx - k_idx < window
            if not causal:
                valid &= k_idx - q_idx < window
        ds, p = _bwd_tile_grads(q, k, v, do, lse, delta, valid, sm_scale)
        dv_acc[:, :] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc[:, :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

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
        dk_ref[0, 0, :, :] = dk_acc[:, :].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[:, :].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         kvm_ref, dq_ref, dq_acc, *, sm_scale, causal,
                         seq_q, seq_k, block_q, block_k, n_kv, has_mask,
                         window=0):
    """Grid (b, h, qi, kj), kv innermost: accumulate dQ for one Q tile."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        dq_acc[:, :] = jnp.zeros_like(dq_acc)

    q_base = qi * block_q
    k_base = kj * block_k

    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32)
        k = k_ref[0, 0, :, :].astype(jnp.float32)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        do = do_ref[0, 0, :, :].astype(jnp.float32)
        lse = lse_ref[0, 0, 0, :][:, None]
        delta = delta_ref[0, 0, 0, :][:, None]
        q_idx = q_base + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_idx = k_base + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = (q_idx < seq_q) & (k_idx < seq_k) & (lse > _MASKED_ROW_LSE)
        if has_mask:
            valid &= kvm_ref[0, 0, :][None, :] > 0
        if causal:
            valid &= k_idx <= q_idx
        if window:
            valid &= q_idx - k_idx < window
            if not causal:
                valid &= k_idx - q_idx < window
        ds, _ = _bwd_tile_grads(q, k, v, do, lse, delta, valid, sm_scale)
        dq_acc[:, :] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

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
        dq_ref[0, 0, :, :] = dq_acc[:, :].astype(dq_ref.dtype)


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
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    T_pad = -T % block_q
    S_pad = -S % block_k
    Tp, Sp = T + T_pad, S + S_pad
    n_q, n_kv = Tp // block_q, Sp // block_k

    pad_q = ((0, 0), (0, 0), (0, T_pad), (0, 0))
    pad_k = ((0, 0), (0, 0), (0, S_pad), (0, 0))
    qp = jnp.pad(q, pad_q)
    kp = jnp.pad(k, pad_k)
    vp = jnp.pad(v, pad_k)
    dop = jnp.pad(dout.astype(jnp.float32), pad_q)
    # delta_i = rowsum(dO * O): one cheap fused elementwise+reduce in XLA;
    # [B, H, 1, T] layout like lse (trailing-1 dims tile-pad 128x)
    delta = jnp.pad(
        jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                axis=-1)[:, :, None, :],
        ((0, 0), (0, 0), (0, 0), (0, T_pad)),
    )
    # lse comes back from the forward already padded to Tp

    has_mask = kv_mask is not None
    if has_mask:
        kvm = jnp.pad(kv_mask.astype(jnp.float32), ((0, 0), (0, S_pad)))
        kvm = kvm[:, None, :]
    else:
        kvm = jnp.ones((B, 1, block_k), jnp.float32)

    # dkv grid: (b, kv-head, kv-block, group-member, q-block); q-side
    # tensors index the ACTUAL query head hk * grp + gi
    q_spec = pl.BlockSpec(
        (1, 1, block_q, d),
        lambda b, hk, j, gi, i: (b, hk * grp + gi, i, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, d), lambda b, hk, j, gi, i: (b, hk, j, 0))
    row_spec = pl.BlockSpec(
        (1, 1, 1, block_q),
        lambda b, hk, j, gi, i: (b, hk * grp + gi, 0, i))
    kvm_spec = pl.BlockSpec(
        (1, 1, block_k),
        (lambda b, hk, j, gi, i: (b, 0, j)) if has_mask
        else (lambda b, hk, j, gi, i: (b, 0, 0)),
    )
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
            seq_q=T, seq_k=S, block_q=block_q, block_k=block_k, n_q=n_q,
            has_mask=has_mask, n_group=grp, window=int(window),
        ),
        grid=(B, Hkv, n_kv, grp, n_q),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec,
                  kvm_spec],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, hk, j, gi, i: (b, hk, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, hk, j, gi, i: (b, hk, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, Sp, d), k.dtype),
            jax.ShapeDtypeStruct((B, Hkv, Sp, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name=BWD_DKV_KERNEL_NAME,
        # dk/dv accumulate over the (gi, qi) inner dims; (b, hk, kj)
        # tiles are independent
        **_mosaic_params(interpret,
                         ("parallel",) * 3 + ("arbitrary",) * 2),
    )(qp, kp, vp, dop, lse, delta, kvm)

    q_spec2 = pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0))
    kv_spec2 = pl.BlockSpec(
        (1, 1, block_k, d), lambda b, h, i, j: (b, h // grp, j, 0))
    row_spec2 = pl.BlockSpec((1, 1, 1, block_q), lambda b, h, i, j: (b, h, 0, i))
    kvm_spec2 = pl.BlockSpec(
        (1, 1, block_k),
        (lambda b, h, i, j: (b, 0, j)) if has_mask
        else (lambda b, h, i, j: (b, 0, 0)),
    )
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
            seq_q=T, seq_k=S, block_q=block_q, block_k=block_k, n_kv=n_kv,
            has_mask=has_mask, window=int(window),
        ),
        grid=(B, H, n_q, n_kv),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2,
                  row_spec2, kvm_spec2],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Tp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
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
    """Fused attention. q:[B,H,T,d], k,v:[B,H,S,d] -> [B,H,T,d].

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
