"""The gated delta rule with per-channel decay (Kimi Delta Attention,
arXiv:2510.26692) for SERVING: a chunked prefill over prompts of different
lengths and the one-token update of every slot's state.

A head keeps a MATRIX ``S`` ``[dk (key), dv (value)]`` float32. A token
brings a query ``q`` and a key ``k`` (both L2-normalised over the head's
``dk``, the query also scaled by ``dk ** -0.5``), a value ``v``, a log
decay ``g <= 0`` a KEY CHANNEL and a step ``beta`` a head:

    S' = Diag(exp(g_t)) S_{t-1}
    w  = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t w^T                       o_t = S_t^T q_t

``beta`` may reach 2 (``kda_allow_neg_eigval``): ``I - beta k k^T`` then
has an eigenvalue in (-1, 1). The state is ``[slots, heads, dk, dv]`` with
``dv`` on the lanes: one layout from the parameter to the kernel operand.

Two kernels, each with its composed ``jax.numpy`` reference beside it (the
explicit oracle, and the default off the TPU, as ``selective_scan``
routes):

* ``state_update`` (``delta_rule_state_update``): one token for every
  slot, the state updated IN PLACE (``input_output_aliases``), ``o`` fused
  so that a token reads its state once and writes it once. Grid ``(slot,
  heads / 32)``; the per-channel columns ``exp(g)``, ``k``, ``q`` and
  ``beta k`` of a block's heads are one ``[128, 128]`` tile transposed
  once. A slot that is not live keeps its state and reads ``o`` = 0.
* ``chunk_prefill`` (``delta_rule_chunk_prefill``): grid ``(prompt, head,
  time block)``; the state lives in VMEM across a prompt's blocks and the
  prompt lengths are scalar-prefetched. A block walks the chunks (64
  tokens) that hold REAL tokens only. With ``G`` the running sum of ``g``
  inside a chunk, ``S_0`` the incoming state, ``P[r, i] = sum_c k_rc k_ic
  exp(G_rc - G_ic)`` and ``R`` the same with ``q_r``, a chunk solves

      (I + Diag(beta) strict_lower(P)) W = Diag(beta) (V - (K exp(G)) S_0)
      O = (Q exp(G)) S_0 + lower(R) W
      S_C = Diag(exp(G_C)) S_0 + (K exp(G_C - G))^T W

  by forward substitution, 16 rows a sub-block: the blocks under the
  diagonal are products on the matrix unit with both factors decayed
  against the sub-block's own first row (each at most 1: ``exp(-G)`` alone
  is not bounded), the diagonal ones are formed pair by pair. Past a
  prompt's last real token ``beta`` = 0 and ``g`` = 0, which leave ``S``
  exactly as it is: a bucket's padding is nothing to the state. Products
  that touch the state are float32 at the highest precision.

A kernel the compiler refuses raises ``KernelCompileError``.
"""

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.flash_attention import _mosaic_params
from paddle_tpu.kernels.selective_scan import _route

STATE_KERNEL_NAME = "delta_rule_state_update"
CHUNK_KERNEL_NAME = "delta_rule_chunk_prefill"

CHUNK = 64        # tokens a chunk (the public kernels')
SUB_BLOCK = 16    # rows solved pair by pair
L2_EPS = 1e-6

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def l2_normalise(x, scale=1.0):
    """``x / sqrt(sum(x^2) + eps) * scale`` over the minor axis, float32."""
    x = x.astype(_F32)
    return x * (jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                              + L2_EPS) * scale)


def _heads(x, H):
    return x.reshape(x.shape[:-1] + (H, -1))


# -- references ---------------------------------------------------------------

def token_step(state, q, k, v, g, beta):
    """One token of the recurrence on normalised ``q``, ``k`` [..., dk],
    ``v`` [..., dv], ``g`` [..., dk], ``beta`` [...] and ``state`` [...,
    dk, dv]. Returns (o [..., dv], the state after the token)."""
    sp = jnp.exp(g)[..., :, None] * state
    w = beta[..., None] * (v - jnp.sum(k[..., :, None] * sp, axis=-2))
    new = sp + k[..., :, None] * w[..., None, :]
    return jnp.sum(q[..., :, None] * new, axis=-2), new


def state_update_reference(state, q, k, v, g, beta, live):
    """state: [S, H, dk, dv] float32; q, k: [S, H * dk]; v: [S, H * dv];
    g: [S, H * dk] float32; beta: [S, H] float32; live: [S]. Returns (o
    [S, H * dv] float32, the state after this token; a slot that is not
    live keeps its own and reads o = 0)."""
    H, dk = state.shape[1], state.shape[2]
    o, new = token_step(
        state, l2_normalise(_heads(q, H), dk ** -0.5),
        l2_normalise(_heads(k, H)), _heads(v, H).astype(_F32),
        _heads(g, H).astype(_F32), beta.astype(_F32))
    keep = (live > 0)[:, None, None]
    return (jnp.where(keep, o, 0.0).reshape(o.shape[0], -1),
            jnp.where(keep[..., None], new, state))


def chunk_prefill_reference(q, k, v, g, beta, lengths):
    """The recurrence as a plain loop over ``t``. q, k: [B, T, H * dk]; v:
    [B, T, H * dv]; g: [B, T, H * dk] float32; beta: [B, T, H] float32;
    lengths: [B]. Returns (o [B, T, H * dv] float32, 0 past a prompt's
    length; state [B, H, dk, dv] float32 after each prompt's last real
    token)."""
    B, T, H = beta.shape
    dk = q.shape[-1] // H
    qn = l2_normalise(_heads(q, H), dk ** -0.5)
    kn = l2_normalise(_heads(k, H))
    v32, g32 = _heads(v, H).astype(_F32), _heads(g, H).astype(_F32)

    def step(s, t):
        o, new = token_step(s, qn[:, t], kn[:, t], v32[:, t], g32[:, t],
                            beta[:, t].astype(_F32))
        real = (t < lengths)[:, None, None]
        return (jnp.where(real[..., None], new, s), jnp.where(real, o, 0.0))

    s0 = jnp.zeros((B, H, dk, v32.shape[-1]), _F32)
    s, os = jax.lax.scan(step, s0, jnp.arange(T))
    return jnp.transpose(os, (1, 0, 2, 3)).reshape(B, T, -1), s


# -- one token for every slot -------------------------------------------------

def _update_kernel(live_ref, s_ref, a_ref, k_ref, q_ref, bk_ref, bv_ref,
                   o_ref, out_ref, *, hb):
    from jax.experimental import pallas as pl

    keep = live_ref[pl.program_id(0)] > 0
    # the per-channel vectors of the block's heads as COLUMNS [dk, 4 hb]
    cols = jnp.concatenate(
        [a_ref[0], k_ref[0], q_ref[0], bk_ref[0]], axis=0).T
    for h in range(hb):          # a head at a time: every value is 2-D
        s = s_ref[0, h]                                    # [dk, dv]
        a_c, k_c, q_c, bk_c = [cols[:, j * hb + h:j * hb + h + 1]
                               for j in range(4)]          # [dk, 1]
        sp = a_c * s
        w = bv_ref[0, h:h + 1, :] - jnp.sum(bk_c * sp, axis=0,
                                            keepdims=True)  # [1, dv]
        new = sp + k_c * w
        o = jnp.sum(q_c * new, axis=0, keepdims=True)
        out_ref[0, h] = jnp.where(keep, new, s)
        o_ref[0, h:h + 1, :] = jnp.where(keep, o, 0.0)


def _update_pallas(state, q, k, v, g, beta, live, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, dk, dv = state.shape
    hb = 32 if H % 32 == 0 else H
    # a few rows a slot: the norms and exp(g) are the wrapper's
    kn = l2_normalise(_heads(k, H))
    b = beta.astype(_F32)[..., None]
    rows = pl.BlockSpec((1, hb, dk), lambda i, j, live: (i, j, 0))
    vals = pl.BlockSpec((1, hb, dv), lambda i, j, live: (i, j, 0))
    st = pl.BlockSpec((1, hb, dk, dv), lambda i, j, live: (i, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(S, H // hb),
        in_specs=[st, rows, rows, rows, rows, vals], out_specs=[vals, st])
    o, new = pl.pallas_call(
        functools.partial(_update_kernel, hb=hb), grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, H, dv), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 0 is the scalar-prefetched live mask
        input_output_aliases={1: 1},
        interpret=interpret, name=STATE_KERNEL_NAME,
        **_mosaic_params(interpret, ("parallel", "parallel")),
    )(live.astype(jnp.int32).reshape(S), state,
      jnp.exp(_heads(g, H).astype(_F32)), kn,
      l2_normalise(_heads(q, H), dk ** -0.5), b * kn,
      b * _heads(v, H).astype(_F32))
    return o.reshape(S, H * dv), new


# -- the chunked prefill ------------------------------------------------------

def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HIGHEST,
                               preferred_element_type=_F32)


def _chunk_kernel(len_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref,
                  fin_ref, s_ref, w_ref, *, C, sub, n_blocks, scale):
    from jax.experimental import pallas as pl

    p, h, tb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    L, dk = q_ref.shape[1], q_ref.shape[2]

    @pl.when(tb == 0)
    def _start():
        s_ref[...] = jnp.zeros_like(s_ref)

    o_ref[...] = jnp.zeros_like(o_ref)
    real = jnp.clip(len_ref[p] - tb * L, 0, L)

    def iota(shape, axis):
        return jax.lax.broadcasted_iota(jnp.int32, shape, axis)

    tril = (iota((C, C), 0) >= iota((C, C), 1)).astype(_F32)
    eye = iota((dk, dk), 0) == iota((dk, dk), 1)
    row_c, row_s = iota((C, 1), 0), iota((sub, 1), 0)
    mine = iota((C, beta_ref.shape[2]), 1) == h

    def chunk(c, carry):
        r0 = pl.multiple_of(c * C, C)
        at = pl.ds(r0, C)
        here = r0 + row_c < real                           # [C, 1]
        qc = q_ref[0, at, :].astype(_F32)
        kc = k_ref[0, at, :].astype(_F32)
        vc = v_ref[0, at, :].astype(_F32)
        # past the prompt's last token g = 0 and beta = 0: S stays as it is
        gc = jnp.where(here, g_ref[0, at, :], 0.0)
        bc = jnp.where(here, jnp.sum(
            jnp.where(mine, beta_ref[0, at, :], 0.0), axis=1,
            keepdims=True), 0.0)                           # [C, 1]
        qn = qc * (jax.lax.rsqrt(jnp.sum(qc * qc, axis=1, keepdims=True)
                                 + L2_EPS) * scale)
        kn = kc * jax.lax.rsqrt(jnp.sum(kc * kc, axis=1, keepdims=True)
                                + L2_EPS)
        G = _dot(tril, gc, ((1,), (0,)))                   # running sum
        s0 = s_ref[...]
        gam = jnp.exp(G)
        rhs = bc * (vc - _dot(kn * gam, s0, ((1,), (0,))))
        o_in = _dot(qn * gam, s0, ((1,), (0,)))
        for lo in range(0, C, sub):
            rows = slice(lo, lo + sub)
            GI, kI, qI, bI = G[rows], kn[rows], qn[rows], bc[rows]
            wb, oI = rhs[rows], o_in[rows]
            if lo:
                # against the sub-block's first row both factors are <= 1
                first = GI[0:1]
                k_before = kn[:lo] * jnp.exp(first - G[:lo])
                decay = jnp.exp(GI - first)
                w_before = w_ref[0:lo, :]
                wb = wb - bI * _dot(
                    _dot(kI * decay, k_before, ((1,), (1,))), w_before,
                    ((1,), (0,)))
                oI = oI + _dot(
                    _dot(qI * decay, k_before, ((1,), (1,))), w_before,
                    ((1,), (0,)))
            for i in range(sub):
                # column i of the diagonal block, pair by pair
                ke = kI[i:i + 1] * jnp.exp(
                    jnp.minimum(GI - GI[i:i + 1], 0.0))
                wi = wb[i:i + 1]                           # final by now
                oI = oI + jnp.where(
                    row_s >= i, jnp.sum(qI * ke, axis=1, keepdims=True),
                    0.0) * wi
                if i < sub - 1:
                    wb = wb - jnp.where(
                        row_s > i,
                        bI * jnp.sum(kI * ke, axis=1, keepdims=True),
                        0.0) * wi
            w_ref[rows, :] = wb
            o_ref[0, pl.ds(pl.multiple_of(r0 + lo, sub), sub), :] = \
                jnp.where(here[rows], oI, 0.0)
        last = G[C - 1:C]                                  # [1, dk]
        last_col = jnp.sum(jnp.where(eye, jnp.exp(last), 0.0), axis=1,
                           keepdims=True)                  # [dk, 1]
        s_ref[...] = last_col * s0 + _dot(
            kn * jnp.exp(last - G), w_ref[...], ((0,), (0,)))
        return carry

    jax.lax.fori_loop(0, (real + C - 1) // C, chunk, 0)

    @pl.when(tb == n_blocks - 1)
    def _finish():
        fin_ref[0, 0] = s_ref[...]


def _chunk_pallas(q, k, v, g, beta, lengths, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H = beta.shape
    dk, dv = q.shape[-1] // H, v.shape[-1] // H
    C = CHUNK if T % CHUNK == 0 else T
    sub = SUB_BLOCK if C % SUB_BLOCK == 0 else C
    L = next(C * n for n in (4, 2, 1) if T % (C * n) == 0)
    keys = pl.BlockSpec((1, L, dk), lambda p, h, tb, lens: (p, tb, h))
    vals = pl.BlockSpec((1, L, dv), lambda p, h, tb, lens: (p, tb, h))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B, H, T // L),
        in_specs=[keys, keys, vals, keys,
                  pl.BlockSpec((1, L, H), lambda p, h, tb, lens: (p, tb, 0))],
        out_specs=[vals, pl.BlockSpec((1, 1, dk, dv),
                                      lambda p, h, tb, lens: (p, h, 0, 0))],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32),
                        pltpu.VMEM((C, dv), _F32)])
    return pl.pallas_call(
        functools.partial(_chunk_kernel, C=C, sub=sub, n_blocks=T // L,
                          scale=dk ** -0.5),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, T, H * dv), _F32),
                   jax.ShapeDtypeStruct((B, H, dk, dv), _F32)],
        interpret=interpret, name=CHUNK_KERNEL_NAME,
        **_mosaic_params(interpret, ("parallel", "parallel", "arbitrary")),
    )(lengths.astype(jnp.int32), q, k, v, g.astype(_F32),
      beta.astype(_F32))


# -- entry points -------------------------------------------------------------

def state_update(state, q, k, v, g, beta, live, force_reference=False,
                 force_pallas=False):
    """One token of the delta rule for every slot, ``o`` fused
    (``state_update_reference`` has the shapes)."""
    return _route(STATE_KERNEL_NAME, _update_pallas, state_update_reference,
                  (state, q, k, v, g, beta, live), force_reference,
                  force_pallas)


def chunk_prefill(q, k, v, g, beta, lengths, force_reference=False,
                  force_pallas=False):
    """The delta rule over ``[B, T, ...]`` prompts of ``lengths`` real
    tokens, in chunks (``chunk_prefill_reference`` has the shapes)."""
    return _route(CHUNK_KERNEL_NAME, _chunk_pallas, chunk_prefill_reference,
                  (q, k, v, g, beta, lengths), force_reference, force_pallas)
