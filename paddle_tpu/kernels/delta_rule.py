"""The gated delta rule for SERVING, with a decay a KEY CHANNEL (Kimi
Delta Attention, arXiv:2510.26692) or a decay a HEAD (Gated DeltaNet,
arXiv:2412.06464): a chunked prefill over prompts of different lengths and
the one-token update of every slot's state.

A head keeps a MATRIX ``S`` ``[dk (key), dv (value)]`` float32; ``dk`` and
``dv`` need not be equal, nor multiples of the 128 lanes (96 beside 192).
A token brings a query ``q`` and a key ``k`` (both L2-normalised over the
head's ``dk``, the query also scaled by ``dk ** -0.5``), a value ``v``, a
log decay ``g <= 0`` and a step ``beta`` a head. ``g`` is ``[.., H * dk]``,
a number a key channel, or ``[.., H]``, one number a head; the shape says
which, and the scalar form is never broadcast outside a kernel:

    S' = Diag(exp(g_t)) S_{t-1}              (exp(g_t) S_{t-1} a head)
    w  = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t w^T                       o_t = S_t^T q_t

``beta`` may reach 2 (``kda_allow_neg_eigval``, ``linear_allow_neg_eigval``):
``I - beta k k^T`` then has an eigenvalue in (-1, 1). The state is
``[slots, heads / pack, dk, pack * dv]`` with the values on the lanes:
``pack`` = 1 is a head a tile, ``[slots, heads, dk, dv]``; ``pack`` = 2
lays two heads' value lanes side by side, so that heads of 192 values
fill three whole 128-lane tiles and the array in HBM is no larger than its
elements (alone they would be stored as 256). ``pack_heads`` /
``unpack_heads`` go between the two; the kernels read ``pack`` from the
state's shape and ``beta``'s.

Two kernels, each with its composed ``jax.numpy`` reference beside it (the
explicit oracle, and the default off the TPU, as ``selective_scan``
routes):

* ``state_update`` (``delta_rule_state_update``): one token for every
  slot, the state updated IN PLACE (``input_output_aliases``), ``o`` fused
  so that a token reads its state once and writes it once. Grid ``(slot,
  heads / hb)`` with ``hb`` = 32 heads a step where 32 divides the heads
  and every head of the slot where it does not (30: the block is then the
  whole axis, which Mosaic takes at any size); the per-channel rows
  (``k``, ``q``, ``beta k`` and, with a decay a channel, ``exp(g)``) of a
  block's heads are one ``[3 or 4 x hb, dk]`` tile transposed once into
  columns (``[128, 128]`` at 32 heads of 128), a scalar decay is a ``[1,
  hb]`` row. A slot that is not live keeps its state and reads ``o`` = 0.
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
  is not bounded), the diagonal ones are formed pair by pair. With a
  decay a HEAD ``G`` is a number a row and ``P[r, i] = (k_r . k_i) exp(G_r
  - G_i)``: ONE product of the undecayed keys on the matrix unit under a
  ``[C, C]`` decay (``G_r - G_i <= 0`` under the diagonal, so it is
  bounded as it stands), ``R`` likewise, and ``lower(R) W`` one product a
  chunk. Past a prompt's last real token ``beta`` = 0 and ``g`` = 0, which
  leave ``S`` exactly as it is: a bucket's padding is nothing to the
  state. Products that touch the state are float32 at the highest
  precision. A head whose ``dk`` and ``dv`` are both lane multiples is a
  lane block of the token rows ``[B, T, H * d]`` as they come; any other
  width is not a block Mosaic takes, and the wrapper turns the rows head-
  major ``[B, H, T, d]`` first (a block's minor axis is then the whole
  of the array's).

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

def pack_heads(state, pack):
    """``[..., H, dk, dv]`` as the served ``[..., H / pack, dk, pack *
    dv]``: ``pack`` heads' value lanes side by side."""
    if pack == 1:
        return state
    *lead, H, dk, dv = state.shape
    x = state.reshape(*lead, H // pack, pack, dk, dv)
    return jnp.swapaxes(x, -3, -2).reshape(*lead, H // pack, dk, pack * dv)


def unpack_heads(state, heads):
    """The served ``[..., heads / pack, dk, pack * dv]`` as ``[..., heads,
    dk, dv]``."""
    *lead, tiles, dk, width = state.shape
    pack = heads // tiles
    if pack == 1:
        return state
    x = state.reshape(*lead, tiles, dk, pack, width // pack)
    return jnp.swapaxes(x, -3, -2).reshape(*lead, heads, dk, width // pack)


def _pack_of(state, heads):
    """How many heads share a tile of ``state``; a state that ``heads``
    heads cannot lie in is refused by its shape."""
    tiles = state.shape[-3]
    pack = heads // max(tiles, 1)
    if tiles * pack != heads or state.shape[-1] % max(pack, 1):
        raise ValueError(
            "a delta-rule state is [slots, heads / pack, dk, pack * dv]; "
            "%s cannot hold %d heads" % (tuple(state.shape), heads))
    return pack


def _decay_a_head(g, heads, dk):
    """``g`` is one number a head (``[.., H]``), not a key channel's."""
    return g.shape[-1] == heads and dk != 1


def token_step(state, q, k, v, g, beta):
    """One token of the recurrence on normalised ``q``, ``k`` [..., dk],
    ``v`` [..., dv], ``g`` [..., dk] (a key channel) or [..., 1] (a
    head), ``beta`` [...] and ``state`` [..., dk, dv]. Returns (o [...,
    dv], the state after the token)."""
    sp = jnp.exp(g)[..., :, None] * state
    w = beta[..., None] * (v - jnp.sum(k[..., :, None] * sp, axis=-2))
    new = sp + k[..., :, None] * w[..., None, :]
    return jnp.sum(q[..., :, None] * new, axis=-2), new


def state_update_reference(state, q, k, v, g, beta, live):
    """state: [S, H / pack, dk, pack * dv] float32; q, k: [S, H * dk]; v:
    [S, H * dv]; g: [S, H * dk] or [S, H] float32; beta: [S, H] float32;
    live: [S]. Returns (o [S, H * dv] float32, the state after this token;
    a slot that is not live keeps its own and reads o = 0)."""
    H, dk = beta.shape[-1], state.shape[2]
    pack = _pack_of(state, H)
    o, new = token_step(
        unpack_heads(state, H), l2_normalise(_heads(q, H), dk ** -0.5),
        l2_normalise(_heads(k, H)), _heads(v, H).astype(_F32),
        _heads(g, H).astype(_F32), beta.astype(_F32))
    keep = (live > 0)[:, None, None]
    return (jnp.where(keep, o, 0.0).reshape(o.shape[0], -1),
            jnp.where(keep[..., None], pack_heads(new, pack), state))


def chunk_prefill_reference(q, k, v, g, beta, lengths):
    """The recurrence as a plain loop over ``t``. q, k: [B, T, H * dk]; v:
    [B, T, H * dv]; g: [B, T, H * dk] or [B, T, H] float32; beta: [B, T,
    H] float32; lengths: [B]. Returns (o [B, T, H * dv] float32, 0 past a
    prompt's length; state [B, H, dk, dv] float32 after each prompt's last
    real token)."""
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

def _side_by_side(parts, dv):
    """``parts`` (one a head of a tile, each ``[dk, 1]`` or ``[1, 1]``)
    spread over the tile's lanes: head ``j``'s over its ``dv`` value
    lanes. One head a tile is its part as it is."""
    out = parts[-1]
    if len(parts) > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, len(parts) * dv), 1)
        for j in range(len(parts) - 2, -1, -1):
            out = jnp.where(lane < (j + 1) * dv, parts[j], out)
    return out


def _update_kernel(live_ref, s_ref, a_ref, k_ref, q_ref, bk_ref, bv_ref,
                   o_ref, out_ref, *, hb, pack, scalar):
    from jax.experimental import pallas as pl

    keep = live_ref[pl.program_id(0)] > 0
    dv = s_ref.shape[3] // pack
    # the per-channel vectors of the block's heads as COLUMNS [dk, n hb]
    rows = [k_ref[0], q_ref[0], bk_ref[0]]
    cols = jnp.concatenate(([] if scalar else [a_ref[0]]) + rows, axis=0).T
    first = 0 if scalar else 1
    if scalar:
        # a head's scalar spread over the lanes first: Mosaic takes no
        # broadcast over sublanes and lanes at once
        a_rows = jnp.broadcast_to(a_ref[0], (hb, pack * dv))
        head_row = jax.lax.broadcasted_iota(jnp.int32, (hb, 1), 0)
    for t in range(hb // pack):  # a tile at a time: every value is 2-D
        heads = range(t * pack, (t + 1) * pack)
        s = s_ref[0, t]                                    # [dk, pack dv]

        def col(j):                 # [dk, pack dv] from the heads' [dk, 1]
            return _side_by_side(
                [cols[:, j * hb + h:j * hb + h + 1] for h in heads], dv)

        if not scalar:
            a_c = col(0)
        elif pack > 1:
            a_c = _side_by_side([a_rows[h:h + 1, :] for h in heads], dv)
        else:
            # a slice here would fold into ONE [1, 1] -> [dk, dv] broadcast
            a_c = jnp.sum(jnp.where(head_row == t, a_rows, 0.0), axis=0,
                          keepdims=True)
        k_c, q_c, bk_c = [col(first + j) for j in range(3)]
        sp = a_c * s
        w = bv_ref[0, t:t + 1, :] - jnp.sum(bk_c * sp, axis=0,
                                            keepdims=True)  # [1, pack dv]
        new = sp + k_c * w
        o = jnp.sum(q_c * new, axis=0, keepdims=True)
        out_ref[0, t] = jnp.where(keep, new, s)
        o_ref[0, t:t + 1, :] = jnp.where(keep, o, 0.0)


def _update_pallas(state, q, k, v, g, beta, live, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, tiles, dk, width = state.shape
    H = beta.shape[-1]
    pack = _pack_of(state, H)
    scalar = _decay_a_head(g, H, dk)
    # heads a grid step: 32 where that divides them (whole sublane tiles
    # of per-channel rows), else every head of the slot (a block that is
    # the whole axis is taken at any size)
    hb = 32 if H % 32 == 0 and 32 % pack == 0 else H
    # a few rows a slot: the norms and exp(g) are the wrapper's
    kn = l2_normalise(_heads(k, H))
    b = beta.astype(_F32)[..., None]
    rows = pl.BlockSpec((1, hb, dk), lambda i, j, live: (i, j, 0))
    vals = pl.BlockSpec((1, hb // pack, width),
                        lambda i, j, live: (i, j, 0))
    st = pl.BlockSpec((1, hb // pack, dk, width),
                      lambda i, j, live: (i, j, 0, 0))
    # a decay a head is a [hb, 1] column of scalars, a channel's a row
    decay = _heads(jnp.exp(g.astype(_F32)), H)
    a_spec = pl.BlockSpec((1, hb, 1), lambda i, j, live: (i, j, 0)) \
        if scalar else rows
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(S, H // hb),
        in_specs=[st, a_spec, rows, rows, rows, vals],
        out_specs=[vals, st])
    o, new = pl.pallas_call(
        functools.partial(_update_kernel, hb=hb, pack=pack, scalar=scalar),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, tiles, width), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 0 is the scalar-prefetched live mask
        input_output_aliases={1: 1},
        interpret=interpret, name=STATE_KERNEL_NAME,
        **_mosaic_params(interpret, ("parallel", "parallel")),
    )(live.astype(jnp.int32).reshape(S), state, decay, kn,
      l2_normalise(_heads(q, H), dk ** -0.5), b * kn,
      (b * _heads(v, H).astype(_F32)).reshape(S, tiles, width))
    return o.reshape(S, tiles * width), new


# -- the chunked prefill ------------------------------------------------------

def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HIGHEST,
                               preferred_element_type=_F32)


def _chunk_kernel(len_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref,
                  fin_ref, s_ref, w_ref, *, C, sub, n_blocks, scale, scalar):
    """A grid step: a prompt's head over ``L`` tokens (the refs are ``[L,
    dk]`` / ``[L, dv]``; ``beta`` and a scalar ``g`` ``[L, H]``). ``scalar``:
    the decay is a number a head (module docstring)."""
    from jax.experimental import pallas as pl

    p, h, tb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    L, dk = q_ref.shape

    @pl.when(tb == 0)
    def _start():
        s_ref[...] = jnp.zeros_like(s_ref)

    o_ref[...] = jnp.zeros_like(o_ref)
    real = jnp.clip(len_ref[p] - tb * L, 0, L)

    def iota(shape, axis):
        return jax.lax.broadcasted_iota(jnp.int32, shape, axis)

    lower = iota((C, C), 0) >= iota((C, C), 1)
    tril = lower.astype(_F32)
    eye = iota((dk, dk), 0) == iota((dk, dk), 1)
    row_c, row_s = iota((C, 1), 0), iota((sub, 1), 0)
    mine = iota((C, beta_ref.shape[1]), 1) == h

    def of_head(ref, at):           # this head's column of [L, H]: [C, 1]
        return jnp.sum(jnp.where(mine, ref[at, :], 0.0), axis=1,
                       keepdims=True)

    def per_channel(r0, here, qn, kn, vc, bc, s0):
        """A chunk under a decay a key channel; returns the state."""
        gc = jnp.where(here, g_ref[pl.ds(r0, C), :], 0.0)
        G = _dot(tril, gc, ((1,), (0,)))                   # running sum
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
            o_ref[pl.ds(pl.multiple_of(r0 + lo, sub), sub), :] = \
                jnp.where(here[rows], oI, 0.0)
        last = G[C - 1:C]                                  # [1, dk]
        last_col = jnp.sum(jnp.where(eye, jnp.exp(last), 0.0), axis=1,
                           keepdims=True)                  # [dk, 1]
        return last_col * s0 + _dot(
            kn * jnp.exp(last - G), w_ref[...], ((0,), (0,)))

    def per_head(r0, here, qn, kn, vc, bc, s0):
        """A chunk under a decay a head: the keys' products undecayed on
        the matrix unit, the decay a ``[C, C]`` factor."""
        gc = jnp.where(here, of_head(g_ref, pl.ds(r0, C)), 0.0)
        # G_r down the rows and G_i along the lanes, both from one
        # broadcast of g: no [C, 1] -> [1, C] transpose
        gb = jnp.broadcast_to(gc, (C, C))
        G_r = _dot(tril, gb, ((1,), (0,)))
        G_i = _dot(gb, tril, ((0,), (1,)))
        decay = jnp.where(lower, jnp.exp(jnp.minimum(G_r - G_i, 0.0)), 0.0)
        G = G_r[:, 0:1]                                    # [C, 1]
        gam = jnp.exp(G)
        # P under the diagonal, scaled by beta a row; R on and under it
        A = jnp.where(iota((C, C), 0) > iota((C, C), 1),
                      bc * _dot(kn, kn, ((1,), (1,))) * decay, 0.0)
        R = _dot(qn, kn, ((1,), (1,))) * decay
        rhs = bc * (vc - gam * _dot(kn, s0, ((1,), (0,))))
        col_c = iota((sub, C), 1)
        w_ref[...] = jnp.zeros_like(w_ref)
        for lo in range(0, C, sub):
            rows = slice(lo, lo + sub)
            AI, wb = A[rows], rhs[rows]
            if lo:
                # the rows solved so far; the rest of w_ref is still 0
                wb = wb - _dot(jnp.where(col_c < lo, AI, 0.0), w_ref[...],
                               ((1,), (0,)))
            for i in range(sub - 1):
                # column i of the diagonal block is 0 down to its row i
                wb = wb - AI[:, lo + i:lo + i + 1] * wb[i:i + 1]
            w_ref[rows, :] = wb
        o_ref[pl.ds(r0, C), :] = jnp.where(
            here, gam * _dot(qn, s0, ((1,), (0,)))
            + _dot(R, w_ref[...], ((1,), (0,))), 0.0)
        last = G[C - 1:C]                                  # [1, 1]
        # exp(G_C) as a row over the value lanes (Mosaic takes no
        # broadcast over sublanes and lanes at once): the chunk's last row
        # of exp(G) spread over the lanes
        last_row = jnp.sum(jnp.where(
            row_c == C - 1, jnp.broadcast_to(gam, (C, s0.shape[1])), 0.0),
            axis=0, keepdims=True)
        return last_row * s0 + _dot(
            kn * jnp.exp(last - G), w_ref[...], ((0,), (0,)))

    def chunk(c, carry):
        r0 = pl.multiple_of(c * C, C)
        at = pl.ds(r0, C)
        here = r0 + row_c < real                           # [C, 1]
        qc = q_ref[at, :].astype(_F32)
        kc = k_ref[at, :].astype(_F32)
        vc = v_ref[at, :].astype(_F32)
        # past the prompt's last token g = 0 and beta = 0: S stays as it is
        bc = jnp.where(here, of_head(beta_ref, at), 0.0)   # [C, 1]
        qn = qc * (jax.lax.rsqrt(jnp.sum(qc * qc, axis=1, keepdims=True)
                                 + L2_EPS) * scale)
        kn = kc * jax.lax.rsqrt(jnp.sum(kc * kc, axis=1, keepdims=True)
                                + L2_EPS)
        s_ref[...] = (per_head if scalar else per_channel)(
            r0, here, qn, kn, vc, bc, s_ref[...])
        return carry

    jax.lax.fori_loop(0, (real + C - 1) // C, chunk, 0)

    @pl.when(tb == n_blocks - 1)
    def _finish():
        fin_ref[...] = s_ref[...]


def _chunk_pallas(q, k, v, g, beta, lengths, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H = beta.shape
    dk, dv = q.shape[-1] // H, v.shape[-1] // H
    scalar = _decay_a_head(g, H, dk)
    C = CHUNK if T % CHUNK == 0 else T
    sub = SUB_BLOCK if C % SUB_BLOCK == 0 else C
    L = next(C * n for n in (4, 2, 1) if T % (C * n) == 0)
    by_head = pl.BlockSpec((None, L, H), lambda p, h, tb, lens: (p, tb, 0))
    # a head of lane-multiple widths is a lane block of the token rows;
    # any other is a block only of head-major rows [B, H, T, d]
    turned = bool(dk % 128 or dv % 128)

    def rows(width):
        if turned:
            return pl.BlockSpec((None, None, L, width),
                                lambda p, h, tb, lens: (p, h, tb, 0))
        return pl.BlockSpec((None, L, width),
                            lambda p, h, tb, lens: (p, tb, h))

    def turn(x):
        return jnp.swapaxes(_heads(x, H), 1, 2) if turned else x

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B, H, T // L),
        in_specs=[rows(dk), rows(dk), rows(dv),
                  by_head if scalar else rows(dk), by_head],
        out_specs=[rows(dv), pl.BlockSpec((None, None, dk, dv),
                                          lambda p, h, tb, lens: (p, h, 0, 0))],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32),
                        pltpu.VMEM((C, dv), _F32)])
    g = g.astype(_F32)
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, C=C, sub=sub, n_blocks=T // L,
                          scale=dk ** -0.5, scalar=scalar),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(
            (B, H, T, dv) if turned else (B, T, H * dv), _F32),
            jax.ShapeDtypeStruct((B, H, dk, dv), _F32)],
        interpret=interpret, name=CHUNK_KERNEL_NAME,
        **_mosaic_params(interpret, ("parallel", "parallel", "arbitrary")),
    )(lengths.astype(jnp.int32), turn(q), turn(k), turn(v),
      g if scalar else turn(g), beta.astype(_F32))
    if turned:
        o = jnp.swapaxes(o, 1, 2).reshape(B, T, H * dv)
    return o, state


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
