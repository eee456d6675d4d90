"""The Mamba-2 recurrence (state-space duality, arXiv:2405.21060) for
SERVING: a chunked prefill over prompts of different lengths and the
one-token update of every slot's state.

A head ``h`` keeps a MATRIX ``s_h`` ``[P (d_head), N (d_state)]`` float32
with ONE scalar decay. A token brings ``x`` ``[H, P]``, a step ``Delta``
``[H]`` (after its softplus) and ``B``, ``C`` ``[N]``, which every head
reads alike (one group):

    s_h <- exp(Delta_h A_h) s_h + Delta_h x_h (x) B        A_h < 0 a head
    y_h  = s_h C + D_h x_h

**The state's layout.** ``[slots, G, N, W]``: ``d_state`` on the sublanes
and, on the lanes, ``W = g P`` channels of ``x`` IN ``x``'S OWN ORDER: the
``g = 128 / P`` heads of a LANE GROUP side by side (two heads of 64 fill
the 128 lanes), ``G = H / g`` groups (``state_shape``; ``to_heads`` /
``from_heads`` go to and from ``[..., H, P, N]``). So everything a token
brings a head, its decay and ``Delta x``, is a ROW over the lanes as the
projections leave it, ``B`` and ``C`` are columns that all heads share
(broadcast along the lanes once a slot, not once a head), and ``y = s C``
is a sum over sublanes that lands in ``x``'s order: no operand is
transposed and none is cut inside a vector register. (With ``d_state`` on
the lanes a head's decay and ``Delta x`` are COLUMNS to broadcast and ``s
C`` a lane reduction a head: that kernel read 1.6 ms a call at the served
size where XLA's own fusion took 0.98: my chip run, PR 46.)

Two kernels, each with its composed ``jax.numpy`` form beside it (the
explicit oracle, and the default off the TPU, as ``selective_scan``
routes); ``token_loop`` is the recurrence itself, a token at a time over
``[..., H, P, N]``, which the tests hold both against:

* ``state_update`` (``ssd_state_update``): one token for every slot, the
  state updated IN PLACE (``input_output_aliases``), ``y`` fused so that a
  token reads its state once and writes it once. Grid ``(slot, groups /
  32)``. A slot that is not live keeps its state and reads ``y`` = 0.
* ``chunk_prefill`` (``ssd_chunk_prefill``): grid ``(prompt, head block,
  chunk)``; the block's states live in VMEM across a prompt's chunks and
  the prompt lengths are scalar-prefetched. With ``L`` the running sum of
  ``Delta A`` inside a chunk of ``Q`` tokens and ``s_0`` the state
  entering it,

      y_t   = sum_{r<=t} exp(L_t - L_r) (C_t . B_r) Delta_r x_r
              + exp(L_t) C_t . s_0
      s_end = exp(L_Q) s_0 + sum_r exp(L_Q - L_r) Delta_r x_r (x) B_r

  ``C B^T`` ``[Q, Q]`` is one product a grid step for all its heads; a
  head's own work is its decay mask and ``[Q, Q] x [Q, P]``; a lane
  group's heads go through the matrix unit together. Every difference
  under an ``exp`` is <= 0. The products' operands are in ``x``'s dtype
  (bfloat16 when serving: one pass of the matrix unit; float32 operands
  take the highest precision) and accumulate in float32; the running sums,
  the masks and the state are float32. Past a prompt's last real token
  ``Delta`` = 0: ``exp(0) s + 0`` is ``s``, so a bucket's padding is
  exactly nothing to the state, and a chunk of padding alone is not
  computed.

A kernel the compiler refuses raises ``KernelCompileError``.
"""

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.flash_attention import _mosaic_params
from paddle_tpu.kernels.selective_scan import _route

STATE_KERNEL_NAME = "ssd_state_update"
CHUNK_KERNEL_NAME = "ssd_chunk_prefill"

CHUNK = 256        # tokens a chunk (the published ``mamba_chunk_size``)
LANES = 128
HEAD_BLOCK = 8     # heads a grid step of the chunked prefill
UPDATE_GROUPS = 32  # lane groups a grid step of the one-token update

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def lane_group(H, P):
    """Heads that share the 128 lanes of one operand."""
    g = max(1, LANES // P)
    while H % g:
        g -= 1
    return g


def state_shape(slots, H, P, N):
    """The served state of ``slots`` slots: ``[slots, G, N, g P]``."""
    g = lane_group(H, P)
    return (slots, H // g, N, g * P)


def from_heads(state):
    """``[..., H, P, N]`` -> the served layout ``[..., G, N, g P]``."""
    H, P, N = state.shape[-3:]
    g = lane_group(H, P)
    lead = state.shape[:-3]
    s = state.reshape(lead + (H // g, g, P, N))
    return jnp.moveaxis(s, -1, -3).reshape(lead + (H // g, N, g * P))


def to_heads(state, H):
    """The served layout ``[..., G, N, g P]`` -> ``[..., H, P, N]``."""
    G, N, W = state.shape[-3:]
    lead = state.shape[:-3]
    s = state.reshape(lead + (G, N, H // G, W // (H // G)))
    return jnp.moveaxis(s, -3, -1).reshape(lead + (H, -1, N))


def _heads(x, H):
    return x.reshape(x.shape[:-1] + (H, -1))


# -- the recurrence, and the composed forms -----------------------------------

def token_step(state, x, dt, a, b, c):
    """One token: state [..., H, P, N]; x [..., H, P]; dt [..., H]; a [H];
    b, c [..., N]. Returns (y [..., H, P] without the skip, the state
    after the token)."""
    new = (jnp.exp(dt * a)[..., None, None] * state
           + (dt[..., None] * x)[..., None] * b[..., None, None, :])
    return jnp.sum(new * c[..., None, None, :], axis=-1), new


def token_loop(x, dt, a, b, c, d_skip, lengths):
    """The recurrence as a plain loop over ``t`` (the oracle of both
    forms; ``chunk_prefill_reference`` has the shapes, but the state it
    returns is ``[B, H, P, N]``)."""
    B, T, H = dt.shape
    x32 = _heads(x, H).astype(_F32)
    b32, c32, dt32 = b.astype(_F32), c.astype(_F32), dt.astype(_F32)

    def step(s, t):
        y, new = token_step(s, x32[:, t], dt32[:, t], a, b32[:, t],
                            c32[:, t])
        y = y + d_skip.astype(_F32)[:, None] * x32[:, t]
        real = (t < lengths)[:, None, None]
        return (jnp.where(real[..., None], new, s), jnp.where(real, y, 0.0))

    s0 = jnp.zeros((B, H, x32.shape[-1], b.shape[-1]), _F32)
    s, ys = jax.lax.scan(step, s0, jnp.arange(T))
    return jnp.transpose(ys, (1, 0, 2, 3)).reshape(B, T, -1), s


def _rows(x, dt, a, d_skip, G):
    """What a token brings a lane group, as rows over its lanes: (the
    decay ``exp(Delta A)``, ``Delta x``, the skip ``D x``), each ``[..., G,
    W]``."""
    H = dt.shape[-1]
    x32 = _heads(x, H).astype(_F32)                        # [..., H, P]
    dt32 = dt.astype(_F32)[..., None]
    grouped = x32.shape[:-2] + (G, -1)
    return (jnp.broadcast_to(jnp.exp(dt32 * a.astype(_F32)[:, None]),
                             x32.shape).reshape(grouped),
            (dt32 * x32).reshape(grouped),
            (d_skip.astype(_F32)[:, None] * x32).reshape(grouped))


def state_update_reference(state, x, dt, a, b, c, d_skip, live):
    """state: [S, G, N, W] float32 (the module's layout); x: [S, H * P];
    dt: [S, H] float32 (after its softplus); a: [H] float32 (negative); b,
    c: [S, N]; d_skip: [H]; live: [S]. Returns (y [S, H * P] float32, the
    state after this token; a slot that is not live keeps its own and
    reads y = 0)."""
    S, G = state.shape[:2]
    decay, dtx, skip = _rows(x, dt, a, d_skip, G)
    new = decay[:, :, None, :] * state + dtx[:, :, None, :] \
        * b.astype(_F32)[:, None, :, None]
    y = jnp.sum(new * c.astype(_F32)[:, None, :, None], axis=2) + skip
    keep = (live > 0)[:, None, None]
    return (jnp.where(keep, y, 0.0).reshape(S, -1),
            jnp.where(keep[..., None], new, state))


def _mm(x):
    """(operand dtype, precision) of the chunked form's products."""
    return (x.dtype, None) if x.dtype == jnp.bfloat16 else (_F32, _HIGHEST)


def _chunk_of(T):
    return CHUNK if T % CHUNK == 0 else T


def _masked_steps(dt, lengths):
    """``Delta`` with 0 past each prompt's last real token."""
    real = jnp.arange(dt.shape[1])[None, :] < lengths[:, None]
    return jnp.where(real[..., None], dt.astype(_F32), 0.0), real


def chunk_prefill_reference(x, dt, a, b, c, d_skip, lengths):
    """The chunked form over all heads at once, a chunk at a time. x:
    [B, T, H * P]; dt: [B, T, H] float32 (after its softplus); a: [H]
    float32 (negative); b, c: [B, T, N]; d_skip: [H]; lengths: [B].
    Returns (y [B, T, H * P] float32, 0 past a prompt's length; state
    [B, G, N, W] float32 after each prompt's last real token)."""
    B, T, H = dt.shape
    Q = _chunk_of(T)
    op, precision = _mm(x)
    dt32, real = _masked_steps(dt, lengths)
    xh = _heads(x, H)
    N, P = b.shape[-1], xh.shape[-1]
    tril = jnp.tril(jnp.ones((Q, Q), bool))

    def ein(spec, lhs, rhs):
        return jnp.einsum(spec, lhs.astype(op), rhs.astype(op),
                          precision=precision, preferred_element_type=_F32)

    def chunk(s0, part):
        xq, dq, bq, cq = part                 # [B, Q, ...]
        run = jnp.cumsum(dq * a, axis=1)                      # [B, Q, H]
        dtx = dq[..., None] * xq.astype(_F32)                 # [B, Q, H, P]
        g = ein("btn,brn->btr", cq, bq)                       # [B, Q, Q]
        decay = jnp.exp(jnp.minimum(
            run[:, :, None, :] - run[:, None, :, :], 0.0))    # [B, t, r, H]
        m = jnp.where(tril[None, :, :, None], g[..., None] * decay, 0.0)
        y = ein("btrh,brhp->bthp", m, dtx) + jnp.exp(run)[..., None] * ein(
            "btn,bhpn->bthp", cq, s0)
        last = run[:, -1:, :]                                 # [B, 1, H]
        new = jnp.exp(last[:, 0])[..., None, None] * s0 + ein(
            "brhp,brn->bhpn", dtx * jnp.exp(last - run)[..., None], bq)
        return new, y

    def chunks(v):
        return jnp.moveaxis(v.reshape((B, T // Q, Q) + v.shape[2:]), 1, 0)

    s0 = jnp.zeros((B, H, P, N), _F32)
    s, ys = jax.lax.scan(chunk, s0, (chunks(xh), chunks(dt32), chunks(b),
                                     chunks(c)))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, T, H, P)
    y = y + d_skip.astype(_F32)[:, None] * xh.astype(_F32)
    return (jnp.where(real[..., None, None], y, 0.0).reshape(B, T, -1),
            from_heads(s))


# -- one token for every slot -------------------------------------------------

def _update_kernel(live_ref, s_ref, rows_ref, bc_ref, y_ref, out_ref, *,
                   gb):
    from jax.experimental import pallas as pl

    keep = live_ref[pl.program_id(0)] > 0
    b_all, c_all = bc_ref[0, 0], bc_ref[0, 1]              # [N, W]

    def group(k, carry):
        s = s_ref[0, k]                                    # [N, W]
        rows = rows_ref[0, k]                              # [2, W]
        new = rows[0:1] * s + b_all * rows[1:2]
        y = jnp.sum(new * c_all, axis=0, keepdims=True)    # [1, W]
        out_ref[0, k] = jnp.where(keep, new, s)
        y_ref[0, pl.ds(k, 1), :] = jnp.where(keep, y, 0.0)
        return carry

    jax.lax.fori_loop(0, gb, group, 0)


def _update_pallas(state, x, dt, a, b, c, d_skip, live, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, G, N, W = state.shape
    gb = UPDATE_GROUPS if G % UPDATE_GROUPS == 0 else G
    decay, dtx, skip = _rows(x, dt, a, d_skip, G)
    # B and C are every head's: broadcast along the lanes once a slot
    bc = jnp.broadcast_to(
        jnp.stack([b.astype(_F32), c.astype(_F32)], axis=1)[..., None],
        (S, 2, N, W))
    st = pl.BlockSpec((1, gb, N, W), lambda i, j, live: (i, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(S, G // gb),
        in_specs=[st,
                  pl.BlockSpec((1, gb, 2, W),
                               lambda i, j, live: (i, j, 0, 0)),
                  pl.BlockSpec((1, 2, N, W),
                               lambda i, j, live: (i, 0, 0, 0))],
        out_specs=[pl.BlockSpec((1, gb, W), lambda i, j, live: (i, j, 0)),
                   st])
    y, new = pl.pallas_call(
        functools.partial(_update_kernel, gb=gb), grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, G, W), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 0 is the scalar-prefetched live mask
        input_output_aliases={1: 1},
        interpret=interpret, name=STATE_KERNEL_NAME,
        **_mosaic_params(interpret, ("parallel", "parallel")),
    )(live.astype(jnp.int32).reshape(S), state,
      jnp.stack([decay, dtx], axis=2), bc)
    y = y + jnp.where((live > 0)[:, None, None], skip, 0.0)
    return y.reshape(S, G * W), new


# -- the chunked prefill ------------------------------------------------------

def _chunk_kernel(len_ref, x_ref, b_ref, c_ref, run_ref, runt_ref, dt_ref,
                  y_ref, fin_ref, s_ref, *, Q, P, g, groups, n_chunks, op,
                  precision):
    from jax.experimental import pallas as pl

    p, j, ch = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    gp = g * P
    H = run_ref.shape[2]

    def dot(lhs, rhs, dims):
        return jax.lax.dot_general(
            lhs.astype(op), rhs.astype(op), (dims, ((), ())),
            precision=precision, preferred_element_type=_F32)

    @pl.when(ch == 0)
    def _start():
        s_ref[...] = jnp.zeros_like(s_ref)

    real = len_ref[p] - ch * Q

    @pl.when(real <= 0)
    def _padding():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(real > 0)
    def _chunk():
        def iota(shape, axis):
            return jax.lax.broadcasted_iota(jnp.int32, shape, axis)

        b, c = b_ref[0], c_ref[0]                          # [Q, N]
        gt = jnp.where(iota((Q, Q), 0) >= iota((Q, Q), 1),
                       dot(c, b, ((1,), (1,))), 0.0)       # lower(C B^T)
        b_t = b.astype(_F32).T                             # [N, Q]
        here = iota((Q, 1), 0) < real
        head_lane = iota((Q, H), 1)
        part = iota((Q, gp), 1) // P       # which head of the group a lane is
        run_all, dt_all = run_ref[0], dt_ref[0]            # [Q, H]
        for k in range(groups):
            first = (j * groups + k) * g                   # the group's head
            x = x_ref[0, :, k * gp:(k + 1) * gp].astype(_F32)   # [Q, gp]
            cols, rows = [], []
            for i in range(g):
                mine = head_lane == first + i
                cols.append([jnp.sum(jnp.where(mine, v, 0.0), axis=1,
                                     keepdims=True)
                             for v in (run_all, dt_all)])  # [Q, 1] each
                rows.append(runt_ref[0, k * g + i:k * g + i + 1, :])  # [1, Q]

            def by_head(values, where=part):
                out = values[0]
                for i in range(1, g):
                    out = jnp.where(where == i, values[i], out)
                return out

            dtx = x * by_head([cols[i][1] for i in range(g)])
            acc = jnp.zeros((Q, gp), _F32)
            for i in range(g):
                m = gt * jnp.exp(jnp.minimum(cols[i][0] - rows[i], 0.0))
                acc = acc + dot(m, jnp.where(part == i, dtx, 0.0),
                                ((1,), (0,)))
            s0 = s_ref[k]                                  # [N, gp]
            acc = acc + by_head([jnp.exp(cols[i][0]) for i in range(g)]) \
                * dot(c, s0, ((1,), (0,)))
            y_ref[0, :, k * gp:(k + 1) * gp] = jnp.where(here, acc, 0.0)
            # the running sum never rises (Delta >= 0 > A): its last is
            # its least, and a reduction's [1, 1] broadcasts either way
            last = [jnp.min(rows[i], axis=1, keepdims=True)
                    for i in range(g)]
            weighted = dtx * by_head(
                [jnp.exp(last[i] - cols[i][0]) for i in range(g)])
            keep = by_head([jnp.exp(jnp.broadcast_to(last[i], (1, gp)))
                            for i in range(g)], part[0:1])
            s_ref[k] = keep * s0 + dot(b_t, weighted, ((1,), (0,)))

    @pl.when(ch == n_chunks - 1)
    def _finish():
        fin_ref[0] = s_ref[...]


def _chunk_pallas(x, dt, a, b, c, d_skip, lengths, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H = dt.shape
    P, N = x.shape[-1] // H, b.shape[-1]
    Q = _chunk_of(T)
    g = lane_group(H, P)
    hb = HEAD_BLOCK if H % HEAD_BLOCK == 0 and HEAD_BLOCK % g == 0 else H
    groups, gp = hb // g, g * P
    op, precision = _mm(x)
    dt32, real = _masked_steps(dt, lengths)
    # the running sum of Delta A inside each chunk, a token a row and a
    # token a column: a few rows a token beside its 2 H P of x and y
    run = jnp.cumsum((dt32 * a.astype(_F32)).reshape(B, T // Q, Q, H),
                     axis=2).reshape(B, T, H)
    tokens = pl.BlockSpec((1, Q, hb * P), lambda p, j, ch, lens: (p, ch, j))
    shared = pl.BlockSpec((1, Q, N), lambda p, j, ch, lens: (p, ch, 0))
    by_head = pl.BlockSpec((1, Q, H), lambda p, j, ch, lens: (p, ch, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B, H // hb, T // Q),
        in_specs=[tokens, shared, shared, by_head,
                  pl.BlockSpec((1, hb, Q), lambda p, j, ch, lens: (p, j, ch)),
                  by_head],
        out_specs=[tokens,
                   pl.BlockSpec((1, groups, N, gp),
                                lambda p, j, ch, lens: (p, j, 0, 0))],
        scratch_shapes=[pltpu.VMEM((groups, N, gp), _F32)])
    y, state = pl.pallas_call(
        functools.partial(_chunk_kernel, Q=Q, P=P, g=g, groups=groups,
                          n_chunks=T // Q, op=op, precision=precision),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, T, H * P), _F32),
                   jax.ShapeDtypeStruct((B, H // g, N, gp), _F32)],
        interpret=interpret, name=CHUNK_KERNEL_NAME,
        **_mosaic_params(interpret, ("parallel", "parallel", "arbitrary")),
    )(lengths.astype(jnp.int32), x, b, c, run,
      jnp.transpose(run, (0, 2, 1)), dt32)
    skip = d_skip.astype(_F32)[:, None] * _heads(x, H).astype(_F32)
    return y + jnp.where(real[..., None], skip.reshape(B, T, -1), 0.0), state


# -- entry points -------------------------------------------------------------

def state_update(state, x, dt, a, b, c, d_skip, live, force_reference=False,
                 force_pallas=False):
    """One token of the recurrence for every slot, ``y`` fused
    (``state_update_reference`` has the shapes)."""
    return _route(STATE_KERNEL_NAME, _update_pallas, state_update_reference,
                  (state, x, dt, a, b, c, d_skip, live), force_reference,
                  force_pallas)


def chunk_prefill(x, dt, a, b, c, d_skip, lengths, force_reference=False,
                  force_pallas=False):
    """The recurrence over ``[B, T, ...]`` prompts of ``lengths`` real
    tokens, in chunks (``chunk_prefill_reference`` has the shapes)."""
    return _route(CHUNK_KERNEL_NAME, _chunk_pallas, chunk_prefill_reference,
                  (x, dt, a, b, c, d_skip, lengths), force_reference,
                  force_pallas)
