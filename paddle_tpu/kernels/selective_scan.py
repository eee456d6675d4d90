"""The selective state-space recurrence (Mamba-1) for SERVING: a causal
depthwise convolution and a selective scan over prompts of different
lengths, and their one-token forms over every slot's recurrent state.

    x_t   = silu(b + sum_j W[j] * u_{t-(kw-1)+j})        # depthwise, causal
    s_t   = exp(Delta_t (x) A) * s_{t-1} + (Delta_t * x_t) (x) B_t
    y_t   = s_t . C_t + D * x_t

Everything keeps the channel axis ``d`` (``d_inner``) on the lanes: the
state is ``[slots, n, d]`` float32 (``n`` = ``d_state`` on the sublanes:
16 rows are two whole float32 tiles), the convolution's window ``[kw-1,
slots, d]`` in the activations' dtype, ``A`` ``[n, d]``. A state stored
``[slots, d, n]`` would have a 16-wide minor axis that the chip pads to
128 lanes (eight times the bytes) or copies between layouts around every
dispatch.

Four kernels, each with its composed ``jax.numpy`` reference beside it
(the explicit oracle, and the default off the TPU, as ``paged_attention``
and ``flash_attention`` route):

* ``causal_conv`` (``ssm_causal_conv``): prompts ``[B, T, d]``, one prompt
  a row of its bucket, time walked in chunks with the last rows of the
  chunk before kept in VMEM.
* ``prefill_scan`` (``ssm_prefill_scan``): grid ``(prompt, d tile, time
  chunk)``; the state tile ``[n, td]`` lives in VMEM across a prompt's
  chunks and the prompt lengths are scalar-prefetched. A chunk walks its
  REAL tokens only, 16 from an aligned row at a time (``fori_loop`` to
  ``len - chunk start``; in the last group a token past the length gets
  ``Delta`` = 0, and ``exp(0) * s + 0`` is ``s``): the padding of a bucket
  is exactly nothing to the state, not a small update, and costs no time.
  Returns ``y`` for every token (0 on padding) and each prompt's state
  after its last real token.
* ``conv_step`` (``ssm_conv_step``) and ``state_update``
  (``ssm_state_update``): one token for every slot, the window and the
  state updated IN PLACE (``input_output_aliases``), ``y = s . C`` fused
  so that a token reads its state once and writes it once. A slot that is
  not live keeps its rows as they are.

A kernel the compiler refuses raises ``KernelCompileError``.
"""

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.flash_attention import _is_tpu_target, _mosaic_params
from paddle_tpu.kernels.paged_attention import KernelCompileError

CONV_KERNEL_NAME = "ssm_causal_conv"
SCAN_KERNEL_NAME = "ssm_prefill_scan"
CONV_STEP_KERNEL_NAME = "ssm_conv_step"
UPDATE_KERNEL_NAME = "ssm_state_update"

_F32 = jnp.float32
_TAIL = 8     # rows of the chunk before that the convolution keeps


def _tile(d, want):
    """The channel tile: the most lanes up to ``want`` that divide ``d``."""
    return next((t for t in range(want, 0, -128) if d % t == 0), d)


def _silu(x):
    return x * jax.nn.sigmoid(x)


# -- references ---------------------------------------------------------------

def causal_conv_reference(x, w, bias):
    """x: [B, T, d]; w: [kw, d]; bias: [d]. Returns [B, T, d] in ``x``'s
    dtype; a prompt starts at row 0 of its bucket (zeros before it)."""
    kw = w.shape[0]
    T = x.shape[1]
    xp = jnp.pad(x.astype(_F32), ((0, 0), (kw - 1, 0), (0, 0)))
    acc = bias.astype(_F32)[None, None, :] + sum(
        w[j].astype(_F32)[None, None, :] * xp[:, j:j + T] for j in range(kw))
    return _silu(acc).astype(x.dtype)


def prefill_scan_reference(x, dt, b, c, a, d_skip, lengths):
    """x: [B, T, d]; dt: [B, T, d] float32; b, c: [B, n, T] float32;
    a: [n, d] float32 (negative); d_skip: [d]; lengths: [B]. Returns
    (y [B, T, d] in ``x``'s dtype, 0 past a prompt's length; state
    [B, n, d] float32 after each prompt's last real token)."""
    B, T, d = x.shape
    x32 = x.astype(_F32)

    def step(s, t):
        xt, dtt = x32[:, t], dt[:, t]                        # [B, d]
        new = (jnp.exp(dtt[:, None, :] * a[None]) * s
               + (dtt * xt)[:, None, :] * b[:, :, t, None])
        real = (t < lengths)[:, None, None]
        s = jnp.where(real, new, s)
        y = jnp.sum(s * c[:, :, t, None], axis=1) + d_skip.astype(_F32) * xt
        return s, jnp.where(real[:, 0], y, 0.0)

    s0 = jnp.zeros((B, a.shape[0], d), _F32)
    s, ys = jax.lax.scan(step, s0, jnp.arange(T))
    return jnp.transpose(ys, (1, 0, 2)).astype(x.dtype), s


def conv_step_reference(window, x, w, bias, live):
    """window: [kw-1, S, d]; x: [S, d]; live: [S]. Returns (y [S, d], the
    window after this token; a slot that is not live keeps its own)."""
    kw = w.shape[0]
    w32 = w.astype(_F32)
    acc = bias.astype(_F32)[None] + w32[kw - 1][None] * x.astype(_F32)
    for j in range(kw - 1):
        acc = acc + w32[j][None] * window[j].astype(_F32)
    new = jnp.concatenate([window[1:], x[None].astype(window.dtype)], axis=0)
    keep = (live > 0)[None, :, None]
    return _silu(acc).astype(x.dtype), jnp.where(keep, new, window)


def state_update_reference(state, x, dt, b, c, a, d_skip, live):
    """state: [S, n, d] float32; x: [S, d]; dt: [S, d] float32; b, c:
    [S, n] float32; live: [S]. Returns (y [S, d] in ``x``'s dtype, the
    state after this token; a slot that is not live keeps its own and
    reads y = 0)."""
    x32 = x.astype(_F32)
    new = (jnp.exp(dt[:, None, :] * a[None]) * state
           + (dt * x32)[:, None, :] * b[:, :, None])
    y = jnp.sum(new * c[:, :, None], axis=1) + d_skip.astype(_F32) * x32
    keep = (live > 0)[:, None, None]
    return (jnp.where(keep[:, 0], y, 0.0).astype(x.dtype),
            jnp.where(keep, new, state))


# -- the prefill convolution --------------------------------------------------

def _conv_kernel(x_ref, w_ref, b_ref, o_ref, tail_ref, *, kw):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    @pl.when(pl.program_id(2) == 0)
    def _start():
        tail_ref[...] = jnp.zeros_like(tail_ref)

    x = x_ref[0].astype(_F32)                              # [L, td]
    w = w_ref[...].astype(_F32)                            # [kw, td]
    tail = tail_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, tail.shape, 0)
    acc = b_ref[...].astype(_F32) + w[kw - 1:kw] * x
    for j in range(1, kw):
        cur = pltpu.roll(x, j, 0)          # cur[r] = x[r - j], wrapped
        prev = pltpu.roll(tail, j, 0)      # prev[r] = tail[_TAIL - j + r]
        head = jnp.where(row < j, prev, cur[:_TAIL])
        shifted = jnp.concatenate([head, cur[_TAIL:]], axis=0) \
            if x.shape[0] > _TAIL else head
        acc = acc + w[kw - 1 - j:kw - j] * shifted
    tail_ref[...] = x[x.shape[0] - _TAIL:]
    o_ref[0] = _silu(acc).astype(o_ref.dtype)


def _conv_pallas(x, w, bias, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, d = x.shape
    kw = w.shape[0]
    td, L = _tile(d, 512), (256 if T % 256 == 0 else T)
    return pl.pallas_call(
        functools.partial(_conv_kernel, kw=kw),
        grid=(B, d // td, T // L),
        in_specs=[pl.BlockSpec((1, L, td), lambda b, j, c: (b, c, j)),
                  pl.BlockSpec((kw, td), lambda b, j, c: (0, j)),
                  pl.BlockSpec((1, td), lambda b, j, c: (0, j))],
        out_specs=pl.BlockSpec((1, L, td), lambda b, j, c: (b, c, j)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((_TAIL, td), _F32)],
        interpret=interpret, name=CONV_KERNEL_NAME,
        **_mosaic_params(interpret, ("parallel", "parallel", "arbitrary")),
    )(x, w, bias.reshape(1, d))


# -- the prefill scan ---------------------------------------------------------

def _scan_kernel(len_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref,
                 fin_ref, s_ref, yg_ref, *, chunk, n_chunks, rows):
    from jax.experimental import pallas as pl

    p, ch = pl.program_id(0), pl.program_id(2)

    @pl.when(ch == 0)
    def _start():
        s_ref[...] = jnp.zeros_like(s_ref)

    y_ref[...] = jnp.zeros_like(y_ref)
    real = jnp.clip(len_ref[p] - ch * chunk, 0, chunk)
    a = a_ref[...]                                         # [n, td]
    d_skip = d_ref[...]                                    # [1, td]
    lane = jax.lax.broadcasted_iota(jnp.int32, b_ref.shape[1:], 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)

    def group(g, s):
        """``rows`` tokens from an aligned row on (a packed dtype is read
        a whole tile at a time); a token past the prompt's length gets
        Delta = 0: exp(0) * s + 0 is s, exactly."""
        r0 = pl.multiple_of(g * rows, rows)
        here = r0 + row < real
        xg = jnp.where(here, x_ref[0, pl.ds(r0, rows), :].astype(_F32), 0.0)
        dtg = jnp.where(here, dt_ref[0, pl.ds(r0, rows), :], 0.0)
        for i in range(rows):
            xt, dtt = xg[i:i + 1], dtg[i:i + 1]            # [1, td]
            at = lane == r0 + i                            # column r0 + i
            bt = jnp.sum(jnp.where(at, b_ref[0], 0.0), axis=1,
                         keepdims=True)                    # [n, 1]
            ct = jnp.sum(jnp.where(at, c_ref[0], 0.0), axis=1,
                         keepdims=True)
            s = jnp.exp(dtt * a) * s + (dtt * xt) * bt     # [n, td]
            yg_ref[i:i + 1, :] = (jnp.sum(s * ct, axis=0, keepdims=True)
                                  + d_skip * xt)
        y_ref[0, pl.ds(r0, rows), :] = jnp.where(
            here, yg_ref[...], 0.0).astype(y_ref.dtype)
        return s

    s_ref[...] = jax.lax.fori_loop(0, (real + rows - 1) // rows, group,
                                   s_ref[...])

    @pl.when(ch == n_chunks - 1)
    def _finish():
        fin_ref[0] = s_ref[...]


def _scan_pallas(x, dt, b, c, a, d_skip, lengths, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, d = x.shape
    n = a.shape[0]
    td, L = _tile(d, 512), (128 if T % 128 == 0 else T)
    rows = 16 if L % 16 == 0 else L
    tokens = pl.BlockSpec((1, L, td), lambda p, j, ch, lens: (p, ch, j))
    columns = pl.BlockSpec((1, n, L), lambda p, j, ch, lens: (p, 0, ch))
    state = pl.BlockSpec((1, n, td), lambda p, j, ch, lens: (p, 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B, d // td, T // L),
        in_specs=[tokens, tokens, columns, columns,
                  pl.BlockSpec((n, td), lambda p, j, ch, lens: (0, j)),
                  pl.BlockSpec((1, td), lambda p, j, ch, lens: (0, j))],
        out_specs=[tokens, state],
        scratch_shapes=[pltpu.VMEM((n, td), _F32),
                        pltpu.VMEM((rows, td), _F32)])
    return pl.pallas_call(
        functools.partial(_scan_kernel, chunk=L, n_chunks=T // L,
                          rows=rows),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((B, n, d), _F32)],
        interpret=interpret, name=SCAN_KERNEL_NAME,
        **_mosaic_params(interpret, ("parallel", "parallel", "arbitrary")),
    )(lengths.astype(jnp.int32), x, dt.astype(_F32), b.astype(_F32),
      c.astype(_F32), a.astype(_F32), d_skip.astype(_F32).reshape(1, d))


# -- one token for every slot -------------------------------------------------

def _conv_step_kernel(win_ref, x_ref, w_ref, b_ref, live_ref, y_ref,
                      out_ref, *, kw):
    x = x_ref[...]                                         # [bs, td]
    w = w_ref[...].astype(_F32)
    acc = b_ref[...].astype(_F32) + w[kw - 1:kw] * x.astype(_F32)
    for j in range(kw - 1):
        acc = acc + w[j:j + 1] * win_ref[j].astype(_F32)
    y_ref[...] = _silu(acc).astype(y_ref.dtype)
    keep = live_ref[...] > 0                               # [bs, 1]
    for j in range(kw - 1):
        new = win_ref[j + 1] if j < kw - 2 else x.astype(out_ref.dtype)
        out_ref[j] = jnp.where(keep, new, win_ref[j])


def _conv_step_pallas(window, x, w, bias, live, interpret):
    from jax.experimental import pallas as pl

    k1, S, d = window.shape
    kw = k1 + 1
    td, bs = _tile(d, 512), (64 if S % 64 == 0 else S)
    rows = pl.BlockSpec((bs, td), lambda i, j: (i, j))
    win = pl.BlockSpec((k1, bs, td), lambda i, j: (0, i, j))
    return pl.pallas_call(
        functools.partial(_conv_step_kernel, kw=kw),
        grid=(S // bs, d // td),
        in_specs=[win, rows, pl.BlockSpec((kw, td), lambda i, j: (0, j)),
                  pl.BlockSpec((1, td), lambda i, j: (0, j)),
                  pl.BlockSpec((bs, 1), lambda i, j: (i, 0))],
        out_specs=[rows, win],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(window.shape, window.dtype)],
        input_output_aliases={0: 1},
        interpret=interpret, name=CONV_STEP_KERNEL_NAME,
        **_mosaic_params(interpret, ("parallel", "parallel")),
    )(window, x, w, bias.reshape(1, d),
      live.astype(jnp.int32).reshape(S, 1))


def _update_kernel(live_ref, s_ref, x_ref, dt_ref, b_ref, c_ref, a_ref,
                   d_ref, y_ref, out_ref, *, slots):
    from jax.experimental import pallas as pl

    first = pl.program_id(0) * slots
    a = a_ref[...]
    d_skip = d_ref[...]
    for i in range(slots):       # a slot at a time: every value is 2-D
        s = s_ref[i]                                       # [n, td]
        xt = x_ref[i:i + 1, :].astype(_F32)                # [1, td]
        dtt = dt_ref[i:i + 1, :]
        new = jnp.exp(dtt * a) * s + (dtt * xt) * b_ref[i]
        y = jnp.sum(new * c_ref[i], axis=0, keepdims=True) + d_skip * xt
        keep = live_ref[first + i] > 0
        out_ref[i] = jnp.where(keep, new, s)
        y_ref[i:i + 1, :] = jnp.where(keep, y, 0.0).astype(y_ref.dtype)


def _update_pallas(state, x, dt, b, c, a, d_skip, live, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, n, d = state.shape
    td, bs = _tile(d, 1024), (8 if S % 8 == 0 else S)
    rows = pl.BlockSpec((bs, td), lambda i, j, live: (i, j))
    st = pl.BlockSpec((bs, n, td), lambda i, j, live: (i, 0, j))
    col = pl.BlockSpec((bs, n, 1), lambda i, j, live: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(S // bs, d // td),
        in_specs=[st, rows, rows, col, col,
                  pl.BlockSpec((n, td), lambda i, j, live: (0, j)),
                  pl.BlockSpec((1, td), lambda i, j, live: (0, j))],
        out_specs=[rows, st])
    return pl.pallas_call(
        functools.partial(_update_kernel, slots=bs),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 0 is the scalar-prefetched live mask
        input_output_aliases={1: 1},
        interpret=interpret, name=UPDATE_KERNEL_NAME,
        **_mosaic_params(interpret, ("parallel", "parallel")),
    )(live.astype(jnp.int32).reshape(S), state, x, dt.astype(_F32),
      b.astype(_F32).reshape(S, n, 1), c.astype(_F32).reshape(S, n, 1),
      a.astype(_F32), d_skip.astype(_F32).reshape(1, d))


# -- entry points -------------------------------------------------------------

def _route(name, kernel, reference, operands, force_reference, force_pallas):
    if not (force_pallas or (not force_reference and _is_tpu_target())):
        return reference(*operands)
    try:
        return kernel(*operands, interpret=not _is_tpu_target())
    except Exception as exc:
        raise KernelCompileError(name, operands, exc) from exc


def causal_conv(x, w, bias, force_reference=False, force_pallas=False):
    """The causal depthwise convolution with SiLU over ``[B, T, d]``
    prompts (``causal_conv_reference`` has the shapes)."""
    return _route(CONV_KERNEL_NAME, _conv_pallas, causal_conv_reference,
                  (x, w, bias), force_reference, force_pallas)


def prefill_scan(x, dt, b, c, a, d_skip, lengths, force_reference=False,
                 force_pallas=False):
    """The selective scan over ``[B, T, d]`` prompts of ``lengths`` real
    tokens (``prefill_scan_reference`` has the shapes)."""
    return _route(SCAN_KERNEL_NAME, _scan_pallas, prefill_scan_reference,
                  (x, dt, b, c, a, d_skip, lengths), force_reference,
                  force_pallas)


def conv_step(window, x, w, bias, live, force_reference=False,
              force_pallas=False):
    """One token of the convolution for every slot
    (``conv_step_reference`` has the shapes)."""
    return _route(CONV_STEP_KERNEL_NAME, _conv_step_pallas,
                  conv_step_reference, (window, x, w, bias, live),
                  force_reference, force_pallas)


def state_update(state, x, dt, b, c, a, d_skip, live, force_reference=False,
                 force_pallas=False):
    """One token of the recurrence for every slot, ``y`` fused
    (``state_update_reference`` has the shapes)."""
    return _route(UPDATE_KERNEL_NAME, _update_pallas, state_update_reference,
                  (state, x, dt, b, c, a, d_skip, live), force_reference,
                  force_pallas)
