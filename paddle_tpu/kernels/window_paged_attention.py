"""Grouped-query paged decode attention over the last ``window`` positions
of every slot, for a layer whose cache is a RING of pages.

``gqa_paged_attention``'s kernel (grid ``(slot,)``, the pools left in HBM,
a slot's resident pages walked inside the body several a step, online
softmax, row layout ``[pages, page_size, Hkv * dh]``, trash page) over a
first visible position a slot (``max(length - window, 0)``). What a window
layer changes:

* **The table is a ring**, ``[S, R]``: logical page ``j`` of a slot (the
  rows of positions ``j * page_size`` and on) is held in column ``j % R``,
  so the entries are not in position order. The host gives a page back
  the moment its last row is behind every query the next dispatch can
  hold, and ``R`` pages a slot are enough whatever the sequence's length.
* **Pages behind the window cost nothing**: the walk's ``p``-th page of a
  slot is logical page ``first // page_size + p`` (position order), found
  through the walk's one hook, the COLUMN of a slot's ``p``-th page
  (``(first // page_size + p) % R``); ``last - first + 1`` pages are
  resident (none in an empty slot), no other column is read, and the
  first page's rows before ``first`` are masked.
* **A kernel name of its own** (``WINDOW_KERNEL_NAME``): a device trace
  tells a window layer's calls from a full layer's.

The body of a grid step and the ``pallas_call`` are
``gqa_paged_attention._attend_slot`` / ``_slot_walk_call``, written once
for both files: the full-table kernel is this one with ``first`` 0 and the
identity column.

``window_paged_attention_reference`` is the composed path beside it (the
explicit oracle, and the default off the TPU).
"""

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.flash_attention import _is_tpu_target
from paddle_tpu.kernels.gqa_paged_attention import (
    _NEG_INF,
    _attend_slot,
    _slot_walk_call,
)
from paddle_tpu.kernels.paged_attention import KernelCompileError

# no other kernel's name is a part of it: a trace's readers find a kernel by
# the names that HOLD theirs
WINDOW_KERNEL_NAME = "gqa_window_decode_attention"


def ring_pages(first, lengths, page_size):
    """(first logical page, last logical page) a slot's ring holds for the
    visible rows ``first <= position < length``."""
    return first // page_size, jnp.maximum(lengths - 1, 0) // page_size


def window_paged_attention_reference(q, k_pool, v_pool, ring_table, lengths,
                                     window, sm_scale):
    """q: [S, H, dh]; k_pool/v_pool: [P, page_size, Hkv * dh]; ring_table:
    [S, R] int (column ``j % R`` holds logical page ``j``); lengths: [S]
    resident rows. Returns [S, H, dh] in ``q``'s dtype; a slot of length 0
    returns 0."""
    S, H, dh = q.shape
    ps, R = k_pool.shape[1], ring_table.shape[1]
    Hkv = k_pool.shape[2] // dh
    first = jnp.maximum(lengths - window, 0)
    lo, _hi = ring_pages(first, lengths, ps)
    col = jnp.arange(R)[None, :]
    # the logical page column c holds: the one in lo .. lo + R - 1 that is
    # c modulo R
    page = lo[:, None] + (col - lo[:, None]) % R                  # [S, R]
    pos = (page[:, :, None] * ps + jnp.arange(ps)[None, None, :]).reshape(
        S, R * ps)

    def rows(pool):
        return pool[ring_table].astype(jnp.float32).reshape(
            S, R * ps, Hkv, dh)

    qg = q.astype(jnp.float32).reshape(S, Hkv, H // Hkv, dh)
    s = jnp.einsum("skgd,stkd->skgt", qg, rows(k_pool)) * sm_scale
    valid = (pos >= first[:, None]) & (pos < lengths[:, None])
    p = jax.nn.softmax(jnp.where(valid[:, None, None, :], s, _NEG_INF),
                       axis=-1)
    out = jnp.einsum("skgt,stkd->skgd", p, rows(v_pool)).reshape(S, H, dh)
    dead = (lengths <= 0)[:, None, None]
    return jnp.where(dead, 0.0, out).astype(q.dtype)


def _window_decode_kernel(table_ref, first_ref, len_ref, q_ref, *refs,
                          page_size, **dims):
    """One grid step is one SLOT over a ring: its ``p``-th resident page
    is logical page ``first // page_size + p``, in the ring's column of
    that page modulo ``R``, and the rows before ``first`` are masked."""
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    R = table_ref.shape[1]

    def pages_of(slot):
        lo, hi = ring_pages(first_ref[slot], len_ref[slot], page_size)
        return jnp.where(len_ref[slot] > 0, hi - lo + 1, 0)

    def column_of(slot, p):
        return (first_ref[slot] // page_size + p) % R

    _attend_slot(table_ref, s, first_ref[s], len_ref[s], pages_of, column_of,
                 q_ref, *refs, page_size=page_size, **dims)


# jitted for the reason ``gqa_paged_attention._gqa_pallas`` is
@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "interpret", "group"))
def _window_pallas(q, k_pool, v_pool, ring_table, first, lengths, sm_scale,
                   interpret, group=None):
    return _slot_walk_call(
        _window_decode_kernel, WINDOW_KERNEL_NAME,
        (ring_table, first, lengths), q, k_pool, v_pool, sm_scale, interpret,
        group)


def window_paged_attention(q, k_pool, v_pool, ring_table, lengths, window,
                           sm_scale=None, force_reference=False,
                           force_pallas=False):
    """Grouped-query decode attention of every slot over the last
    ``window`` of its ``lengths`` resident rows, held in a ring of pages.

    q: [S, H, dh]; k_pool/v_pool: [num_pages, page_size, Hkv * dh] with
    ``H`` a multiple of ``Hkv``; ring_table: [S, R], logical page ``j`` in
    column ``j % R``, ``R`` pages enough for ``window`` rows at any
    alignment. Returns [S, H, dh]. The Pallas kernel on TPU targets, the
    reference elsewhere; a kernel the compiler refuses raises
    ``KernelCompileError``.
    """
    S, H, dh = q.shape
    ps, width = k_pool.shape[1], k_pool.shape[2]
    R = ring_table.shape[1]
    if k_pool.ndim != 3 or width % dh or H % (width // dh):
        raise ValueError(
            "a grouped-query page pool is [num_pages, page_size, Hkv * dh] "
            "with the %d query heads of %d a multiple of Hkv; got pool %s"
            % (H, dh, tuple(k_pool.shape)))
    if (R - 1) * ps < int(window) - 1:
        raise ValueError(
            "a ring of %d pages of %d rows cannot hold a window of %d at "
            "every alignment" % (R, ps, window))
    if sm_scale is None:
        sm_scale = dh ** -0.5
    use_pallas = force_pallas or (not force_reference and _is_tpu_target())
    if not use_pallas:
        return window_paged_attention_reference(
            q, k_pool, v_pool, ring_table, lengths, int(window), sm_scale)
    first = jnp.maximum(lengths - int(window), 0)
    try:
        return _window_pallas(q, k_pool, v_pool, ring_table, first, lengths,
                              sm_scale, interpret=not _is_tpu_target())
    except Exception as exc:
        raise KernelCompileError(
            WINDOW_KERNEL_NAME, (q, k_pool, v_pool, ring_table, lengths),
            exc) from exc
