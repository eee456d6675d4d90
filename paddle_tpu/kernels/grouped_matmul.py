"""Grouped matrix products for routed experts: ``lhs`` rows sorted by
group, ``rhs`` one matrix a group, ``out[rows of group g] = lhs[rows of
group g] @ rhs[g]``.

``jax.lax.ragged_dot`` is the reference, and what runs off the TPU. On a
v5e XLA lowers it to a Mosaic kernel of its own with 512-row tiles: a
decode step's 64 groups of ~16 rows each pay a whole tile, and the
product is compute-bound on padding at 36% of its (memory) roofline (1.39
ms for 1024 rows x [64, 2048, 1536], where reading the weights once takes
0.49; my chip runs, PR 27). On TPU targets the product therefore runs
through the Pallas grouped-matmul kernel that ships with JAX
(``jax.experimental.pallas.ops.tpu.megablox.gmm``) with 128-row tiles and
the whole contraction in one tile: 0.65 ms for the same product, 0.76 ms
against 1.53 for a prefill dispatch's 3200 rows of 8192.

Rows past the last group (tokens that do not exist) are not computed and
hold whatever the output buffer held: callers mask them.
"""

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.flash_attention import _is_tpu_target
from paddle_tpu.kernels.paged_attention import KernelCompileError

# megablox's pallas_call carries its function's name: what a trace and the
# compiled text call the kernel
GROUPED_KERNEL_NAME = "gmm"
_ROWS = 128       # rows a tile: a group of a few rows pays one tile of these
_COLS = 512       # output columns a tile
_DEPTH = 2048     # the contraction in ONE tile up to this (two buffers of a
#                   [6144, 512] weight tile pass the 16 MB of scoped VMEM)


def _depth_tile(k):
    """The whole contraction where it fits a tile, else its largest
    divisor of whole lanes under ``_DEPTH``."""
    if k <= _DEPTH:
        return k
    return next((t for t in range(_DEPTH, 127, -128) if k % t == 0), k)


def grouped_matmul_reference(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32)


def grouped_matmul(lhs, rhs, group_sizes, force_reference=False,
                   force_pallas=False):
    """lhs: [M, K] (rows sorted by group); rhs: [G, K, N]; group_sizes:
    [G] int32, their sum at most M. Returns [M, N] float32 (operands in
    their own dtype, float32 accumulation)."""
    use_pallas = force_pallas or (not force_reference and _is_tpu_target())
    if not use_pallas:
        return grouped_matmul_reference(lhs, rhs, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = lhs.shape
    n = rhs.shape[2]
    pad = -m % _ROWS
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    try:
        out = gmm(lhs, rhs, group_sizes.astype(jnp.int32),
                  preferred_element_type=jnp.float32,
                  tiling=(_ROWS, _depth_tile(k), min(n, _COLS)),
                  interpret=not _is_tpu_target())
    except Exception as exc:
        raise KernelCompileError(
            GROUPED_KERNEL_NAME, (lhs, rhs, group_sizes), exc) from exc
    return out[:m]
