"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference framework predates long-context training entirely
(SURVEY.md §5.7 — no attention kernel, no sequence parallelism); this
module is the TPU-native design that provides it:

* ``ring_attention`` — sequence-sharded Q/K/V; K/V blocks rotate around
  the mesh axis with ``jax.lax.ppermute`` (ICI neighbor exchange) while a
  running online-softmax accumulator absorbs one block per step. Memory per
  chip is O(T/N), enabling contexts N× longer than one chip could hold.
* ``ulysses_attention`` — all-to-all re-partition: trade the sequence
  sharding for a head sharding (`jax.lax.all_to_all`), run ordinary
  (flash) attention on full sequences for a head subset, and trade back.
  Cheaper for moderate T when heads % N == 0.

Both are pure per-shard functions for use under ``shard_map`` over a
``jax.sharding.Mesh`` axis, and both are reverse-differentiable (scan +
ppermute / all_to_all have transposition rules), so they drop into the
training path.
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30


def _ring_attention_shard(q, k, v, axis_name, causal, sm_scale):
    """Per-shard body. q,k,v: [B, H, Tl, d] local sequence chunks."""
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    Tl = q.shape[2]
    d = q.shape[3]
    qf = q.astype(jnp.float32) * sm_scale
    q_pos = my * Tl + jnp.arange(Tl)  # global query positions

    def _vary(x):
        # Mark device-uniform initial carries as varying over the ring axis
        # (shard_map's varying-axis type system requires carry in/out match).
        return jax.lax.pcast(x, (axis_name,), to="varying")

    acc0 = _vary(jnp.zeros(q.shape[:3] + (d,), jnp.float32))
    m0 = _vary(jnp.full(q.shape[:3] + (1,), _NEG_INF, jnp.float32))
    l0 = _vary(jnp.zeros(q.shape[:3] + (1,), jnp.float32))
    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(carry, i):
        acc, m, l, k_cur, v_cur = carry
        src = (my - i) % n  # owner of the block currently held
        s = jnp.einsum(
            "bhtd,bhsd->bhts", qf, k_cur.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        if causal:
            k_pos = src * Tl + jnp.arange(Tl)
            s = jnp.where(
                k_pos[None, None, None, :] <= q_pos[None, None, :, None],
                s,
                _NEG_INF,
            )
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bhts,bhsd->bhtd", p, v_cur.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return (acc_new, m_new, l_new, k_next, v_next), None

    (acc, m, l, _, _), _ = jax.lax.scan(
        step, (acc0, m0, l0, k, v), jnp.arange(n)
    )
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def _ring_flash_shard(q, k, v, axis_name, causal, sm_scale):
    """Ring attention with the Pallas flash kernel as the per-block
    engine: each rotating K/V block is absorbed through
    ``_flash_forward`` (O(block) memory — no [Tl, Tl] score matrix even
    within a shard) and the per-block (out, lse) partials merge by
    log-sum-exp. The causal diagonal block is PEELED before the scan so
    the kernel's static ``causal`` flag applies only there; rotated
    blocks are whole-block keep/drop decided by a traced ownership test.

    Backward recomputes through the XLA reference shard
    (``_ring_attention_shard``) under custom_vjp at the ring level —
    the same recompute strategy flash attention itself launched with.
    """
    from paddle_tpu.kernels.flash_attention import (
        _DEFAULT_BLOCK_K,
        _DEFAULT_BLOCK_Q,
        _flash_forward,
        _is_tpu_target,
    )

    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    interpret = not _is_tpu_target()
    perm = [(j, (j + 1) % n) for j in range(n)]

    def block_partial(k_blk, v_blk, blk_causal):
        out, lse = _flash_forward(
            q, k_blk, v_blk, None, blk_causal, sm_scale,
            _DEFAULT_BLOCK_Q, _DEFAULT_BLOCK_K, interpret,
        )
        # lse: [B, H, 1, Tp] (padded); out: [B, H, Tl, d]
        Tl = q.shape[2]
        return out.astype(jnp.float32), jnp.moveaxis(
            lse[:, :, :, :Tl], 3, 2)  # -> [B, H, Tl, 1]

    def merge(acc, lse_acc, out_b, lse_b, keep):
        # drop the whole block by sending its lse to -inf
        lse_b = jnp.where(keep, lse_b, _NEG_INF)
        lse_new = jnp.logaddexp(lse_acc, lse_b)
        w_acc = jnp.exp(lse_acc - lse_new)
        w_b = jnp.exp(lse_b - lse_new)
        return acc * w_acc + out_b * w_b, lse_new

    # Peeled diagonal block: own K/V, causal iff the global op is causal.
    acc, lse_acc = block_partial(k, v, causal)
    # First rotation happens alongside the peeled compute above.
    k_cur = jax.lax.ppermute(k, axis_name, perm)
    v_cur = jax.lax.ppermute(v, axis_name, perm)

    def step(carry, i):
        acc, lse_acc, k_cur, v_cur = carry
        # Compute on the HELD block while the next exchange is in
        # flight — both read k_cur, so XLA overlaps ICI with the MXU
        # (the reference shard's schedule).
        out_b, lse_b = block_partial(k_cur, v_cur, False)
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        src = (my - i) % n  # owner of the held block
        # causal: keep only blocks strictly before this shard's queries
        keep = (src < my) if causal else jnp.asarray(True)
        acc, lse_acc = merge(acc, lse_acc, out_b, lse_b, keep)
        return (acc, lse_acc, k_next, v_next), None

    if n > 1:
        (acc, lse_acc, _, _), _ = jax.lax.scan(
            step, (acc, lse_acc, k_cur, v_cur), jnp.arange(1, n))
    return acc.astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_shard_flash(q, k, v, axis_name, causal, sm_scale):
    return _ring_flash_shard(q, k, v, axis_name, causal, sm_scale)


def _ring_shard_flash_fwd(q, k, v, axis_name, causal, sm_scale):
    out = _ring_shard_flash(q, k, v, axis_name, causal, sm_scale)
    return out, (q, k, v)


def _ring_shard_flash_bwd(axis_name, causal, sm_scale, res, g):
    # Recompute through the XLA reference ring (ppermute and scan both
    # have transpose rules) — the flash forward's memory win stands, the
    # backward matches the reference shard bit-for-bit in math.
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _ring_attention_shard(
            q_, k_, v_, axis_name=axis_name, causal=causal,
            sm_scale=sm_scale),
        q, k, v,
    )
    return vjp(g)


_ring_shard_flash.defvjp(_ring_shard_flash_fwd, _ring_shard_flash_bwd)


def ring_attention(q, k, v, mesh, axis_name="data", causal=False,
                   sm_scale=None, impl="auto"):
    """Ring attention over sequence-sharded [B, H, T, d] tensors.

    q/k/v are GLOBAL arrays; the mesh axis ``axis_name`` shards the
    sequence (dim 2). Returns the global output with the same sharding.

    impl: "auto" (flash blocks on TPU targets, XLA reference elsewhere),
    "flash" (force the Pallas per-block engine — interpret mode off-TPU),
    or "reference".
    """
    from paddle_tpu.kernels.flash_attention import _is_tpu_target

    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if impl not in ("auto", "flash", "reference"):
        raise ValueError(
            "ring_attention: impl must be 'auto', 'flash' or 'reference'"
            ", got %r" % (impl,))
    use_flash = impl == "flash" or (impl == "auto" and _is_tpu_target())
    spec = P(None, None, axis_name, None)
    sm_kwargs = dict(mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)
    if use_flash:
        # custom_vjp takes its nondiff args positionally
        def body(q_, k_, v_):
            return _ring_shard_flash(q_, k_, v_, axis_name, causal,
                                     sm_scale)

        # pallas_call out_shapes carry no varying-axis (vma) annotation,
        # which shard_map's type checker rejects; the check is a static
        # lint, not a semantic change — disable it for this body
        fn = jax.shard_map(body, check_vma=False, **sm_kwargs)
    else:
        fn = jax.shard_map(
            functools.partial(
                _ring_attention_shard, axis_name=axis_name, causal=causal,
                sm_scale=sm_scale),
            **sm_kwargs)
    return fn(q, k, v)


def _ulysses_shard(q, k, v, axis_name, causal, sm_scale):
    """Per-shard body. q,k,v: [B, H, Tl, d]; requires H % n == 0."""
    # public entry: Pallas flash kernel on TPU targets, XLA reference on
    # CPU (pallas_call composes with shard_map)
    from paddle_tpu.kernels.flash_attention import flash_attention

    # [B, H, Tl, d] -> all_to_all -> [B, H/n, T, d]
    def seq_to_head(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    def head_to_seq(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    qh = seq_to_head(q)
    kh = seq_to_head(k)
    vh = seq_to_head(v)
    out = flash_attention(qh, kh, vh, causal=causal, sm_scale=sm_scale)
    return head_to_seq(out)


def ulysses_attention(q, k, v, mesh, axis_name="data", causal=False,
                      sm_scale=None):
    """All-to-all (DeepSpeed-Ulysses style) sequence-parallel attention."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    n = mesh.shape[axis_name]
    if q.shape[1] % n != 0:
        raise ValueError(
            "ulysses_attention needs heads (%d) divisible by axis size (%d)"
            % (q.shape[1], n)
        )
    spec = P(None, None, axis_name, None)
    fn = jax.shard_map(
        functools.partial(
            _ulysses_shard,
            axis_name=axis_name,
            causal=causal,
            sm_scale=sm_scale,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
