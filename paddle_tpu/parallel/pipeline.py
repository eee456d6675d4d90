"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

The reference has no pipelined trainer (it predates pipeline parallelism);
this module is the TPU-native design that provides the capability, sized
to the mesh's reserved "pipe" axis (parallel/mesh.py):

* stage s of the network lives on device s of the axis — stage parameters
  are STACKED on a leading dim and sharded over the axis, so each device
  holds only its own stage's weights;
* M microbatches flow through S stages in M + S - 1 ticks; at every tick
  each device runs its stage on the activation it holds, then hands the
  result to the next device with one ``jax.lax.ppermute`` hop (nearest
  neighbor on ICI — the cheapest collective on TPU);
* the schedule is a ``lax.scan`` over ticks, so it is a single compiled
  loop, and because it is built from transposable primitives the BACKWARD
  pipeline comes for free from jax.grad (reverse ppermute direction,
  reverse tick order — exactly GPipe's B-phase).

Activations are fed replicated by default, or batch-sharded over a second
mesh axis (``batch_axis``, pipeline x data parallel); outputs are
stage-stacked. Per-device activation memory is O(local batch), parameter
memory O(params / S). This is the capability layer (like ring_attention):
models wire it explicitly; the Program-level front-end keeps dp/tp/ZeRO
shardings via ParallelExecutor.
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def stack_stage_params(stage_params_list):
    """[pytree per stage] -> one pytree with a leading stage dim (what
    ``gpipe`` expects; shard dim 0 over the pipe axis)."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves, axis=0), *stage_params_list
    )


def _gpipe_shard(params, x, stage_fn, axis_name):
    """Per-device body. params leaves: [1, ...] (this stage's block);
    x: [M, B_local, ...] microbatches (the full batch when replicated, a
    batch shard under gpipe's batch_axis). Returns [M, B_local, ...] —
    only the LAST device's block holds the pipeline output; gpipe()
    slices it out of the stage-stacked global result."""
    n = jax.lax.psum(1, axis_name)
    d = jax.lax.axis_index(axis_name)
    local = jax.tree_util.tree_map(lambda l: l[0], params)
    m = x.shape[0]
    ticks = m + n - 1
    fwd_perm = [(i, i + 1) for i in range(n - 1)]

    # varying-marked zero activation: used for carries and as the cond
    # bubble branch, whose output type must match stage_fn's (varying)
    zero_act = jax.lax.pcast(jnp.zeros_like(x[0]), (axis_name,),
                             to="varying")

    def tick(carry, t):
        prev_out, outbuf = carry
        # activation arriving this tick: device 0 injects a fresh
        # microbatch, everyone else receives the left neighbor's output
        recv = jax.lax.ppermute(prev_out, axis_name, fwd_perm)
        inj = jax.lax.dynamic_index_in_dim(
            x, jnp.clip(t, 0, m - 1), 0, keepdims=False
        )
        my_in = jnp.where(d == 0, inj, recv)
        # device d works on microbatch t - d; outside [0, M) the lane is
        # a pipeline bubble — lax.cond SKIPS the stage there, so bubbles
        # cost nothing and stage_fns that are non-finite at zero (log,
        # rsqrt, ...) can't poison values OR gradients
        mb = t - d
        valid = (mb >= 0) & (mb < m)
        my_in = jnp.where(valid, my_in, zero_act)
        y = jax.lax.cond(
            valid,
            lambda a: stage_fn(local, a),
            lambda a: zero_act,
            my_in,
        )
        # the last device banks its (valid) results into the out buffer
        slot = jnp.clip(mb, 0, m - 1)
        cur = jax.lax.dynamic_index_in_dim(outbuf, slot, 0, keepdims=False)
        banked = jnp.where((d == n - 1) & valid, y, cur)
        outbuf = jax.lax.dynamic_update_index_in_dim(outbuf, banked, slot, 0)
        return (y, outbuf), None

    outbuf0 = jax.lax.pcast(jnp.zeros_like(x), (axis_name,), to="varying")
    (_, outbuf), _ = jax.lax.scan(
        tick, (zero_act, outbuf0), jnp.arange(ticks)
    )
    return outbuf


def gpipe(stage_fn, stage_params, x, mesh, axis_name="pipe",
          batch_axis=None, param_specs=None):
    """Run x through S pipelined stages.

    Args:
      stage_fn: (params_for_one_stage, activation [B, ...]) -> [B, ...].
        Every stage must map activations to the SAME shape (classic GPipe
        requirement; wrap reshape stages into neighbors).
      stage_params: pytree whose leaves are stage-stacked [S, ...]
        (see stack_stage_params); S must equal mesh.shape[axis_name].
      x: [M, B, ...] — M microbatches.
      mesh: jax.sharding.Mesh containing ``axis_name``.
      batch_axis: optional second mesh axis to keep the microbatch batch
        dim sharded over (pipeline x data parallel on a 2-D mesh). Without
        it the activations are replicated across the other axes.
      param_specs: optional pytree of PartitionSpec matching stage_params,
        for sharding stage weights over FURTHER mesh axes (tensor
        parallelism inside a stage — dp x tp x pp on a 3-D mesh). Every
        spec's dim 0 must be ``axis_name``; inside ``stage_fn`` the
        model-axis collectives (e.g. ``jax.lax.psum(.., "model")`` after
        a row-parallel matmul) are explicit, shard_map-style.

    Returns [M, B, ...]: the pipeline output, differentiable w.r.t. both
    stage_params and x; with batch_axis it stays batch-sharded.
    """
    n = mesh.shape[axis_name]
    leaves = jax.tree_util.tree_leaves(stage_params)
    if not leaves:
        raise ValueError("gpipe: empty stage_params")
    for l in leaves:
        if l.ndim == 0 or l.shape[0] != n:
            raise ValueError(
                "gpipe: every stage_params leaf needs a leading stage dim "
                "equal to the pipe axis size %d, got shape %s (one stage "
                "per device; stack with stack_stage_params, fold deeper "
                "networks into stage_fn)" % (n, l.shape))
    if param_specs is None:
        param_specs = jax.tree_util.tree_map(
            lambda _: P(axis_name), stage_params
        )
    else:
        for spec in jax.tree_util.tree_leaves(
                param_specs, is_leaf=lambda s: isinstance(s, P)):
            # dim-0 entries may be a bare axis name or an axis tuple
            # (P(("pipe", "data"), ...)); require pipe among them
            first = spec[0] if spec else None
            axes0 = first if isinstance(first, tuple) else (first,)
            if axis_name not in axes0:
                raise ValueError(
                    "gpipe: every param_specs entry must shard dim 0 over "
                    "the pipe axis %r, got %s" % (axis_name, spec))
    if batch_axis is not None:
        if batch_axis not in mesh.shape or batch_axis == axis_name:
            raise ValueError(
                "gpipe: batch_axis must name a mesh axis distinct from "
                "the pipe axis %r; got %r (mesh axes: %s)"
                % (axis_name, batch_axis, tuple(mesh.shape)))
        x_spec = P(None, batch_axis)
        out_spec = P(axis_name, batch_axis)
    else:
        x_spec = P()
        out_spec = P(axis_name)
    fn = jax.shard_map(
        functools.partial(
            _gpipe_shard, stage_fn=stage_fn, axis_name=axis_name
        ),
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=out_spec,
    )
    from paddle_tpu.observability import telemetry as _telemetry

    if _telemetry.ENABLED:
        # bubble fraction of this schedule: M useful ticks of M+S-1
        _telemetry.record_pipeline_occupancy(n, x.shape[0])
    stacked = fn(stage_params, x)  # [S*M, B, ...], last block is real
    m = x.shape[0]
    return stacked[(n - 1) * m:]
