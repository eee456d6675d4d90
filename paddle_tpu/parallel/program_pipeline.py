"""Program-level pipeline parallelism: cut a fluid Program into S stages.

Reference capability: the transparent multi-device story of
``paddle/fluid/framework/details/multi_devices_graph_pass.cc`` — the user
writes an ordinary Program (layers + optimizer.minimize) and the executor
spreads it over devices. The reference spreads by DATA parallelism; this
module adds the pipeline dimension the same transparent way: ParallelExecutor
cuts the Program's forward into S stages, runs a GPipe microbatch schedule
over the mesh's ``pipe`` axis, and applies the Program's own optimizer ops —
no hand-stacked homogeneous blocks (that capability layer is
``parallel/pipeline.py:gpipe``; this is the front-end that subsumes it for
real models with heterogeneous per-stage parameters).

TPU-first design (one compiled SPMD program, no per-stage executables):

- **Cutting**: a valid cut point is an op boundary where exactly ONE
  non-persistable, non-feed var is live across it (the classic GPipe
  single-activation boundary); all chosen boundaries must agree on
  activation shape[1:]/dtype so the rotating carry is a single buffer.
  Cuts are chosen to balance parameter bytes per stage.
- **Heterogeneous stage params**: each stage's params are flattened and
  concatenated into one f32 vector, padded to the longest stage, and
  stacked [S, L] — sharded ``P("pipe")`` so device s holds ONLY stage s's
  weights (O(P/S) param memory). Inside the per-device body each stage's
  branch unpacks its own slices; ``lax.switch`` on the device's axis index
  dispatches the right stage function (SPMD-compatible heterogeneity:
  every device compiles all branches, runs one).
- **Schedule**: M microbatches flow through S stages in M+S-1 ticks of a
  ``lax.scan``; activations hop to the next device with ``lax.ppermute``
  (nearest-neighbor on ICI). Bubbles are skipped with ``lax.cond``.
- **Backward**: ``jax.grad`` of the whole pipelined loss — the transpose
  of ppermute/scan/switch IS the reverse pipeline schedule; no backward
  graph is cut or scheduled by hand.
- **Optimizer**: the Program's optimize-role ops are applied on the packed
  [S, L] vectors directly (elementwise updates vectorize over the packed
  layout and preserve the pipe sharding); LR-schedule ops and scalar
  accumulators (beta powers) lower on a replicated scalar environment via
  the ordinary op registry.
- **data parallelism**: with a 2-D (pipe, data) mesh the microbatch batch
  dim is sharded over "data"; GSPMD inserts the gradient psum across the
  data axis because the packed params are replicated along it.

Constraints (checked, with errors naming them): the forward must be
cuttable at single-var uniform boundaries (encoder-style stacks and MLPs
qualify; encoder-decoder cross-attention does not — its boundary carries
two live vars); all trainable params must share one optimizer op type,
attrs, and learning rate; forward ops must not write persistables (fold
BN-stats models into data parallelism instead); fetches are limited to
the loss.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.core import op_registry
from paddle_tpu.core.lowering import BlockLowerer
from paddle_tpu.core.op_registry import LowerContext, normalize_outputs
from paddle_tpu.framework import OP_ROLE_ATTR_NAME, OpRole

_NON_SEMANTIC_ATTRS = (OP_ROLE_ATTR_NAME, "op_role_var", "__rng_id__")


class _Segment(object):
    def __init__(self, ops, in_var, out_var):
        self.ops = ops
        self.in_var = in_var      # boundary var consumed (None for stage 0)
        self.out_var = out_var    # boundary var produced (loss for last)
        self.param_names = []     # persistable inputs, packing order
        self.feed_names = []


def _role(op):
    return op.attrs.get(OP_ROLE_ATTR_NAME, OpRole.Forward)


def _split_roles(block):
    fwd, opt, lrsched = [], [], []
    for op in block.ops:
        r = _role(op)
        if r == OpRole.LRSched:
            lrsched.append(op)
        elif r & OpRole.Optimize:
            opt.append(op)
        elif r & OpRole.Backward:
            pass  # re-derived by jax.grad of the pipelined forward
        else:
            fwd.append(op)
    return fwd, opt, lrsched


def _var_bytes(v):
    if not v.shape:
        return 4
    return 4 * int(np.prod([abs(d) for d in v.shape]))


def _find_cuts(block, fwd_ops, feed_names, n_stages):
    """Choose n_stages-1 single-live-var cut points balancing param bytes."""
    produced_at = {}
    for i, op in enumerate(fwd_ops):
        for name in op.output_arg_names():
            if name:
                produced_at.setdefault(name, i)
    consumers = {}
    for i, op in enumerate(fwd_ops):
        for name in op.input_arg_names():
            if name:
                consumers.setdefault(name, []).append(i)

    def is_state(name):
        v = block._find_var_recursive(name)
        return v is not None and v.persistable

    # candidate cut at position p: live set {produced < p, consumed >= p}
    candidates = []
    for p in range(1, len(fwd_ops)):
        live = set()
        for name, start in produced_at.items():
            if start < p and not is_state(name) and name not in feed_names:
                if any(c >= p for c in consumers.get(name, ())):
                    live.add(name)
        if len(live) == 1:
            (name,) = live
            v = block._find_var_recursive(name)
            if v is None or v.shape is None:
                continue
            sig = (tuple(v.shape[1:]), str(v.dtype))
            candidates.append((p, name, sig))
    if not candidates:
        raise ValueError(
            "pipeline: no single-live-var cut point exists in the forward "
            "(multi-var boundaries — e.g. encoder-decoder cross attention "
            "— are not pipelineable by this pass)")

    # boundaries must agree on activation signature: take the modal group
    groups = {}
    for c in candidates:
        groups.setdefault(c[2], []).append(c)
    sig, group = max(groups.items(), key=lambda kv: len(kv[1]))
    if len(group) < n_stages - 1:
        raise ValueError(
            "pipeline: only %d uniform cut points (activation %s) but "
            "%d stages need %d cuts — lower pipeline_stages"
            % (len(group), sig, n_stages, n_stages - 1))

    # balance parameter bytes: weight[i] = bytes of params first READ at op i
    seen = set()
    weight = np.zeros(len(fwd_ops))
    for i, op in enumerate(fwd_ops):
        for name in op.input_arg_names():
            if name and name not in seen and is_state(name):
                seen.add(name)
                weight[i] = weight[i] + _var_bytes(
                    block._find_var_recursive(name))
    cum = np.cumsum(weight)
    total = float(cum[-1]) or 1.0
    group.sort(key=lambda c: c[0])
    cuts = []
    for s in range(1, n_stages):
        target = total * s / n_stages
        remaining_after = n_stages - 1 - s
        # a pick must stay increasing AND leave enough later candidates
        # for the cuts still to be placed (greedy-by-target alone could
        # grab a late position and strand the tail)
        feasible = [
            c for i, c in enumerate(group)
            if (not cuts or c[0] > cuts[-1][0])
            and len(group) - i - 1 >= remaining_after
        ]
        best = min(
            feasible,
            key=lambda c: abs(float(cum[c[0] - 1]) - target),
            default=None)
        if best is None:
            raise ValueError(
                "pipeline: could not place %d increasing cuts among the "
                "uniform candidates" % (n_stages - 1))
        cuts.append(best)
    return cuts


def _pack_layout(segments, block):
    """Per stage: [(name, offset, size, shape)] + the padded row length."""
    layouts, lengths = [], []
    for seg in segments:
        off, entries = 0, []
        for name in seg.param_names:
            v = block._find_var_recursive(name)
            if str(v.dtype) not in ("float32", "paddle_tpu_f32", "FP32"):
                # packed rows are one f32 buffer; params are f32 in this
                # framework (AMP casts at op boundaries, not in storage)
                raise ValueError(
                    "pipeline: param %r has dtype %s; only float32 params "
                    "are packable" % (name, v.dtype))
            shape = tuple(int(d) for d in v.shape)
            size = int(np.prod(shape)) if shape else 1
            entries.append((name, off, size, shape))
            off += size
        layouts.append(entries)
        lengths.append(off)
    return layouts, max(lengths) if lengths else 1


class PipelinedProgram(object):
    """One jitted pipelined train step for a minimize()'d Program."""

    def __init__(self, program, loss_name, feed_specs, mesh,
                 n_microbatches, axis_name="pipe", batch_axis=None):
        self.program = program
        self.loss_name = loss_name
        self.mesh = mesh
        self.axis_name = axis_name
        self.batch_axis = batch_axis
        self.n_stages = int(mesh.shape[axis_name])
        self.n_micro = int(n_microbatches)
        self.data_size = int(mesh.shape[batch_axis]) if batch_axis else 1
        if self.n_stages < 2:
            raise ValueError("pipeline needs a pipe axis of size >= 2")
        block = program.global_block()
        self.block = block
        self.lowerer = BlockLowerer(program, 0, is_test=False)

        fwd_ops, opt_ops, lrsched_ops = _split_roles(block)
        if not fwd_ops:
            raise ValueError("pipeline: program has no forward ops")
        self._check_no_persistable_writes(fwd_ops, block)
        self._build_segments(fwd_ops, set(feed_specs))
        self._classify_optimizer(opt_ops, lrsched_ops, block)
        self.layouts, self.row_len = _pack_layout(self.segments, block)
        self._record_stage_metrics()
        self._build_step(feed_specs)

    def _record_stage_metrics(self):
        """Per-stage balance + occupancy gauges, one series per stage.
        Recorded once per BUILD (never per step): an imbalanced cut —
        one stage holding most of the ops/params — is the pipeline's
        straggler, visible here before a single tick runs."""
        from paddle_tpu.observability import telemetry
        from paddle_tpu.observability.metrics_registry import REGISTRY

        telemetry.record_pipeline_occupancy(self.n_stages, self.n_micro)
        ops_g = REGISTRY.gauge(
            "paddle_tpu_pipeline_stage_ops",
            "forward ops per pipeline stage (cut balance)",
            labels=("stage",))
        bytes_g = REGISTRY.gauge(
            "paddle_tpu_pipeline_stage_param_bytes",
            "packed parameter bytes per pipeline stage",
            labels=("stage",))
        for s, seg in enumerate(self.segments):
            ops_g.set(len(seg.ops), stage="%d" % s)
            bytes_g.set(
                sum(_var_bytes(self.block._find_var_recursive(n))
                    for n in seg.param_names
                    if self.block._find_var_recursive(n) is not None),
                stage="%d" % s)

    # -- analysis ----------------------------------------------------------
    @staticmethod
    def _check_no_persistable_writes(fwd_ops, block):
        for op in fwd_ops:
            for name in op.output_arg_names():
                v = block._find_var_recursive(name) if name else None
                if v is not None and v.persistable:
                    raise ValueError(
                        "pipeline: forward op %r writes persistable %r "
                        "(running-stats models are not pipelineable; use "
                        "data parallelism)" % (op.type, name))

    def _build_segments(self, fwd_ops, feed_names):
        cuts = _find_cuts(self.block, fwd_ops, feed_names, self.n_stages)
        bounds = [0] + [c[0] for c in cuts] + [len(fwd_ops)]
        names = [c[1] for c in cuts]
        self.segments = []
        for s in range(self.n_stages):
            seg = _Segment(
                fwd_ops[bounds[s]:bounds[s + 1]],
                in_var=names[s - 1] if s > 0 else None,
                out_var=names[s] if s < self.n_stages - 1
                else self.loss_name,
            )
            produced = set()
            for op in seg.ops:
                for name in op.input_arg_names():
                    if not name or name in produced:
                        continue
                    v = self.block._find_var_recursive(name)
                    if v is not None and v.persistable:
                        if name not in seg.param_names:
                            seg.param_names.append(name)
                    elif name in feed_names and name not in seg.feed_names:
                        seg.feed_names.append(name)
                produced.update(op.output_arg_names())
            self.segments.append(seg)
        if not any(self.loss_name in op.output_arg_names()
                   for op in self.segments[-1].ops):
            raise ValueError(
                "pipeline: loss %r is not produced by the last stage"
                % self.loss_name)

    def _classify_optimizer(self, opt_ops, lrsched_ops, block):
        updates = [op for op in opt_ops
                   if op.input("Param") and op.input("Grad")]
        if not updates:
            raise ValueError(
                "pipeline: program has no optimizer update ops (call "
                "optimizer.minimize first)")
        tmpl = updates[0]
        sem = {k: v for k, v in tmpl.attrs.items()
               if k not in _NON_SEMANTIC_ATTRS}
        for op in updates[1:]:
            if op.type != tmpl.type or sem != {
                    k: v for k, v in op.attrs.items()
                    if k not in _NON_SEMANTIC_ATTRS}:
                raise ValueError(
                    "pipeline: all params must share one optimizer "
                    "(found %s vs %s)" % (tmpl.type, op.type))
            if op.input("LearningRate") != tmpl.input("LearningRate"):
                raise ValueError(
                    "pipeline: per-parameter learning rates are not "
                    "supported under the packed pipeline update")
        self.update_by_param = {op.input("Param")[0]: op for op in updates}
        self.update_template = tmpl
        self.update_attrs = sem
        opdef = op_registry.get_op_def(tmpl.type)
        # acc slots: same-shape-as-param -> packed [S, L]; [1] -> scalar env
        self.packed_slots, self.scalar_slots = [], []
        for slot in opdef.input_slots():
            if slot in ("Param", "Grad", "LearningRate"):
                continue
            if not tmpl.input(slot):
                continue
            name = tmpl.input(slot)[0]
            v = block._find_var_recursive(name)
            pshape = block._find_var_recursive(
                tmpl.input("Param")[0]).shape
            if tuple(v.shape or ()) == tuple(pshape or ()):
                if ("%sOut" % slot) not in opdef.output_slots():
                    raise ValueError(
                        "pipeline: optimizer slot %s has no %sOut output"
                        % (slot, slot))
                self.packed_slots.append(slot)
            else:
                self.scalar_slots.append(slot)
        # scalar ops: optimize-role ops that are not param updates (lr
        # scaling, beta-pow advance); split around the first update op
        first_update = min(block.ops.index(op) for op in updates)
        self.pre_scalar_ops = [
            op for op in opt_ops + lrsched_ops
            if op not in updates and block.ops.index(op) < first_update]
        self.post_scalar_ops = [
            op for op in opt_ops + lrsched_ops
            if op not in updates and block.ops.index(op) >= first_update]
        self.pre_scalar_ops.sort(key=block.ops.index)
        self.post_scalar_ops.sort(key=block.ops.index)
        # replicated scalar state: persistables read/written by scalar ops
        # and the scalar optimizer slots of EVERY param
        names = []
        for op in self.pre_scalar_ops + self.post_scalar_ops:
            names.extend(op.input_arg_names())
            names.extend(op.output_arg_names())
        names.extend(self.update_template.input("LearningRate"))
        for slot in self.scalar_slots:
            for op in updates:
                names.extend(op.input(slot))
        self.scalar_state = []
        for n in names:
            v = block._find_var_recursive(n) if n else None
            if v is not None and v.persistable and n not in self.scalar_state:
                self.scalar_state.append(n)

    # -- the compiled step --------------------------------------------------
    def _branch(self, s):
        seg = self.segments[s]
        layout = self.layouts[s]
        lowerer = self.lowerer
        is_last = s == self.n_stages - 1

        def run(local_vec, act, mb_feeds, key, zero_act, zero_loss):
            env = {}
            for name, off, size, shape in layout:
                flat = jax.lax.dynamic_slice(local_vec, (off,), (size,))
                env[name] = flat.reshape(shape) if shape else flat[0]
            for name in seg.feed_names:
                env[name] = mb_feeds[name]
            if seg.in_var is not None:
                env[seg.in_var] = act
            for op in seg.ops:
                lowerer.lower_op(op, env, key)
            # zero_act/zero_loss carry the varying-axes marking every
            # branch output must share (lax.switch type agreement)
            if is_last:
                loss = jnp.reshape(
                    env[self.loss_name], ()).astype(jnp.float32)
                return zero_act, zero_loss + loss
            return (zero_act + env[seg.out_var].astype(zero_act.dtype),
                    zero_loss)

        return run

    def _boundary_act_spec(self, feed_specs):
        """Trace stage 0 alone to learn the boundary activation shape for
        one LOCAL microbatch (batch dim = B / M / data_parallel)."""
        micro = self._micro_local(feed_specs)
        branch0 = self._branch(0)

        def probe(feeds):
            vec = jnp.zeros((self.row_len,), jnp.float32)
            mb = {n: feeds[n] for n in feeds}
            dummy = jnp.zeros((), jnp.float32)
            act, _ = branch0(vec, dummy, mb, jax.random.PRNGKey(0), dummy,
                            jnp.float32(0.0))
            return act

        specs = {
            n: jax.ShapeDtypeStruct((micro,) + tuple(shape[1:]), dtype)
            for n, (shape, dtype) in feed_specs.items()
        }
        # params in the probe are zeros of the right size: shape inference
        # only needs shapes, and stage 0's slices all fit in one row
        out = jax.eval_shape(probe, specs)
        return out.shape, out.dtype

    def _micro_local(self, feed_specs):
        any_shape = next(iter(feed_specs.values()))[0]
        b = any_shape[0]
        denom = self.n_micro * self.data_size
        if b % denom:
            raise ValueError(
                "pipeline: batch %d must divide microbatches*data = %d*%d"
                % (b, self.n_micro, self.data_size))
        return b // denom

    def _build_step(self, feed_specs):
        mesh = self.mesh
        axis = self.axis_name
        n, m = self.n_stages, self.n_micro
        act_shape, act_dtype = self._boundary_act_spec(feed_specs)
        branches = [self._branch(s) for s in range(n)]
        fwd_perm = [(i, i + 1) for i in range(n - 1)]
        batch_axis = self.batch_axis

        def _vary(x):
            axes = (axis, batch_axis) if batch_axis else (axis,)
            return jax.lax.pcast(x, axes, to="varying")

        def shard_body(vec, feeds, key):
            # vec [1, L]; feeds [M, micro_local, ...]
            d = jax.lax.axis_index(axis)
            local = vec[0]
            zero_act = _vary(jnp.zeros(act_shape, act_dtype))
            zero_loss = _vary(jnp.float32(0.0))
            ticks = m + n - 1

            def tick(carry, t):
                prev_out, loss_sum = carry
                recv = jax.lax.ppermute(prev_out, axis, fwd_perm)
                mb = t - d
                valid = (mb >= 0) & (mb < m)
                slot = jnp.clip(mb, 0, m - 1)
                mb_feeds = {
                    k: jax.lax.dynamic_index_in_dim(
                        v, slot, 0, keepdims=False)
                    for k, v in feeds.items()
                }
                tick_key = jax.random.fold_in(
                    jax.random.fold_in(key, t), d)

                def work(args):
                    act, mbf = args
                    return jax.lax.switch(
                        d, branches, local, act, mbf, tick_key, zero_act,
                        zero_loss)

                def bubble(args):
                    return zero_act, zero_loss

                safe_recv = jnp.where(valid, recv, zero_act)
                y, lval = jax.lax.cond(
                    valid, work, bubble, (safe_recv, mb_feeds))
                loss_sum = loss_sum + jnp.where(valid, lval, 0.0)
                return (y, loss_sum), None

            init = (zero_act, zero_loss)
            (_, loss_sum), _ = jax.lax.scan(
                tick, init, jnp.arange(ticks))
            # only the last device banked nonzero loss; share it out
            total = jax.lax.psum(loss_sum, axis) / m
            if batch_axis:
                total = jax.lax.pmean(total, batch_axis)
            return total

        feed_spec = (P(None, batch_axis) if batch_axis else P())
        pipeline_loss = jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(P(axis), {k: feed_spec for k in feed_specs}, P()),
            out_specs=P(),
        )

        lowerer = self.lowerer
        pre_ops, post_ops = self.pre_scalar_ops, self.post_scalar_ops
        tmpl, attrs = self.update_template, dict(self.update_attrs)
        packed_slots, scalar_slots = self.packed_slots, self.scalar_slots
        opdef = op_registry.get_op_def(tmpl.type)
        lr_name = tmpl.input("LearningRate")[0]

        def train_step(packed, accs, scalars, feeds, key):
            env = dict(scalars)
            for op in pre_ops:
                lowerer.lower_op(op, env, key)
            split = {
                k: v.reshape((m, v.shape[0] // m) + v.shape[1:])
                for k, v in feeds.items()
            }

            def loss_fn(p):
                return pipeline_loss(p, split, key)

            loss, grad = jax.value_and_grad(loss_fn)(packed)
            ins = {"Param": [packed], "Grad": [grad],
                   "LearningRate": [jnp.reshape(env[lr_name], (1,))]}
            for slot in packed_slots:
                ins[slot] = [accs[slot]]
            for slot in scalar_slots:
                ins[slot] = [env[tmpl.input(slot)[0]]]
            ctx = LowerContext(
                tmpl, rng=lambda: jax.random.PRNGKey(0), is_test=False,
                block_lowerer=lowerer)
            outs = normalize_outputs(opdef, opdef.lower(ctx, ins, attrs))
            new_packed = outs["ParamOut"][0]
            new_accs = {slot: outs["%sOut" % slot][0]
                        for slot in packed_slots}
            for op in post_ops:
                lowerer.lower_op(op, env, key)
            new_scalars = {n: env[n] for n in scalars}
            return new_packed, new_accs, new_scalars, loss

        row = NamedSharding(mesh, P(axis))
        rep = NamedSharding(mesh, P())
        feed_in = NamedSharding(mesh, P(batch_axis) if batch_axis else P())
        self.jitted = jax.jit(
            train_step,
            in_shardings=(row, {s: row for s in self.packed_slots},
                          {n: rep for n in self.scalar_state},
                          {n: feed_in for n in feed_specs}, rep),
            out_shardings=(row, {s: row for s in self.packed_slots},
                           {n: rep for n in self.scalar_state}, rep),
            donate_argnums=(0, 1, 2),
        )

    # -- packed state <-> scope --------------------------------------------
    def pack_from_scope(self, scope):
        """Build the packed [S, L] param/acc arrays from scope values."""
        row = NamedSharding(self.mesh, P(self.axis_name))
        rep = NamedSharding(self.mesh, P())

        def read(name):
            v = scope.find_var(name)
            if v is None or v.value is None:
                raise RuntimeError(
                    "pipeline: persistable %r not initialized (run the "
                    "startup program first)" % name)
            return np.asarray(v.value)

        def packed(name_of):
            mat = np.zeros((self.n_stages, self.row_len), np.float32)
            for s, layout in enumerate(self.layouts):
                for pname, off, size, _ in layout:
                    mat[s, off:off + size] = read(
                        name_of(pname)).reshape(-1)
            return jax.device_put(mat, row)

        params = packed(lambda p: p)
        accs = {}
        for slot in self.packed_slots:
            accs[slot] = packed(
                lambda p, slot=slot:
                self.update_by_param[p].input(slot)[0])
        # scalar slots must be equal across params to share one value
        for slot in self.scalar_slots:
            vals = [read(op.input(slot)[0])
                    for op in self.update_by_param.values()]
            if not all(np.allclose(vals[0], v) for v in vals[1:]):
                raise ValueError(
                    "pipeline: per-param %s values diverge; cannot share "
                    "a packed update" % slot)
        scalars = {n: jax.device_put(read(n), rep)
                   for n in self.scalar_state}
        return params, accs, scalars

    def unpack_to_scope(self, scope, params, accs):
        """Write packed params/accs back to their per-name scope vars (for
        save_persistables / inspection)."""
        host = np.asarray(params)
        host_accs = {s: np.asarray(a) for s, a in accs.items()}
        for s, layout in enumerate(self.layouts):
            for pname, off, size, shape in layout:
                scope.set_value(
                    pname, host[s, off:off + size].reshape(shape))
                for slot in self.packed_slots:
                    aname = self.update_by_param[pname].input(slot)[0]
                    scope.set_value(
                        aname,
                        host_accs[slot][s, off:off + size].reshape(shape))
