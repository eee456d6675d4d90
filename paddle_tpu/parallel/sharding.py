"""Sharding transpiler: derive a ``data x fsdp x tp`` GSPMD plan from the
op graph.

This replaces the pserver-era ``distribute_transpiler`` *planning* role
(slice_variable deciding which rows live on which pserver) with the GSPMD
equivalent: walk the Program's op graph once and annotate every VarDesc
with a PartitionSpec over the named mesh axes, so ``ParallelExecutor``
can shard a model with **zero hand-written layout entries**. The axis
semantics follow the scaling-book recipe (SNIPPETS [1] ``SpecLayout``):

* ``data`` — pure data parallelism: batch dims shard over it, params
  replicate, gradients all-reduce;
* ``fsdp`` — data parallelism that ALSO shards parameters/optimizer
  state (ZeRO-ish): batch dims shard over ``data x fsdp``, params shard
  a dim over ``fsdp`` (all-gather on use, reduce-scatter on grads);
* ``tp`` — tensor (model) parallelism: Megatron column/row splits on
  matmul weights, vocab splits on embeddings.

Canonical per-op rules (the table docs/DISTRIBUTED_DESIGN.md documents):

  mul/matmul (param Y)   column-parallel ``P(fsdp, tp)`` — or, when the
                         input activation already carries a tp-sharded
                         feature dim, row-parallel ``P(tp, fsdp)`` with
                         the implied psum charged to the tp axis
  lookup_table (W)       vocab-sharded ``P((fsdp, tp), None)``
  conv2d* (Filter)       ``P(fsdp, ...)`` on the out-channel dim
  batch_norm/layer_norm  stats/scale/bias replicated; activations stay
                         batch-sharded (reductions are global under jit)
  elementwise/reshape/   propagate batch and tp tags through
  transpose/split/...

Conflict resolution inserts an explicit *resharding point* (a
``jax.lax.with_sharding_constraint`` applied by the lowering at the
producing op — see core/lowering.py) rather than silently replicating:
e.g. tp-partial logits flowing into a loss reduction get constrained
back to batch-sharded/replicated-features exactly once, visibly.

Every fallback to replication is recorded in ``plan.notes`` ("no silent
caps"), and hand-written ``sharding_overrides`` remain an *override* on
top of the derived plan, validated by analysis rule S001
(analysis/shard_check.py) at transpile time.
"""

import logging

import numpy as np

from paddle_tpu.analysis.shard_check import (
    _mesh_axes_dict,
    check_sharding,
    normalize_spec,
    spec_axes,
    spec_shard_factor,
)

__all__ = [
    "ShardingPlan", "DerivedShardingPolicy", "derive_sharding",
    "record_collective_bytes", "plan_shard_factors", "MIN_SHARD_NUMEL",
]

logger = logging.getLogger("paddle_tpu.parallel")

# Params below this element count replicate: the per-step collective to
# gather a tiny sharded bias costs more than the bytes it saves (same
# threshold the legacy dim-0 "reduce" policy used).
MIN_SHARD_NUMEL = 1024

# Ops whose outputs keep their inputs' batch/tp tags verbatim.
_PROPAGATE_OPS = frozenset((
    "relu6", "brelu", "elu", "leaky_relu", "prelu", "soft_relu", "swish",
    "stanh", "hard_sigmoid", "hard_shrink", "softshrink",
    "thresholded_relu", "scale", "cast", "dropout", "softmax",
    "log_softmax", "clip", "pad", "pad2d", "label_smooth", "pow",
    "one_hot", "add_position_encoding", "rotary_embedding",
    "scaled_dot_product_attention", "l2_normalize", "cumsum",
))
_PROPAGATE_PREFIXES = ("elementwise_",)
# unary activation wrappers (layers/ops.py) all lower through these names
_PROPAGATE_UNARY = frozenset((
    "sigmoid", "logsigmoid", "exp", "relu", "gelu", "tanh", "tanh_shrink",
    "sqrt", "rsqrt", "abs", "ceil", "floor", "cos", "sin", "round",
    "reciprocal", "log", "square", "softplus", "softsign",
))
# shape surgery: tags flow through, batch tag only while dim 0 survives
_RESHAPEY_OPS = frozenset((
    "reshape", "reshape2", "flatten", "flatten2", "squeeze", "squeeze2",
    "unsqueeze", "unsqueeze2", "split", "concat", "stack", "slice",
    "expand", "transpose", "transpose2",
))
# batch-sharded compute whose params stay replicated
_NORM_OPS = frozenset(("batch_norm", "layer_norm", "group_norm",
                       "affine_channel"))
_CONV_OPS = frozenset(("conv2d", "depthwise_conv2d", "conv3d",
                       "conv2d_transpose", "conv3d_transpose",
                       "depthwise_conv2d_transpose"))
_POOL_OPS = frozenset(("pool2d", "pool3d", "max_pool2d_with_index",
                       "max_pool3d_with_index", "lrn", "spp"))


class ShardingPlan(object):
    """The derived plan: var -> PartitionSpec (as plain tuples), plus the
    audit trail (fallback notes, reshard points, per-axis collective-byte
    estimates). ``specs`` holds every annotated var; ``param_specs()`` /
    ``feed_specs()`` filter by kind for the executor."""

    def __init__(self, mesh_axes):
        self.mesh_axes = {str(a): int(s) for a, s in dict(mesh_axes).items()}
        self.specs = {}        # name -> normalized spec tuple
        self.kinds = {}        # name -> "param" | "feed" | "activation"
        self.notes = {}        # name -> why it fell back / was overridden
        self.reshard_points = []  # {"var", "op_idx", "op_type", "spec"}
        self.collective_bytes = {}  # axis -> predicted bytes per step

    def _set(self, name, spec, kind, note=None):
        self.specs[name] = normalize_spec(spec)
        self.kinds[name] = kind
        if note:
            self.notes[name] = note

    def spec(self, name):
        return self.specs.get(name)

    def _by_kind(self, kind):
        return {n: s for n, s in self.specs.items()
                if self.kinds.get(n) == kind}

    def param_specs(self):
        return self._by_kind("param")

    def feed_specs(self):
        return self._by_kind("feed")

    def shard_factor(self, name):
        """How many devices split var ``name`` (1 = replicated)."""
        spec = self.specs.get(name)
        if not spec:
            return 1
        return spec_shard_factor(spec, self.mesh_axes)

    def sharded_params(self):
        return sorted(n for n in self.param_specs()
                      if self.shard_factor(n) > 1)

    def summary(self):
        """Compact dict for captures/benches: mesh axes, per-kind counts,
        how many params shard over which axes, reshard points."""
        params = self.param_specs()
        axis_counts = {}
        for n in params:
            for a in spec_axes(self.specs[n]):
                axis_counts[a] = axis_counts.get(a, 0) + 1
        return {
            "mesh_axes": dict(self.mesh_axes),
            "params": len(params),
            "params_sharded": len(self.sharded_params()),
            "params_by_axis": axis_counts,
            "feeds": len(self.feed_specs()),
            "activations_annotated": len(self._by_kind("activation")),
            "reshard_points": len(self.reshard_points),
            "fallbacks": len(self.notes),
            "collective_bytes": dict(self.collective_bytes),
        }

    def as_dict(self):
        return {
            "mesh_axes": dict(self.mesh_axes),
            "specs": {n: _spec_str(s) for n, s in sorted(self.specs.items())},
            "kinds": dict(self.kinds),
            "notes": dict(self.notes),
            "reshard_points": [dict(r) for r in self.reshard_points],
            "collective_bytes": dict(self.collective_bytes),
        }

    def __repr__(self):
        s = self.summary()
        return ("ShardingPlan(mesh=%s, %d/%d params sharded, "
                "%d reshard points)" % (s["mesh_axes"], s["params_sharded"],
                                        s["params"], s["reshard_points"]))


def _spec_str(spec):
    return "P(%s)" % ", ".join(
        "None" if e is None else
        ("(%s)" % ",".join(e) if isinstance(e, tuple) else e)
        for e in spec) if spec else "P()"


def _numel(shape):
    n = 1
    for d in shape:
        n *= max(1, int(d))
    return n


def _var_bytes(v, batch_size):
    """Logical bytes of one var, dynamic (-1) dims priced at
    ``batch_size`` — the collective-estimate discipline, matching
    observability/memory.py's accounting."""
    if v is None or v.shape is None:
        return 0
    size = 1
    for d in v.shape:
        d = int(d)
        size *= d if d > 0 else max(1, int(batch_size))
    try:
        item = np.dtype(str(v.dtype)).itemsize
    except Exception:
        item = 4
    return size * item


class _Deriver(object):
    def __init__(self, program, axes, overrides, feed_shapes, batch_size,
                 min_shard_numel):
        self.program = program
        self.block = program.global_block()
        self.axes = axes
        self.overrides = {n: normalize_spec(s)
                          for n, s in (overrides or {}).items()}
        self.feed_shapes = dict(feed_shapes or {})
        self.batch_size = batch_size
        self.min_numel = min_shard_numel
        self.plan = ShardingPlan(axes)
        self.data_n = axes.get("data", 1)
        self.fsdp_n = axes.get("fsdp", 1)
        self.tp_n = axes.get("tp", 1)
        # batch dims shard over every data-parallel axis present
        self.batch_axes = tuple(a for a in ("data", "fsdp") if a in axes)
        self.batch_ways = self.data_n * self.fsdp_n
        self.batch_vars = set()   # vars whose dim 0 is the global batch
        self.tp_vars = set()      # vars carrying a tp-sharded feature dim
        self.batch_ok = True      # concrete batch divides the batch axes

    # -- small helpers ------------------------------------------------------

    def _var(self, name):
        return self.block._find_var_recursive(name)

    def _is_param(self, name):
        from paddle_tpu.framework import Parameter

        return isinstance(self._var(name), Parameter)

    def _note(self, name, why):
        self.plan.notes[name] = why
        logger.info("derive_sharding: %s -> replicated dim (%s)", name, why)

    def _axis_fits(self, name, dim_size, axis_n, why_tag):
        """One dim, one axis: shardable iff the axis divides the dim."""
        if axis_n <= 1:
            return False
        if dim_size is None or int(dim_size) <= 0:
            return False
        if int(dim_size) % axis_n:
            self._note(name, "%s axis %d does not divide dim of size %d"
                       % (why_tag, axis_n, dim_size))
            return False
        return True

    def _set_param(self, name, spec, note=None):
        if name in self.overrides:
            self.plan._set(name, self.overrides[name], "param",
                           note="override (derived %s)" % _spec_str(
                               normalize_spec(spec)))
            return
        if name in self.plan.specs:
            # conflict: two use sites derived different layouts — the
            # FIRST wins (its collectives were already priced); a
            # differing second demand is recorded, not silently merged
            old = self.plan.specs[name]
            new = normalize_spec(spec)
            if old != new:
                self._note(name, "conflicting derived specs %s vs %s; "
                           "kept the first, consumer reshards"
                           % (_spec_str(old), _spec_str(new)))
            return
        self.plan._set(name, spec, "param", note=note)

    def _tag_out(self, op, batch=None, tp=None):
        for name in op.output_arg_names():
            if not name:
                continue
            if batch:
                self.batch_vars.add(name)
            if tp:
                self.tp_vars.add(name)

    def _inputs_tagged(self, op):
        ins = [n for n in op.input_arg_names() if n]
        return (any(n in self.batch_vars for n in ins),
                any(n in self.tp_vars for n in ins))

    def _charge(self, axis, nbytes):
        if nbytes > 0 and self.axes.get(axis, 1) > 1:
            self.plan.collective_bytes[axis] = (
                self.plan.collective_bytes.get(axis, 0) + int(nbytes))

    # -- feeds --------------------------------------------------------------

    def _seed_feeds(self):
        for name in sorted(self.block.vars):
            v = self.block.vars[name]
            if not getattr(v, "is_data", False):
                continue
            if name in self.overrides:
                # overrides win outright, feeds included (the legacy
                # ShardingPolicy honored feed overrides; so do we)
                self.plan._set(name, self.overrides[name], "feed",
                               note="override")
                continue
            shape = self.feed_shapes.get(name, v.shape)
            rank = len(shape) if shape is not None else None
            if not self.batch_axes or rank in (None, 0):
                self.plan._set(name, (), "feed",
                               note="scalar or unknown-rank feed" if rank
                               in (None, 0) else None)
                continue
            dim0 = int(shape[0])
            if dim0 > 0 and dim0 % self.batch_ways:
                self.plan._set(name, (), "feed",
                               note="batch %d not divisible by %d-way "
                               "data x fsdp" % (dim0, self.batch_ways))
                self.batch_ok = False
                continue
            self.plan._set(
                name, (self.batch_axes,) + (None,) * (rank - 1), "feed")
            self.batch_vars.add(name)

    # -- per-op rules -------------------------------------------------------

    def _rule_matmul(self, op, op_idx):
        xs = op.input("X") or op.input("Input")
        ys = op.input("Y") or op.input("W")
        outs = op.output("Out")
        if not xs or not ys or not outs:
            return
        x, y, out = xs[0], ys[0], outs[0]
        x_batch = x in self.batch_vars
        x_tp = x in self.tp_vars
        yv = self._var(y)
        if not self._is_param(y) or yv is None or yv.shape is None \
                or len(yv.shape) != 2:
            # activation x activation (attention scores etc.): tags flow
            self._tag_out(op, batch=x_batch, tp=x_tp or y in self.tp_vars)
            return
        rows, cols = int(yv.shape[0]), int(yv.shape[1])
        # "matmul" spells it transpose_Y (ops/math_ops.py); "mul" has none
        transpose_y = bool(op.attrs.get("transpose_Y", False))
        if transpose_y:
            rows, cols = cols, rows
        small = _numel(yv.shape) < self.min_numel
        if small:
            self._set_param(y, (), note="numel %d < %d threshold"
                            % (_numel(yv.shape), self.min_numel))
            self._tag_out(op, batch=x_batch, tp=False)
            return
        row_parallel = x_tp
        if row_parallel:
            # contracted dim already tp-sharded: shard W's rows over tp
            # (local partial matmul + psum), park fsdp on the cols
            r = "tp" if self._axis_fits(y, rows, self.tp_n, "tp") else None
            c = "fsdp" if self._axis_fits(y, cols, self.fsdp_n, "fsdp") \
                else None
            spec = (r, c)
            if transpose_y:
                spec = (c, r)
            self._set_param(y, spec)
            if r:
                ov = self._var(out)
                self._charge("tp", _var_bytes(ov, self.batch_size))
            self._tag_out(op, batch=x_batch, tp=False)
        else:
            # column-parallel: rows carry fsdp (storage), cols carry tp
            r = "fsdp" if self._axis_fits(y, rows, self.fsdp_n, "fsdp") \
                else None
            c = "tp" if self._axis_fits(y, cols, self.tp_n, "tp") else None
            spec = (r, c)
            if transpose_y:
                spec = (c, r)
            self._set_param(y, spec)
            self._tag_out(op, batch=x_batch, tp=bool(c))

    def _rule_lookup(self, op, op_idx):
        ws = op.input("W")
        outs = op.output("Out")
        if not ws:
            return
        w = ws[0]
        wv = self._var(w)
        if wv is None or wv.shape is None or not self._is_param(w):
            return
        vocab = int(wv.shape[0])
        if _numel(wv.shape) < self.min_numel:
            self._set_param(w, (), note="numel %d < %d threshold"
                            % (_numel(wv.shape), self.min_numel))
        else:
            # vocab rows shard over fsdp x tp together when divisible,
            # degrading one axis at a time before giving up
            for entry, ways in ((("fsdp", "tp"), self.fsdp_n * self.tp_n),
                                (("fsdp",), self.fsdp_n),
                                (("tp",), self.tp_n)):
                if ways > 1 and vocab % ways == 0:
                    self._set_param(
                        w, (entry,) + (None,) * (len(wv.shape) - 1))
                    if "tp" in entry:
                        # out-of-shard rows resolve via psum over tp
                        self._charge("tp", _var_bytes(
                            self._var(outs[0]) if outs else None,
                            self.batch_size))
                    break
            else:
                if self.fsdp_n * self.tp_n > 1:
                    self._set_param(w, (), note="vocab %d not divisible "
                                    "by fsdp x tp (%d)"
                                    % (vocab, self.fsdp_n * self.tp_n))
        ids_batch = any(n in self.batch_vars for n in op.input("Ids"))
        self._tag_out(op, batch=ids_batch, tp=False)

    def _rule_conv(self, op, op_idx):
        fs = op.input("Filter")
        if fs:
            w = fs[0]
            wv = self._var(w)
            if self._is_param(w) and wv is not None and wv.shape:
                if _numel(wv.shape) < self.min_numel:
                    self._set_param(w, (), note="numel %d < %d threshold"
                                    % (_numel(wv.shape), self.min_numel))
                elif self._axis_fits(w, wv.shape[0], self.fsdp_n, "fsdp"):
                    self._set_param(
                        w, ("fsdp",) + (None,) * (len(wv.shape) - 1))
                else:
                    self._set_param(w, ())
        batch, _tp = self._inputs_tagged(op)
        self._tag_out(op, batch=batch, tp=False)

    def _rule_norm(self, op, op_idx):
        for slot in ("Scale", "Bias", "Mean", "Variance"):
            for name in op.input(slot):
                if name and self._is_param(name) or (
                        name and self._var(name) is not None
                        and self._var(name).persistable):
                    self._set_param(name, (), note="norm statistics stay "
                                    "replicated (reductions are global "
                                    "under jit)")
        batch, tp = self._inputs_tagged(op)
        self._tag_out(op, batch=batch, tp=tp)

    def _rule_generic_param(self, op, op_idx):
        """Default for params consumed by ops with no specific rule:
        fsdp-shard dim 0 when it divides and the var is big enough."""
        batch, tp = self._inputs_tagged(op)
        for name in op.input_arg_names():
            if not name or not self._is_param(name) \
                    or name in self.plan.specs:
                continue
            v = self._var(name)
            if v is None or v.shape is None or not v.shape:
                continue
            if _numel(v.shape) < self.min_numel:
                self._set_param(name, (), note="numel %d < %d threshold"
                                % (_numel(v.shape), self.min_numel))
            elif self._axis_fits(name, v.shape[0], self.fsdp_n, "fsdp"):
                self._set_param(
                    name, ("fsdp",) + (None,) * (len(v.shape) - 1))
            else:
                self._set_param(name, ())
        self._tag_out(op, batch=batch, tp=tp)

    def _maybe_reshard(self, op, op_idx):
        """Conflict resolution: a tp-partial activation flowing into an
        op that reduces/consumes it with no tp story (losses, metrics,
        full reductions) gets an explicit resharding point at its
        producer — batch stays sharded, features go whole — instead of
        the weight silently replicating."""
        for name in op.input_arg_names():
            if name in self.tp_vars:
                v = self._var(name)
                rank = len(v.shape) if (v is not None and
                                        v.shape is not None) else 1
                batch0 = (self.batch_axes if (
                    name in self.batch_vars and self.batch_axes
                    and self.batch_ok) else None)
                spec = (batch0,) + (None,) * (rank - 1) if rank else ()
                self.plan.reshard_points.append({
                    "var": name, "op_idx": op_idx, "op_type": op.type,
                    "spec": _spec_str(normalize_spec(spec))})
                if v is not None:
                    v.reshard_spec = normalize_spec(spec)
                self._charge("tp", _var_bytes(v, self.batch_size))
                self.tp_vars.discard(name)

    # -- the walk -----------------------------------------------------------

    def derive(self):
        from paddle_tpu.framework import OpRole, OP_ROLE_ATTR_NAME

        self._clear_annotations()
        self._seed_feeds()
        for op_idx, op in enumerate(self.block.ops):
            role = op.attrs.get(OP_ROLE_ATTR_NAME, OpRole.Forward)
            if role not in (OpRole.Forward, OpRole.Loss,
                            OpRole.Forward | OpRole.Loss):
                continue  # backward/optimize follow the forward layout
            t = op.type
            if t in ("mul", "matmul"):
                self._rule_matmul(op, op_idx)
            elif t == "lookup_table":
                self._rule_lookup(op, op_idx)
            elif t in _CONV_OPS:
                self._rule_conv(op, op_idx)
            elif t in _NORM_OPS:
                self._rule_norm(op, op_idx)
            elif (t in _PROPAGATE_OPS or t in _PROPAGATE_UNARY
                  or t.startswith(_PROPAGATE_PREFIXES)):
                # params riding along (biases, learned embeddings added
                # elementwise) get the generic rule: tiny ones replicate
                # with a note, big divisible ones fsdp-shard dim 0 —
                # never a silent un-noted replication
                self._rule_generic_param(op, op_idx)
            elif t in _RESHAPEY_OPS:
                batch, tp = self._inputs_tagged(op)
                if batch and not self._keeps_batch_dim(op):
                    batch = False
                self._tag_out(op, batch=batch, tp=tp)
            elif t in _POOL_OPS:
                batch, _tp = self._inputs_tagged(op)
                self._tag_out(op, batch=batch, tp=False)
            elif t in ("mean", "reduce_sum", "reduce_mean", "reduce_max",
                       "reduce_min", "cross_entropy",
                       "softmax_with_cross_entropy",
                       "fused_label_smooth_ce", "accuracy",
                       "square_error_cost", "sum", "top_k", "arg_max",
                       "fetch"):
                self._maybe_reshard(op, op_idx)
                # per-row losses keep the batch dim; scalars drop it
                batch, _tp = self._inputs_tagged(op)
                for name in op.output_arg_names():
                    v = self._var(name)
                    if (batch and v is not None and v.shape
                            and len(v.shape) >= 1):
                        self.batch_vars.add(name)
            else:
                self._rule_generic_param(op, op_idx)

        self._annotate_activations()
        self._inherit_accumulators()
        self._apply_leftover_overrides()
        self._price_param_collectives()
        self._write_annotations()
        return self.plan

    def _keeps_batch_dim(self, op):
        """Dim 0 survives: transpose keeping axis 0 first, reshape whose
        leading dim is -1/unchanged, split/concat off dim 0, etc."""
        t = op.type
        if t in ("transpose", "transpose2"):
            perm = op.attrs.get("axis") or op.attrs.get("perm") or ()
            return not perm or list(perm)[0] == 0
        if t in ("split", "concat", "stack", "slice"):
            dim = op.attrs.get("dim", op.attrs.get("axis", -1))
            axes = op.attrs.get("axes", None)
            if t == "slice":
                return not axes or 0 not in list(axes)
            return dim != 0
        if t in ("reshape", "reshape2", "flatten", "flatten2"):
            ins = [n for n in op.input_arg_names() if n]
            outs = [n for n in op.output_arg_names() if n]
            if ins and outs:
                vi, vo = self._var(ins[0]), self._var(outs[0])
                if (vi is not None and vo is not None and vi.shape
                        and vo.shape):
                    return int(vi.shape[0]) == int(vo.shape[0]) or (
                        int(vi.shape[0]) < 0 and int(vo.shape[0]) < 0)
            shape_attr = op.attrs.get("shape") or ()
            return bool(shape_attr) and int(shape_attr[0]) in (-1, 0)
        return True  # squeeze/unsqueeze/expand of trailing dims

    def _annotate_activations(self):
        if not (self.batch_axes and self.batch_ok):
            return
        for name in self.batch_vars:
            if name in self.plan.specs:
                continue
            v = self._var(name)
            if v is None or v.shape is None or not v.shape:
                continue
            self.plan._set(
                name, (self.batch_axes,) + (None,) * (len(v.shape) - 1),
                "activation",
                note="tp-partial features" if name in self.tp_vars
                else None)

    def _inherit_accumulators(self):
        """Optimizer accumulators ('<param>_moment_0' etc.) declared in
        the program inherit their param's layout when same-shaped, so
        moments partition exactly like the weight (the mesh.py prefix
        rule, resolved statically here)."""
        params = self.plan.param_specs()
        for name in sorted(self.block.vars):
            if name in self.plan.specs:
                continue
            v = self.block.vars[name]
            if not getattr(v, "persistable", False) or v.shape is None:
                continue
            for base, spec in params.items():
                if name.startswith(base + "_") and tuple(v.shape) == tuple(
                        getattr(self._var(base), "shape", ()) or ()):
                    self.plan._set(name, spec, "param",
                                   note="inherits %s" % base)
                    break

    def _price_param_collectives(self):
        """Per-axis per-step collective-byte estimates for the plan's
        params: grads all-reduce over pure-data axes; fsdp-sharded
        params all-gather + their grads reduce-scatter (2x bytes);
        fsdp-replicated params still all-reduce grads over fsdp."""
        from paddle_tpu.framework import Parameter

        for name, spec in self.plan.param_specs().items():
            v = self._var(name)
            nbytes = _var_bytes(v, self.batch_size)
            if not nbytes:
                continue
            if not isinstance(v, Parameter) or getattr(
                    v, "stop_gradient", False):
                # optimizer accumulators (sharding-aligned updates, no
                # gather) and non-trainable state (BN stats): no grad or
                # fsdp traffic of their own
                continue
            axes_used = set(spec_axes(spec))
            if self.data_n > 1:
                self._charge("data", nbytes)
            if self.fsdp_n > 1:
                self._charge("fsdp",
                             2 * nbytes if "fsdp" in axes_used else nbytes)

    def _clear_annotations(self):
        """Drop annotations a PREVIOUS derivation stamped (possibly under
        a different mesh or overrides): a var this plan never touches
        must not keep — and core/lowering.py must not apply — the old
        plan's spec. (A cached plan skips derive(), so two executors
        alternating derivations over one program can still interleave
        stamps; each fresh derivation at least starts from zero.)"""
        for block in self.program.blocks:
            for v in block.vars.values():
                if hasattr(v, "partition_spec"):
                    del v.partition_spec
                if hasattr(v, "reshard_spec"):
                    del v.reshard_spec

    def _apply_leftover_overrides(self):
        """Overrides win outright — including for vars no op rule or
        feed/accumulator sweep reached (S001 already validated them
        against the program and mesh)."""
        for name, spec in self.overrides.items():
            if name in self.plan.specs:
                continue
            v = self._var(name)
            if getattr(v, "is_data", False):
                kind = "feed"
            elif self._is_param(name) or getattr(v, "persistable", False):
                kind = "param"
            else:
                kind = "activation"
            self.plan._set(name, spec, kind,
                           note="override (no derivation rule reached it)")

    def _write_annotations(self):
        """Stamp every derived spec onto its VarDesc so the plan is
        inspectable (debugger.program_to_code) without running it."""
        for name, spec in self.plan.specs.items():
            v = self._var(name)
            if v is not None:
                v.partition_spec = spec


def derive_sharding(program, mesh_axes, overrides=None, feed_shapes=None,
                    batch_size=None, min_shard_numel=MIN_SHARD_NUMEL,
                    validate=True):
    """Derive a :class:`ShardingPlan` for ``program`` over ``mesh_axes``
    (a ``jax.sharding.Mesh`` or an ``{axis: size}`` dict using the
    ``data``/``fsdp``/``tp`` names).

    ``overrides`` (the old hand-written ``tp_layout`` surface) take
    precedence over the derived specs and are validated by analysis rule
    S001 first — a bad override raises
    :class:`analysis.ProgramVerifyError` here, at transpile time, not as
    an XLA shape error mid-compile. ``feed_shapes`` resolves dynamic
    batch dims so batch-axis divisibility is checked for real; without
    it the plan assumes a divisible batch and the runtime feed fallback
    still protects execution. Annotates every planned var's
    ``Variable.partition_spec`` (and conflict vars' ``reshard_spec``,
    which core/lowering.py turns into an explicit
    ``with_sharding_constraint``).
    """
    axes = _mesh_axes_dict(mesh_axes)
    if validate and overrides:
        from paddle_tpu.analysis.diagnostics import (
            ProgramVerifyError, at_or_above)

        diags = check_sharding(program, axes, overrides,
                               origin="sharding override")
        errors = at_or_above(diags, "error")
        if errors:
            raise ProgramVerifyError(errors, origin="derive_sharding")
    if batch_size is None:
        batch_size = 1
        for s in (feed_shapes or {}).values():
            if s and int(s[0]) > 0:
                batch_size = max(batch_size, int(s[0]))
    d = _Deriver(program, axes, overrides, feed_shapes, batch_size,
                 min_shard_numel)
    return d.derive()


class DerivedShardingPolicy(object):
    """A :class:`ShardingPlan` in the ``ShardingPolicy`` interface the
    executors consume (``mesh`` / ``state_sharding`` / ``feed_sharding``
    / ``replicated`` / ``plan``): the derived specs become the in/out
    shardings of the single jitted executable. Vars the plan never saw
    (scalar LR counters, beta pows) replicate; optimizer accumulators
    created AFTER derivation still inherit their param's layout through
    the same prefix+shape rule mesh.ShardingPolicy applies."""

    strategy = "derived"

    def __init__(self, mesh, plan, state_shapes=None):
        from jax.sharding import NamedSharding, PartitionSpec

        self.mesh = mesh
        self.derived = plan
        self.state_shapes = dict(state_shapes or {})
        self._NamedSharding = NamedSharding
        self._PartitionSpec = PartitionSpec
        self._logged = set()

    def replicated(self):
        return self._NamedSharding(self.mesh, self._PartitionSpec())

    def _spec_to_sharding(self, spec):
        return self._NamedSharding(
            self.mesh, self._PartitionSpec(*normalize_spec(spec)))

    def _derived_spec(self, name):
        spec = self.derived.specs.get(name)
        if spec is not None:
            return spec
        # late-created accumulators ("<param>_moment1_0"): inherit the
        # param's layout when same-shaped (same rule the legacy policy
        # applies dynamically; derive-time inheritance only covers vars
        # already declared in the program)
        shape = self.state_shapes.get(name)
        if shape is not None:
            for base, pspec in self.derived.param_specs().items():
                if name.startswith(base + "_") and tuple(shape) == tuple(
                        self.state_shapes.get(base, ())):
                    return pspec
        return None

    def state_sharding(self, name):
        spec = self._derived_spec(name)
        if spec:
            return self._spec_to_sharding(spec)
        return self.replicated()

    def feed_sharding(self, name, shape=None):
        spec = self.derived.specs.get(name)
        if spec is None:
            # a feed the derivation never saw (derived without
            # feed_shapes, or a var fed ad hoc): batch-shard when the
            # concrete shape divides, replicate otherwise
            axes = tuple(a for a in ("data", "fsdp")
                         if self.derived.mesh_axes.get(a, 1) >= 1
                         and a in self.derived.mesh_axes)
            ways = 1
            for a in axes:
                ways *= self.derived.mesh_axes[a]
            if (shape is None or not len(shape) or ways <= 1
                    or int(shape[0]) % ways):
                if name not in self._logged:
                    self._logged.add(name)
                    logger.info(
                        "derived sharding fallback: feed %s -> replicated "
                        "(shape %s not divisible by %d-way batch axes)",
                        name, tuple(shape) if shape is not None else None,
                        ways)
                return self.replicated()
            return self._spec_to_sharding((axes,))
        if shape is not None and spec:
            # concrete shape wins over the derive-time assumption
            factor = 1
            for a in spec_axes((spec[0],) if spec else ()):
                factor *= self.derived.mesh_axes.get(a, 1)
            if len(shape) and factor > 1 and int(shape[0]) % factor:
                if name not in self._logged:
                    self._logged.add(name)
                    logger.info(
                        "derived sharding fallback: feed %s -> replicated "
                        "(batch %d not divisible by %d)", name,
                        int(shape[0]), factor)
                return self.replicated()
        return self._spec_to_sharding(spec)

    def plan(self):
        """name -> (spec str, note) for observability — the same contract
        mesh.ShardingPolicy.plan() has, fed from the derived plan."""
        out = {}
        for name in sorted(self.derived.specs):
            out[name] = (_spec_str(self.derived.specs[name]),
                         self.derived.notes.get(name, ""))
        return out


def plan_shard_factors(plan):
    """{var name -> ways split} for every var the plan shards — the
    divisor Program.memory_plan applies so the predicted peak reflects
    per-device bytes, not logical bytes."""
    out = {}
    for name in plan.specs:
        f = plan.shard_factor(name)
        if f > 1:
            out[name] = f
    return out


def record_collective_bytes(plan):
    """Export the plan's per-axis collective-byte estimates as labeled
    gauges (``paddle_tpu_collective_bytes{axis}``) — the topology-traffic
    twin of the PR 4 straggler/imbalance metrics, refreshed once per
    compile, never per step."""
    from paddle_tpu.observability.metrics_registry import REGISTRY

    g = REGISTRY.gauge(
        "paddle_tpu_collective_bytes",
        "predicted per-step collective traffic per mesh axis, from the "
        "derived sharding plan (grad all-reduce / fsdp gather+scatter / "
        "tp psum)", labels=("axis",))
    for axis in plan.mesh_axes:
        g.set(int(plan.collective_bytes.get(axis, 0)), axis=str(axis))
    return dict(plan.collective_bytes)
