"""Operator registry: schema + JAX lowering + gradient wiring.

Reference parity: ``paddle/fluid/framework/op_registry.h:190`` (registrar
macros), ``op_info.h`` (OpInfoMap), ``grad_op_desc_maker.h:34`` (grad desc
makers), ``op_proto_maker.cc`` (schemas). The TPU-first difference: instead
of registering per-device kernels dispatched one op at a time, each op
registers a *lowering rule* — a pure JAX function — and the Executor traces
a whole block through these rules into a single XLA computation.

Gradients: the default grad maker emits a ``<type>_grad`` op whose lowering
re-traces the forward rule under ``jax.vjp``. Recomputed forward values are
eliminated by XLA CSE inside the fused step program, so this costs nothing
at runtime while keeping Fluid's graph-level autodiff contract (grad ops are
real, inspectable ops that transpilers can rewrite).
"""

import jax
import jax.numpy as jnp

from paddle_tpu.core.types import canonical_dtype


class LowerContext(object):
    """Per-op context handed to lowering rules.

    Attributes:
      op: the framework.Operator being lowered (desc access).
      is_test: inference mode flag (clone(for_test=True) programs).
      block_lowerer: the BlockLowerer driving the trace (for control-flow
        mega-ops that need to lower sub-blocks).
    """

    def __init__(self, op, rng, is_test=False, block_lowerer=None):
        self.op = op
        self._rng = rng
        self.is_test = is_test
        self.block_lowerer = block_lowerer

    def rng(self):
        """A fresh PRNG key for this op instance (dropout, random init...).

        Deterministic given (program seed, op index); ops with a nonzero
        ``seed`` attr get a key derived from that seed instead, matching the
        reference's per-op seed semantics (e.g. dropout_op.cc seed attr).
        """
        return self._rng()


class OpDef(object):
    __slots__ = (
        "type",
        "inputs",
        "outputs",
        "attrs",
        "lower",
        "grad",
        "no_grad_inputs",
        "intermediate_outputs",
        "infer_shape",
        "lower_grad",
    )

    def __init__(
        self,
        type,
        inputs,
        outputs,
        attrs,
        lower,
        grad,
        no_grad_inputs,
        intermediate_outputs,
        infer_shape,
        lower_grad=None,
    ):
        self.type = type
        self.inputs = inputs  # list of slot names; "*X" marks duplicable
        self.outputs = outputs
        self.attrs = attrs  # dict name -> default
        self.lower = lower  # fn(ctx, ins, attrs) -> dict slot -> value(s)
        self.grad = grad  # None | "auto" | callable grad-desc maker
        self.no_grad_inputs = no_grad_inputs
        self.intermediate_outputs = intermediate_outputs
        self.infer_shape = infer_shape  # optional override
        # optional rule of the synthesized <type>_grad op, in place of the
        # vjp of ``lower`` (lower_grad_via_vjp's signature)
        self.lower_grad = lower_grad

    def input_slots(self):
        return [s.lstrip("*") for s in self.inputs]

    def output_slots(self):
        return [s.lstrip("*") for s in self.outputs]

    def is_duplicable_input(self, slot):
        return ("*" + slot) in self.inputs

    def is_duplicable_output(self, slot):
        return ("*" + slot) in self.outputs


_REGISTRY = {}


def register_op(
    type,
    inputs,
    outputs,
    attrs=None,
    lower=None,
    grad="auto",
    no_grad_inputs=(),
    intermediate_outputs=(),
    infer_shape=None,
    lower_grad=None,
):
    """Register an operator definition (REGISTER_OPERATOR analog).

    ``inputs``/``outputs``: slot names; prefix with ``*`` for duplicable
    slots (lists of vars, e.g. sum's X). ``grad``:
      - "auto": a generic ``<type>_grad`` op is synthesized whose lowering
        runs jax.vjp over this op's ``lower``;
      - callable(op, out_grads, in_grads_wanted) -> list of op spec dicts:
        custom grad-desc maker (for ops composed of other ops);
      - None: op has no gradient (EmptyGradOpMaker).
    ``lower_grad`` (with ``grad="auto"``): the synthesized op's lowering
    calls it in place of ``lower_grad_via_vjp``, with the same arguments,
    where an op forms a gradient another way than ``jax.vjp`` would.
    """
    if type in _REGISTRY:
        raise ValueError("op %r already registered" % type)
    if lower is None:
        raise ValueError("op %r needs a lowering rule" % type)
    opdef = OpDef(
        type=type,
        inputs=list(inputs),
        outputs=list(outputs),
        attrs=dict(attrs or {}),
        lower=lower,
        grad=grad,
        no_grad_inputs=frozenset(no_grad_inputs),
        intermediate_outputs=frozenset(intermediate_outputs),
        infer_shape=infer_shape,
        lower_grad=lower_grad,
    )
    _REGISTRY[type] = opdef
    return opdef


def get_op_def(type):
    opdef = _REGISTRY.get(type)
    if opdef is None:
        raise KeyError("operator %r is not registered" % type)
    return opdef


def has_op(type):
    return type in _REGISTRY


def registered_ops():
    return sorted(_REGISTRY)


def normalize_outputs(opdef, result):
    """Lowerings may return a single array, a tuple (positional outputs), or
    a dict slot -> array|list. Normalize to dict slot -> list[array]."""
    slots = opdef.output_slots()
    if isinstance(result, dict):
        out = {}
        for k, v in result.items():
            out[k] = list(v) if isinstance(v, (list, tuple)) else [v]
        return out
    if isinstance(result, tuple):
        if len(result) != len(slots):
            raise ValueError(
                "op %s lowering returned %d outputs, schema has %d"
                % (opdef.type, len(result), len(slots))
            )
        return {s: [r] for s, r in zip(slots, result)}
    return {slots[0]: [result]}


# ---------------------------------------------------------------------------
# Generic vjp-based gradient lowering
# ---------------------------------------------------------------------------


def lower_grad_via_vjp(fwd_def, ctx, ins, attrs, out_grads, wanted_input_grads):
    """Lower a ``<type>_grad`` op by differentiating the forward lowering.

    ins: forward inputs, dict slot -> list[array].
    out_grads: dict fwd-output-slot -> list[array or None] (None = no
      incoming gradient for that output; treated as zeros).
    wanted_input_grads: dict fwd-input-slot -> list[bool].

    Returns dict fwd-input-slot -> list[array or None].
    """
    import numpy as np

    def _is_inexact_array(a):
        # Composite values (tensor arrays = (buffer, size) tuples) are not
        # differentiable leaves themselves. Checked structurally:
        # jnp.result_type over a tuple PROMOTES instead of raising.
        if isinstance(a, (tuple, list)):
            return False
        try:
            return jnp.issubdtype(jnp.result_type(a), jnp.inexact)
        except TypeError:
            return False

    # Differentiable leaves: wanted AND inexact-dtyped.
    diff_index = []  # (slot, i)
    for slot, arrs in ins.items():
        wants = wanted_input_grads.get(slot, [False] * len(arrs))
        for i, a in enumerate(arrs):
            if i < len(wants) and wants[i] and _is_inexact_array(a):
                diff_index.append((slot, i))

    if not diff_index:
        return {}

    def fwd_fn(*diff_args):
        local = {s: list(v) for s, v in ins.items()}
        for (slot, i), a in zip(diff_index, diff_args):
            local[slot][i] = a
        # Output pytree: dict slot -> list of arrays.
        return normalize_outputs(fwd_def, fwd_def.lower(ctx, local, attrs))

    # memory_optimize: recompute this op's forward inside the backward
    # (jax.checkpoint) instead of letting XLA CSE share stored activations
    # with the forward pass — FLOPs for peak HBM.
    program = ctx.op.block.program
    if getattr(program, "_remat", False) or _flag_remat():
        skip = getattr(program, "_remat_skip", ())
        # skip_opt_set holds forward var names; they appear among the grad
        # op's inputs (forward ins/outs are replayed into it).
        if not (skip and set(ctx.op.input_arg_names()) & set(skip)):
            fwd_fn = jax.checkpoint(fwd_fn)

    primals = tuple(ins[slot][i] for slot, i in diff_index)
    out_tree, vjp_fn = jax.vjp(fwd_fn, *primals)

    def _zero_cot(ref):
        # Composite refs (tensor arrays): zero cotangent per leaf.
        def per_leaf(r):
            rd = jnp.result_type(r)
            if jnp.issubdtype(rd, jnp.inexact):
                return jnp.zeros(jnp.shape(r), rd)
            return np.zeros(jnp.shape(r), jax.dtypes.float0)

        return jax.tree.map(per_leaf, ref)

    # Cotangent pytree mirroring out_tree's structure.
    cot = {}
    for oslot, refs in out_tree.items():
        gs = out_grads.get(oslot, [])
        slot_cot = []
        for j, ref in enumerate(refs):
            if isinstance(ref, (tuple, list)):
                # composite (tensor-array) output: zero cotangent per
                # leaf — result_type would silently promote the tuple
                slot_cot.append(_zero_cot(ref))
                continue
            try:
                rdtype = jnp.result_type(ref)
            except TypeError:
                slot_cot.append(_zero_cot(ref))
                continue
            if not jnp.issubdtype(rdtype, jnp.inexact):
                slot_cot.append(np.zeros(jnp.shape(ref), jax.dtypes.float0))
                continue
            g = gs[j] if j < len(gs) else None
            if g is None:
                g = jnp.zeros(jnp.shape(ref), rdtype)
            else:
                g = jnp.asarray(g, rdtype)
                if jnp.shape(g) != jnp.shape(ref):
                    g = jnp.reshape(g, jnp.shape(ref))
            slot_cot.append(g)
        cot[oslot] = slot_cot
    grads = vjp_fn(cot)

    result = {}
    for (slot, i), g in zip(diff_index, grads):
        result.setdefault(slot, {})[i] = g
    out = {}
    for slot, arrs in ins.items():
        if slot in result:
            out[slot] = [result[slot].get(i) for i in range(len(arrs))]
    return out


def ensure_auto_grad_op(fwd_type):
    """Register (once) the synthesized ``<type>_grad`` operator whose
    lowering differentiates the forward rule. GradOpDescMaker analog."""
    gtype = fwd_type + "_grad"
    if gtype in _REGISTRY:
        return _REGISTRY[gtype]
    fwd = get_op_def(fwd_type)
    if fwd.grad is None:
        raise ValueError("op %r has no gradient" % fwd_type)

    g_inputs = list(fwd.inputs)
    for s in fwd.outputs:
        g_inputs.append(s)
        star = "*" if s.startswith("*") else ""
        g_inputs.append(star + s.lstrip("*") + "@GRAD")
    g_outputs = [
        ("*" if s.startswith("*") else "") + s.lstrip("*") + "@GRAD"
        for s in fwd.inputs
    ]

    def lower(ctx, ins, attrs):
        op = ctx.op
        fwd_ins = {s: ins[s] for s in fwd.input_slots() if s in ins}
        out_grads = {
            o: ins[o + "@GRAD"]
            for o in fwd.output_slots()
            if (o + "@GRAD") in ins
        }
        wanted = {}
        for s in fwd.input_slots():
            names = op.output(s + "@GRAD")
            if any(names):
                wanted[s] = [bool(n) for n in names]
        rule = fwd.lower_grad or lower_grad_via_vjp
        gres = rule(fwd, ctx, fwd_ins, attrs, out_grads, wanted)
        return {s + "@GRAD": gs for s, gs in gres.items()}

    return register_op(
        gtype, inputs=g_inputs, outputs=g_outputs, lower=lower, grad=None
    )


def assert_dtype(x, dtype):
    return jnp.asarray(x, canonical_dtype(dtype))


def _flag_remat():
    try:
        from paddle_tpu import flags

        return flags.get("remat_gradients")
    except Exception:
        return False
