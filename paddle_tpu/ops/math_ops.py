"""Dense math ops: mul/matmul/elementwise/reduce/scale/sum/...

Reference parity: paddle/fluid/operators/{mul,matmul,elementwise_*,reduce_*,
scale,sum,clip,cumsum,...}_op.cc — each lowered to XLA instead of
cuBLAS/Eigen kernels. Matmuls run in the input dtype (bf16 stays bf16 on
the MXU with float32 accumulation via XLA's default precision).
"""

import jax
import jax.numpy as jnp

from paddle_tpu.core.op_registry import lower_grad_via_vjp, register_op
from paddle_tpu.ops.common import broadcast_y, flatten_to_2d, reduce_axes, to_dtype


def _lower_mul(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    xn = attrs.get("x_num_col_dims", 1)
    yn = attrs.get("y_num_col_dims", 1)
    x2 = flatten_to_2d(x, xn)
    y2 = flatten_to_2d(y, yn)
    out = x2 @ y2
    out_shape = tuple(jnp.shape(x)[:xn]) + tuple(jnp.shape(y)[yn:])
    return jnp.reshape(out, out_shape)


def _lone_weight_grad(x2, dout2):
    """``x2^T dout2`` (``[K, N]``) for an ``x2`` WIDER than ``dout2``, as a
    product of its own on a STORED ``dout2``; the optimizer's update then
    runs alone behind it.

    Fused with its Adam update and with whatever makes its operands, this
    gradient was the slowest product of the trainer's step on the v5e: 2.35
    ms where the same product alone on plain operands takes 0.79, because
    its operand fusion rebuilt ``dout2`` (a dropout's regenerated bits and a
    select over the float32 residual gradient) for every window of the
    ``[K, N]`` result. Two barriers hold the form; both were read in the
    step compiled for a described v5e and timed on the chip (PERF.md section
    6, PR 54):

    - on ``dout2``: the narrow operand is stored once (``rows x N``, the
      smaller of the two) and the product reads it plain: 0.73 ms a product
      against 1.49 with the rebuild in its operand fusion;
    - on the product: it keeps the update out of the product's fusion, so
      the window is chosen for the product; with the update fused behind
      it eight of twelve read 1.07 ms, and the step 4.4 ms more.

    The operands keep the order ``jax.vjp`` gives them. The other order
    (``dout2^T x2``, turned afterwards) is the same product at the same
    rate and needs a relayout and a third barrier to keep the update in the
    parameter's layout: 5.7 ms a step slower in all."""
    dout2 = jax.lax.optimization_barrier(dout2)
    return jax.lax.optimization_barrier(
        jax.lax.dot_general(x2, dout2, (((0,), (0,)), ((), ()))))


def _lower_mul_grad(fwd_def, ctx, ins, attrs, out_grads, wanted):
    """``mul_grad``: what ``jax.vjp`` of ``_lower_mul`` gives, except
    ``Y@GRAD`` where the flattened ``X`` has more columns than ``Out`` (a
    feed-forward's second product): that one is ``_lone_weight_grad``. The
    rule sees the two column counts and nothing else; equal widths and a
    narrower ``X`` keep the vjp's form, and so does ``X@GRAD`` always."""
    x, y = ins["X"][0], ins["Y"][0]
    dout = (out_grads.get("Out") or [None])[0]
    x2 = flatten_to_2d(x, attrs.get("x_num_col_dims", 1))
    y2 = flatten_to_2d(y, attrs.get("y_num_col_dims", 1))
    alone = (
        dout is not None
        and (wanted.get("Y") or [False])[0]
        and jnp.issubdtype(jnp.result_type(y), jnp.inexact)
        and x2.shape[1] > y2.shape[1]
    )
    if not alone:
        return lower_grad_via_vjp(fwd_def, ctx, ins, attrs, out_grads, wanted)
    rest = {slot: w for slot, w in wanted.items() if slot != "Y"}
    grads = lower_grad_via_vjp(fwd_def, ctx, ins, attrs, out_grads, rest)
    dout2 = jnp.reshape(jnp.asarray(dout, jnp.result_type(x, y)),
                        (x2.shape[0], y2.shape[1]))
    dy = _lone_weight_grad(x2, dout2).astype(jnp.result_type(y))
    grads["Y"] = [jnp.reshape(dy, jnp.shape(y))]
    return grads


register_op(
    "mul",
    inputs=["X", "Y"],
    outputs=["Out"],
    attrs={"x_num_col_dims": 1, "y_num_col_dims": 1},
    lower=_lower_mul,
    lower_grad=_lower_mul_grad,
)


def _lower_matmul(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    if attrs.get("transpose_X", False):
        x = jnp.swapaxes(x, -1, -2) if jnp.ndim(x) > 1 else x
    if attrs.get("transpose_Y", False):
        y = jnp.swapaxes(y, -1, -2) if jnp.ndim(y) > 1 else y
    out = jnp.matmul(x, y)
    alpha = attrs.get("alpha", 1.0)
    if alpha != 1.0:
        out = out * jnp.asarray(alpha, out.dtype)
    return out


register_op(
    "matmul",
    inputs=["X", "Y"],
    outputs=["Out"],
    attrs={"transpose_X": False, "transpose_Y": False, "alpha": 1.0},
    lower=_lower_matmul,
)


def _elementwise(fn):
    def lower(ctx, ins, attrs):
        x, y = ins["X"][0], ins["Y"][0]
        y = broadcast_y(x, y, attrs.get("axis", -1))
        return fn(x, y)

    return lower


for _name, _fn in [
    ("elementwise_add", jnp.add),
    ("elementwise_sub", jnp.subtract),
    ("elementwise_mul", jnp.multiply),
    ("elementwise_div", jnp.divide),
    ("elementwise_max", jnp.maximum),
    ("elementwise_min", jnp.minimum),
    ("elementwise_pow", jnp.power),
    ("elementwise_mod", jnp.mod),
    ("elementwise_floordiv", jnp.floor_divide),
]:
    register_op(
        _name,
        inputs=["X", "Y"],
        outputs=["Out"],
        attrs={"axis": -1},
        lower=_elementwise(_fn),
    )


register_op(
    "sum",
    inputs=["*X"],
    outputs=["Out"],
    lower=lambda ctx, ins, attrs: sum(ins["X"][1:], ins["X"][0]),
)

register_op(
    "scale",
    inputs=["X"],
    outputs=["Out"],
    attrs={"scale": 1.0, "bias": 0.0, "bias_after_scale": True},
    lower=lambda ctx, ins, attrs: (
        ins["X"][0] * jnp.asarray(attrs.get("scale", 1.0), ins["X"][0].dtype)
        + jnp.asarray(attrs.get("bias", 0.0), ins["X"][0].dtype)
        if attrs.get("bias_after_scale", True)
        else (ins["X"][0] + jnp.asarray(attrs.get("bias", 0.0), ins["X"][0].dtype))
        * jnp.asarray(attrs.get("scale", 1.0), ins["X"][0].dtype)
    ),
)

register_op(
    "mean",
    inputs=["X"],
    outputs=["Out"],
    lower=lambda ctx, ins, attrs: jnp.reshape(jnp.mean(ins["X"][0]), (1,)),
)


def _reduce(fn):
    def lower(ctx, ins, attrs):
        x = ins["X"][0]
        axes = reduce_axes(
            jnp.ndim(x), attrs.get("dim", [0]), attrs.get("reduce_all", False)
        )
        out = fn(x, axis=axes, keepdims=attrs.get("keep_dim", False))
        if jnp.ndim(out) == 0:
            out = jnp.reshape(out, (1,))
        return out

    return lower


for _name, _fn in [
    ("reduce_sum", jnp.sum),
    ("reduce_mean", jnp.mean),
    ("reduce_max", jnp.max),
    ("reduce_min", jnp.min),
    ("reduce_prod", jnp.prod),
]:
    register_op(
        _name,
        inputs=["X"],
        outputs=["Out"],
        attrs={"dim": [0], "keep_dim": False, "reduce_all": False},
        lower=_reduce(_fn),
    )

register_op(
    "clip",
    inputs=["X"],
    outputs=["Out"],
    attrs={"min": 0.0, "max": 0.0},
    lower=lambda ctx, ins, attrs: jnp.clip(
        ins["X"][0],
        jnp.asarray(attrs["min"], ins["X"][0].dtype),
        jnp.asarray(attrs["max"], ins["X"][0].dtype),
    ),
)

register_op(
    "clip_by_norm",
    inputs=["X"],
    outputs=["Out"],
    attrs={"max_norm": 1.0},
    lower=lambda ctx, ins, attrs: _clip_by_norm(ins["X"][0], attrs["max_norm"]),
)


def _clip_by_norm(x, max_norm):
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    max_norm = jnp.asarray(max_norm, x.dtype)
    return jnp.where(norm > max_norm, x * (max_norm / norm), x)


register_op(
    "cumsum",
    inputs=["X"],
    outputs=["Out"],
    attrs={"axis": -1, "exclusive": False, "reverse": False},
    lower=lambda ctx, ins, attrs: _cumsum(ins["X"][0], attrs),
)


def _cumsum(x, attrs):
    axis = attrs.get("axis", -1)
    if attrs.get("reverse", False):
        x = jnp.flip(x, axis)
    out = jnp.cumsum(x, axis=axis)
    if attrs.get("exclusive", False):
        out = out - x
    if attrs.get("reverse", False):
        out = jnp.flip(out, axis)
    return out


register_op(
    "l2_normalize",
    inputs=["X"],
    outputs=["Out", "Norm"],
    attrs={"axis": -1, "epsilon": 1e-10},
    lower=lambda ctx, ins, attrs: _l2_normalize(ins["X"][0], attrs),
    intermediate_outputs=("Norm",),
)


def _l2_normalize(x, attrs):
    axis = attrs.get("axis", -1)
    eps = jnp.asarray(attrs.get("epsilon", 1e-10), x.dtype)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    return x / norm, norm


register_op(
    "norm",
    inputs=["X"],
    outputs=["Out", "Norm"],
    attrs={"axis": 1, "epsilon": 1e-10},
    lower=lambda ctx, ins, attrs: _l2_normalize(ins["X"][0], attrs),
    intermediate_outputs=("Norm",),
)


def _lower_isfinite(ctx, ins, attrs):
    flat = [jnp.all(jnp.isfinite(x)) for x in ins["X"]]
    return jnp.reshape(jnp.stack(flat).all(), (1,))


register_op("isfinite", inputs=["*X"], outputs=["Out"], lower=_lower_isfinite, grad=None)

register_op(
    "isinf",
    inputs=["X"],
    outputs=["Out"],
    lower=lambda ctx, ins, attrs: jnp.reshape(
        jnp.any(jnp.isinf(ins["X"][0])), (1,)
    ),
    grad=None,
)

register_op(
    "isnan",
    inputs=["X"],
    outputs=["Out"],
    lower=lambda ctx, ins, attrs: jnp.reshape(
        jnp.any(jnp.isnan(ins["X"][0])), (1,)
    ),
    grad=None,
)


def _lower_cos_sim(ctx, ins, attrs):
    # cos_sim_op.cc: per-sample cosine similarity with all trailing dims
    # flattened (rows are dim 0); Y may have a single row (broadcast against
    # every row of X). Output is [N, 1].
    x = ins["X"][0]
    y = ins["Y"][0]
    x = jnp.reshape(x, (jnp.shape(x)[0], -1))
    y = jnp.reshape(y, (jnp.shape(y)[0], -1))
    xn = jnp.sqrt(jnp.sum(jnp.square(x), axis=1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(jnp.square(y), axis=1, keepdims=True))
    dot = jnp.sum(x * y, axis=1, keepdims=True)
    out = dot / jnp.maximum(xn * yn, 1e-12)
    return {"Out": out, "XNorm": xn, "YNorm": yn}


register_op(
    "cos_sim",
    inputs=["X", "Y"],
    outputs=["Out", "XNorm", "YNorm"],
    lower=_lower_cos_sim,
    intermediate_outputs=("XNorm", "YNorm"),
)


def _lower_minus(ctx, ins, attrs):
    """minus_op.cc: Out = X - Y (kept as its own schema; the v2 layer
    surface exposes it separately from elementwise_sub)."""
    return ins["X"][0] - ins["Y"][0]


register_op(
    "minus",
    inputs=["X", "Y"],
    outputs=["Out"],
    lower=_lower_minus,
)


def _lower_l1_norm(ctx, ins, attrs):
    """l1_norm_op.cc: scalar sum of absolute values."""
    return jnp.reshape(jnp.sum(jnp.abs(ins["X"][0])), (1,))


register_op(
    "l1_norm",
    inputs=["X"],
    outputs=["Out"],
    lower=_lower_l1_norm,
)


def _lower_multiplex(ctx, ins, attrs):
    """multiplex_op.cc: per-row select among the candidate tensors —
    Out[b] = X[Ids[b]][b]. Lowering: stack candidates on a new axis and
    take_along_axis with the row index (one fused gather on TPU)."""
    ids = jnp.reshape(ins["Ids"][0], (-1,)).astype(jnp.int32)
    xs = jnp.stack(ins["X"], axis=0)  # [K, B, ...]
    b = xs.shape[1]
    idx = jnp.reshape(ids, (1, b) + (1,) * (xs.ndim - 2))
    return jnp.squeeze(
        jnp.take_along_axis(xs, jnp.broadcast_to(idx, (1,) + xs.shape[1:]),
                            axis=0),
        axis=0,
    )


register_op(
    "multiplex",
    inputs=["Ids", "*X"],
    outputs=["Out"],
    lower=_lower_multiplex,
    no_grad_inputs=("Ids",),
)


def _lower_bilinear_tensor_product(ctx, ins, attrs):
    """bilinear_tensor_product_op.cc: Out[b,k] = X[b]^T W_k Y[b] (+bias);
    one einsum so XLA maps it onto batched MXU matmuls."""
    x = ins["X"][0]
    y = ins["Y"][0]
    w = ins["Weight"][0]  # [K, M, N]
    out = jnp.einsum("bm,kmn,bn->bk", x, w, y)
    if "Bias" in ins and ins["Bias"]:
        out = out + jnp.reshape(ins["Bias"][0], (1, -1))
    return out


register_op(
    "bilinear_tensor_product",
    inputs=["X", "Y", "Weight", "Bias"],
    outputs=["Out"],
    lower=_lower_bilinear_tensor_product,
)


def _lower_conv_shift(ctx, ins, attrs):
    """conv_shift_op.cc (NTM circular convolution): X [B,M], Y [B,N] with
    N odd; Out[b,i] = sum_j X[b, (i + j - (N-1)/2) mod M] * Y[b,j].
    Lowered as a static modular gather + one einsum (no scalar loops)."""
    x = ins["X"][0]
    y = ins["Y"][0]
    m = x.shape[1]
    n = y.shape[1]
    half = (n - 1) // 2
    idx = (jnp.arange(m)[:, None] + jnp.arange(n)[None, :] - half) % m
    # windows[b, i, j] = X[b, idx[i, j]]
    windows = x[:, idx]
    return jnp.einsum("bij,bj->bi", windows, y)


register_op(
    "conv_shift",
    inputs=["X", "Y"],
    outputs=["Out"],
    lower=_lower_conv_shift,
)
