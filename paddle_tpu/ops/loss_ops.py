"""Loss ops: cross_entropy, softmax_with_cross_entropy, regression losses.

Reference parity: paddle/fluid/operators/{cross_entropy,softmax_with_cross_
entropy,sigmoid_cross_entropy_with_logits,smooth_l1_loss,squared_l2_distance,
huber_loss,hinge_loss,log_loss,rank_loss,margin_rank_loss}_op.cc
"""

import jax
import jax.numpy as jnp

from paddle_tpu.core.op_registry import register_op


def _label_to_int(label):
    if jnp.ndim(label) > 1 and jnp.shape(label)[-1] == 1:
        label = jnp.squeeze(label, -1)
    return label.astype(jnp.int32)


def _pick_label(x, lbl):
    """``x[..., lbl]`` as ``[..., 1]`` float32, by a masked sum over the
    class axis and not a gather. XLA fuses no gather's operand: under
    ``take_along_axis`` the whole ``[tokens, classes]`` operand is stored
    first (in float32, behind an upcast), where the mask and the sum fuse
    into whichever pass already reads ``x``. A sum of one value and zeros
    is that value, and its gradient is the one-hot scatter the gather's
    gradient is. A label outside ``[0, classes)`` matches no class: 0."""
    classes = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    picked = jnp.where(classes == lbl[..., None], x.astype(jnp.float32), 0.0)
    return jnp.sum(picked, axis=-1, keepdims=True)


def _lower_softmax_xent(ctx, ins, attrs):
    """Hard labels pick their log-probability with ``_pick_label``. A
    label outside ``[0, classes)`` (the default ``ignore_index=-100`` fed
    as a label) gives a loss and a gradient of zero, the reference's
    meaning of an ignored label (a gather would wrap round to another
    class); ``ignore_index >= 0`` names one class to ignore besides."""
    logits, label = ins["Logits"][0], ins["Label"][0]
    lse = jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    log_softmax = logits - lse
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * log_softmax, axis=-1, keepdims=True)
    else:
        lbl = _label_to_int(label)
        nll = -_pick_label(log_softmax, lbl).astype(log_softmax.dtype)
        ignore = attrs.get("ignore_index", -100)
        if ignore >= 0:
            nll = jnp.where((lbl == ignore)[..., None], jnp.zeros_like(nll), nll)
        loss = nll
    return {"Softmax": jnp.exp(log_softmax), "Loss": loss}


register_op(
    "softmax_with_cross_entropy",
    inputs=["Logits", "Label"],
    outputs=["Softmax", "Loss"],
    attrs={"soft_label": False, "ignore_index": -100, "numeric_stable_mode": True},
    lower=_lower_softmax_xent,
    no_grad_inputs=("Label",),
    intermediate_outputs=("Softmax",),
)


def _lower_fused_label_smooth_ce(ctx, ins, attrs):
    """Label-smoothed cross entropy over the vocab dim, the Transformer's
    loss head (models/transformer.py). The logits stay in their network
    dtype (bf16 under AMP: the op is on neither AMP list) and the loss is
    the factored identity

        L = lse - (1-eps) * x_y - (eps/V) * sum_i x_i

    so the smoothing term needs only sum(x) — no second log-softmax
    pass — with every reduction in f32 and x_y a masked sum
    (``_pick_label``). The hand-written backward is the single expression

        dL/dx_i = (softmax_i - eps/V - (1-eps) * 1[i=y]) * g

    (exact: d lse = softmax, d x_y = onehot, d sum = 1), which XLA fuses
    into the operand of the two products that consume it.

    Measured on a v5e (PERF.md section 6, PR 48; `train_big_1chip`,
    16384 tokens x 32000 classes a step, three pairs): against the head
    composed of softmax_with_cross_entropy + log_softmax + scale/add, both
    with the masked pick, the step is 264.40 ms against 270.19 (the
    composed head's d(logits) costs the two products 3.4 ms more and its
    row passes 2.4 ms); neither stores anything of the logits' size but
    the bf16 logits. With the gather the composed head stored two f32
    copies, 275.19 ms.

    Reference capability anchor: softmax_with_cross_entropy_op.cc +
    label_smooth_op.cc composed; the fusion itself is TPU-motivated.
    """
    logits, label = ins["Logits"][0], ins["Label"][0]
    eps = float(attrs.get("epsilon", 0.0))
    vocab = int(jnp.shape(logits)[-1])
    lbl = _label_to_int(label)

    def fwd(x, l):
        # float32 inside each pass whatever the logits' dtype (the cast
        # fuses into the reader): bfloat16 logits lose nothing here, and
        # dx is rounded once, at its store
        xf = x.astype(jnp.float32)
        m = jnp.max(xf, axis=-1, keepdims=True)
        s = jnp.sum(jnp.exp(xf - m), axis=-1, keepdims=True)
        lse = m + jnp.log(s)
        sumx = jnp.sum(xf, axis=-1, keepdims=True)
        loss = lse - (1.0 - eps) * _pick_label(x, l) - (eps / vocab) * sumx
        return loss, (x, l, m, s)

    def bwd(res, g):
        x, l, m, s = res
        softmax = jnp.exp(x.astype(jnp.float32) - m) / s
        onehot = jax.nn.one_hot(l, vocab, dtype=jnp.float32)
        dx = (softmax - eps / vocab - (1.0 - eps) * onehot) \
            * g.astype(jnp.float32)
        return (dx.astype(x.dtype), None)

    f = jax.custom_vjp(lambda x, l: fwd(x, l)[0])
    f.defvjp(fwd, bwd)
    return {"Loss": f(logits, lbl)}


register_op(
    "fused_label_smooth_ce",
    inputs=["Logits", "Label"],
    outputs=["Loss"],
    attrs={"epsilon": 0.0},
    lower=_lower_fused_label_smooth_ce,
    no_grad_inputs=("Label",),
)


def _lower_cross_entropy(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    eps = 1e-8
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(jnp.maximum(x, eps)), axis=-1, keepdims=True)
    else:
        lbl = _label_to_int(label)
        p = jnp.take_along_axis(x, lbl[..., None], axis=-1)
        loss = -jnp.log(jnp.maximum(p, eps))
    return loss


register_op(
    "cross_entropy",
    inputs=["X", "Label"],
    outputs=["Y"],
    attrs={"soft_label": False, "ignore_index": -100},
    lower=_lower_cross_entropy,
    no_grad_inputs=("Label",),
)

register_op(
    "sigmoid_cross_entropy_with_logits",
    inputs=["X", "Label"],
    outputs=["Out"],
    attrs={"ignore_index": -100},
    lower=lambda ctx, ins, attrs: jnp.maximum(ins["X"][0], 0.0)
    - ins["X"][0] * ins["Label"][0]
    + jnp.log1p(jnp.exp(-jnp.abs(ins["X"][0]))),
    no_grad_inputs=("Label",),
)

register_op(
    "bce_loss",
    inputs=["X", "Label"],
    outputs=["Out"],
    lower=lambda ctx, ins, attrs: -(
        ins["Label"][0] * jnp.log(jnp.maximum(ins["X"][0], 1e-12))
        + (1.0 - ins["Label"][0]) * jnp.log(jnp.maximum(1.0 - ins["X"][0], 1e-12))
    ),
    no_grad_inputs=("Label",),
)


def _lower_squared_l2_distance(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    d = x - y
    return {
        "sub_result": d,
        "Out": jnp.sum(jnp.square(d), axis=tuple(range(1, jnp.ndim(d))))[..., None],
    }


register_op(
    "squared_l2_distance",
    inputs=["X", "Y"],
    outputs=["sub_result", "Out"],
    lower=_lower_squared_l2_distance,
    intermediate_outputs=("sub_result",),
)

register_op(
    "squared_l2_norm",
    inputs=["X"],
    outputs=["Out"],
    lower=lambda ctx, ins, attrs: jnp.reshape(jnp.sum(jnp.square(ins["X"][0])), (1,)),
)


def _lower_smooth_l1(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    sigma = attrs.get("sigma", 1.0)
    sigma2 = sigma * sigma
    d = x - y
    if "InsideWeight" in ins:
        d = d * ins["InsideWeight"][0]
    abs_d = jnp.abs(d)
    loss = jnp.where(
        abs_d < 1.0 / sigma2, 0.5 * sigma2 * jnp.square(d), abs_d - 0.5 / sigma2
    )
    if "OutsideWeight" in ins:
        loss = loss * ins["OutsideWeight"][0]
    summed = jnp.sum(loss, axis=tuple(range(1, jnp.ndim(loss))))[..., None]
    return {"Diff": d, "Out": summed}


register_op(
    "smooth_l1_loss",
    inputs=["X", "Y", "InsideWeight", "OutsideWeight"],
    outputs=["Diff", "Out"],
    attrs={"sigma": 1.0},
    lower=_lower_smooth_l1,
    no_grad_inputs=("InsideWeight", "OutsideWeight"),
    intermediate_outputs=("Diff",),
)

register_op(
    "huber_loss",
    inputs=["X", "Y"],
    outputs=["Residual", "Out"],
    attrs={"delta": 1.0},
    lower=lambda ctx, ins, attrs: _huber(ins, attrs),
    intermediate_outputs=("Residual",),
)


def _huber(ins, attrs):
    d = ins["Y"][0] - ins["X"][0]
    delta = attrs.get("delta", 1.0)
    abs_d = jnp.abs(d)
    loss = jnp.where(
        abs_d <= delta, 0.5 * jnp.square(d), delta * (abs_d - 0.5 * delta)
    )
    return {"Residual": d, "Out": loss}


register_op(
    "log_loss",
    inputs=["Predicted", "Labels"],
    outputs=["Loss"],
    attrs={"epsilon": 1e-4},
    lower=lambda ctx, ins, attrs: -ins["Labels"][0]
    * jnp.log(ins["Predicted"][0] + attrs.get("epsilon", 1e-4))
    - (1.0 - ins["Labels"][0])
    * jnp.log(1.0 - ins["Predicted"][0] + attrs.get("epsilon", 1e-4)),
    no_grad_inputs=("Labels",),
)

register_op(
    "hinge_loss",
    inputs=["Logits", "Labels"],
    outputs=["Loss"],
    lower=lambda ctx, ins, attrs: jnp.maximum(
        0.0, 1.0 - (2.0 * ins["Labels"][0] - 1.0) * ins["Logits"][0]
    ),
    no_grad_inputs=("Labels",),
)

register_op(
    "rank_loss",
    inputs=["Label", "Left", "Right"],
    outputs=["Out"],
    lower=lambda ctx, ins, attrs: jnp.log1p(
        jnp.exp(ins["Left"][0] - ins["Right"][0])
    )
    - ins["Label"][0] * (ins["Left"][0] - ins["Right"][0]),
    no_grad_inputs=("Label",),
)

register_op(
    "margin_rank_loss",
    inputs=["Label", "X1", "X2"],
    outputs=["Activated", "Out"],
    attrs={"margin": 0.0},
    lower=lambda ctx, ins, attrs: _margin_rank(ins, attrs),
    no_grad_inputs=("Label",),
    intermediate_outputs=("Activated",),
)


def _margin_rank(ins, attrs):
    label, x1, x2 = ins["Label"][0], ins["X1"][0], ins["X2"][0]
    out = jnp.maximum(0.0, -label * (x1 - x2) + attrs.get("margin", 0.0))
    return {"Activated": (out > 0).astype(x1.dtype), "Out": out}


register_op(
    "kldiv_loss",
    inputs=["X", "Target"],
    outputs=["Loss"],
    attrs={"reduction": "mean"},
    lower=lambda ctx, ins, attrs: _kldiv(ins, attrs),
    no_grad_inputs=("Target",),
)


def _kldiv(ins, attrs):
    x, t = ins["X"][0], ins["Target"][0]
    loss = t * (jnp.log(jnp.maximum(t, 1e-12)) - x)
    red = attrs.get("reduction", "mean")
    if red == "mean":
        return jnp.reshape(jnp.mean(loss), (1,))
    if red == "sum":
        return jnp.reshape(jnp.sum(loss), (1,))
    if red == "batchmean":
        return jnp.reshape(jnp.sum(loss) / jnp.shape(x)[0], (1,))
    return loss


def _lower_modified_huber_loss(ctx, ins, attrs):
    """modified_huber_loss_op.cc: binary classification loss on labels
    {0,1} mapped to {-1,+1}. With z = (2y-1)*x: quadratic max(0, 1-z)^2
    for z >= -1, linear -4z beyond (outlier robustness)."""
    x = jnp.reshape(ins["X"][0], (-1,))
    y = jnp.reshape(ins["Y"][0], (-1,)).astype(x.dtype)
    z = (2.0 * y - 1.0) * x
    loss = jnp.where(
        z >= -1.0, jnp.square(jnp.maximum(1.0 - z, 0.0)), -4.0 * z
    )
    shape = (x.shape[0], 1)
    return {
        "Out": jnp.reshape(loss, shape),
        "IntermediateVal": jnp.reshape(z, shape),
    }


register_op(
    "modified_huber_loss",
    inputs=["X", "Y"],
    outputs=["Out", "IntermediateVal"],
    lower=_lower_modified_huber_loss,
    no_grad_inputs=("Y",),
    intermediate_outputs=("IntermediateVal",),
)
