"""NN structural ops: conv, pooling, normalization.

Reference parity: paddle/fluid/operators/{conv,conv_transpose,pool,
batch_norm,layer_norm,lrn,group_norm}_op.cc(+cudnn variants). On TPU these
lower to XLA convolution/reduce-window HLOs which tile onto the MXU; cuDNN
algorithm selection has no analog (XLA autotunes).
"""

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.op_registry import register_op

_CONV_DN = ("NCHW", "OIHW", "NCHW")


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v] * n


def _conv_nhwc():
    from paddle_tpu import flags

    return flags.get("conv_nhwc")


def _lower_conv2d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = _pair(attrs.get("strides", [1, 1]))
    paddings = _pair(attrs.get("paddings", [0, 0]))
    dilations = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1)
    if _conv_nhwc():
        # FLAGS_conv_nhwc layout experiment: run the conv in NHWC inside a
        # transpose sandwich. Between consecutive convs the out-transpose
        # and the next in-transpose cancel in XLA, so a conv-dominated
        # block effectively runs NHWC end to end while the Program stays
        # NCHW at every op boundary. Numerics unchanged; the per-hardware
        # win is for the bench to measure.
        out = jax.lax.conv_general_dilated(
            jnp.transpose(x, (0, 2, 3, 1)),
            w,
            window_strides=strides,
            padding=[(p, p) for p in paddings],
            rhs_dilation=dilations,
            dimension_numbers=("NHWC", "OIHW", "NHWC"),
            feature_group_count=groups,
        )
        return jnp.transpose(out, (0, 3, 1, 2))
    out = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=[(p, p) for p in paddings],
        rhs_dilation=dilations,
        dimension_numbers=_CONV_DN,
        feature_group_count=groups,
    )
    return out


register_op(
    "conv2d",
    inputs=["Input", "Filter"],
    outputs=["Output"],
    attrs={
        "strides": [1, 1],
        "paddings": [0, 0],
        "dilations": [1, 1],
        "groups": 1,
        "use_cudnn": False,
        "data_format": "NCHW",
    },
    lower=_lower_conv2d,
)


def _lower_depthwise_conv2d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    # Paddle depthwise: groups == in_channels, filter [C*mult, 1, kh, kw].
    a = dict(attrs)
    a["groups"] = jnp.shape(x)[1]
    return _lower_conv2d(ctx, ins, a)


register_op(
    "depthwise_conv2d",
    inputs=["Input", "Filter"],
    outputs=["Output"],
    attrs={
        "strides": [1, 1],
        "paddings": [0, 0],
        "dilations": [1, 1],
        "groups": 1,
        "data_format": "NCHW",
    },
    lower=_lower_depthwise_conv2d,
)


def _lower_depthwise_conv2d_transpose(ctx, ins, attrs):
    # depthwise transpose: groups == in_channels (filter [C, mult, kh, kw])
    a = dict(attrs)
    a["groups"] = jnp.shape(ins["Input"][0])[1]
    return _lower_conv2d_transpose(ctx, ins, a)


register_op(
    "depthwise_conv2d_transpose",
    inputs=["Input", "Filter"],
    outputs=["Output"],
    attrs={
        "strides": [1, 1],
        "paddings": [0, 0],
        "dilations": [1, 1],
        "groups": 1,
        "output_size": None,
        "data_format": "NCHW",
    },
    lower=_lower_depthwise_conv2d_transpose,
)


def _lower_conv3d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = _pair(attrs.get("strides", [1, 1, 1]), 3)
    paddings = _pair(attrs.get("paddings", [0, 0, 0]), 3)
    dilations = _pair(attrs.get("dilations", [1, 1, 1]), 3)
    return jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=[(p, p) for p in paddings],
        rhs_dilation=dilations,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        feature_group_count=attrs.get("groups", 1),
    )


register_op(
    "conv3d",
    inputs=["Input", "Filter"],
    outputs=["Output"],
    attrs={
        "strides": [1, 1, 1],
        "paddings": [0, 0, 0],
        "dilations": [1, 1, 1],
        "groups": 1,
    },
    lower=_lower_conv3d,
)


def _lower_conv2d_transpose(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = _pair(attrs.get("strides", [1, 1]))
    paddings = _pair(attrs.get("paddings", [0, 0]))
    dilations = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1)
    # Paddle filter layout for transpose conv: [in_c, out_c/groups, kh, kw].
    # Gradient-of-conv formulation: lhs-dilate input by stride.
    kh = (jnp.shape(w)[2] - 1) * dilations[0] + 1
    kw = (jnp.shape(w)[3] - 1) * dilations[1] + 1
    pad_h = kh - 1 - paddings[0]
    pad_w = kw - 1 - paddings[1]
    # output_size picks among the stride ambiguous output shapes: the
    # shortfall vs the default arithmetic becomes extra high-side padding
    extra = _transpose_extra_pad(
        attrs.get("output_size"), [jnp.shape(x)[2], jnp.shape(x)[3]],
        strides, paddings, [kh, kw],
    )
    return jax.lax.conv_general_dilated(
        x,
        _transpose_weight(w, groups, 2),
        window_strides=(1, 1),
        padding=[(pad_h, pad_h + extra[0]), (pad_w, pad_w + extra[1])],
        lhs_dilation=strides,
        rhs_dilation=dilations,
        dimension_numbers=_CONV_DN,
        feature_group_count=groups,
    )


def _transpose_weight(w, groups, nd):
    """Paddle transpose-conv filter [in_c, out_c/groups, *k] -> the
    [out_c, in_c/groups, *k] layout of the gradient-of-conv formulation:
    spatial flip + (per-group) in/out channel transpose."""
    spatial = tuple(range(2, 2 + nd))
    w_flip = jnp.flip(w, axis=spatial)
    if groups == 1:
        return jnp.swapaxes(w_flip, 0, 1)
    ic, ocg = jnp.shape(w)[0], jnp.shape(w)[1]
    wg = jnp.reshape(w_flip, (groups, ic // groups, ocg) + tuple(jnp.shape(w)[2:]))
    wg = jnp.swapaxes(wg, 1, 2)
    return jnp.reshape(wg, (groups * ocg, ic // groups) + tuple(jnp.shape(w)[2:]))


def _transpose_extra_pad(output_size, in_spatial, strides, paddings, keff):
    """conv_transpose_op.cc InferShape: output_size selects an output among
    the stride-ambiguous candidates; here the surplus over the minimal
    arithmetic becomes high-side padding (must satisfy 0 <= surplus <
    stride, as in the reference's shape check)."""
    nd = len(in_spatial)
    if not output_size:
        return [0] * nd
    extras = []
    for d in range(nd):
        base = (int(in_spatial[d]) - 1) * strides[d] - 2 * paddings[d] + keff[d]
        surplus = int(output_size[d]) - base
        if not 0 <= surplus < strides[d]:
            raise ValueError(
                "conv_transpose: output_size %d for dim %d not reachable "
                "(base %d, stride %d)" % (output_size[d], d, base, strides[d]))
        extras.append(surplus)
    return extras


register_op(
    "conv2d_transpose",
    inputs=["Input", "Filter"],
    outputs=["Output"],
    attrs={
        "strides": [1, 1],
        "paddings": [0, 0],
        "dilations": [1, 1],
        "groups": 1,
        "output_size": [],
    },
    lower=_lower_conv2d_transpose,
)


def _pool_geometry(x, attrs, nd):
    """Shared N-spatial-dim pooling geometry: (ksize, strides, window,
    full strides, pads) honoring ceil_mode's extra high-side padding."""
    ksize = _pair(attrs.get("ksize", [2] * nd), nd)
    strides = _pair(attrs.get("strides", [1] * nd), nd)
    paddings = _pair(attrs.get("paddings", [0] * nd), nd)
    pads = [(0, 0), (0, 0)]
    if attrs.get("ceil_mode", False):
        # pad extra on the high side so ceil-division window count fits
        for i in range(nd):
            size = int(jnp.shape(x)[2 + i])
            k, s, p = ksize[i], strides[i], paddings[i]
            out_ceil = -(-(size + 2 * p - k) // s) + 1
            # Caffe/reference rule: the last window must START inside
            # input+low-pad; without this clamp a window lying entirely
            # in high-side padding poisons max pooling with the -inf
            # init (and exclusive-avg with 0/0). The C++ interpreter's
            # PoolOutDim mirrors this exactly.
            if (out_ceil - 1) * s >= size + p:
                out_ceil -= 1
            needed = (out_ceil - 1) * s + k - (size + 2 * p)
            pads.append((p, p + max(0, int(needed))))
    else:
        pads += [(p, p) for p in paddings]
    return ksize, strides, (1, 1) + tuple(ksize), (1, 1) + tuple(strides), pads


def _pool_max_or_global(x, attrs, nd):
    """Global and max pooling, any rank; returns None for windowed avg
    (the 2d/3d cores differ only in their avg strategy)."""
    ptype = attrs.get("pooling_type", "max")
    if attrs.get("global_pooling", False):
        axis = tuple(range(2, 2 + nd))
        if ptype == "max":
            return jnp.max(x, axis=axis, keepdims=True)
        return jnp.mean(x, axis=axis, keepdims=True)
    if ptype == "max":
        _, _, window, strides_full, pads = _pool_geometry(x, attrs, nd)
        # init must be a static python scalar for JAX to recognize the max
        # monoid and use the differentiable reduce_window_max primitive.
        if jnp.issubdtype(x.dtype, jnp.floating):
            init = -np.inf
        else:
            init = int(jnp.iinfo(x.dtype).min)
        return jax.lax.reduce_window(
            x, init, jax.lax.max, window, strides_full, pads
        )
    return None


def _pool2d_core(x, attrs):
    out = _pool_max_or_global(x, attrs, 2)
    if out is not None:
        return out
    # avg pooling via depthwise conv with a ones kernel (differentiable,
    # MXU-tiled); exclusive=True divides by the unpadded window size.
    ksize, strides, _, _, pads = _pool_geometry(x, attrs, 2)
    c = jnp.shape(x)[1]
    kern = jnp.ones((c, 1) + tuple(ksize), x.dtype)
    spatial_pads = pads[2:]

    def _sum_pool(v):
        return jax.lax.conv_general_dilated(
            v,
            kern,
            window_strides=strides,
            padding=spatial_pads,
            dimension_numbers=_CONV_DN,
            feature_group_count=c,
        )

    summed = _sum_pool(x)
    if attrs.get("exclusive", True):
        counts = _sum_pool(jnp.ones_like(x))
    else:
        counts = jnp.asarray(float(np.prod(ksize)), x.dtype)
    return summed / counts


register_op(
    "pool2d",
    inputs=["X"],
    outputs=["Out"],
    attrs={
        "pooling_type": "max",
        "ksize": [2, 2],
        "strides": [1, 1],
        "paddings": [0, 0],
        "global_pooling": False,
        "exclusive": True,
        "ceil_mode": False,
        "adaptive": False,
        "use_cudnn": False,
    },
    lower=lambda ctx, ins, attrs: _pool2d_core(ins["X"][0], attrs),
)


def _pool3d_core(x, attrs):
    """NCDHW pooling (pool_op.cc pool3d registration): same windowing rules
    as pool2d with three spatial dims; avg uses reduce_window so the kernel
    does not blow up into a depthwise conv over D*H*W."""
    out = _pool_max_or_global(x, attrs, 3)
    if out is not None:
        return out
    ksize, _, window, strides5, pads = _pool_geometry(x, attrs, 3)
    summed = jax.lax.reduce_window(
        x, 0.0, jax.lax.add, window, strides5, pads
    )
    if attrs.get("exclusive", True):
        counts = jax.lax.reduce_window(
            jnp.ones_like(x), 0.0, jax.lax.add, window, strides5, pads
        )
    else:
        counts = jnp.asarray(float(np.prod(ksize)), x.dtype)
    return summed / counts


register_op(
    "pool3d",
    inputs=["X"],
    outputs=["Out"],
    attrs={
        "pooling_type": "max",
        "ksize": [2, 2, 2],
        "strides": [1, 1, 1],
        "paddings": [0, 0, 0],
        "global_pooling": False,
        "exclusive": True,
        "ceil_mode": False,
        "adaptive": False,
        "use_cudnn": False,
    },
    lower=lambda ctx, ins, attrs: _pool3d_core(ins["X"][0], attrs),
)


def _lower_batch_norm(ctx, ins, attrs):
    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean_in, var_in = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    layout = attrs.get("data_layout", "NCHW")
    is_test = ctx.is_test or attrs.get("is_test", False)
    ch_axis = 1 if layout == "NCHW" else jnp.ndim(x) - 1
    reduce_ax = tuple(i for i in range(jnp.ndim(x)) if i != ch_axis)
    bshape = tuple(
        jnp.shape(x)[ch_axis] if i == ch_axis else 1 for i in range(jnp.ndim(x))
    )

    if is_test or attrs.get("use_global_stats", False):
        mean, var = mean_in, var_in
        saved_mean, saved_var = mean_in, var_in
        mean_out, var_out = mean_in, var_in
    else:
        cdtype = jnp.float32 if x.dtype != jnp.float64 else jnp.float64
        xc = x.astype(cdtype)
        mean = jnp.mean(xc, axis=reduce_ax)
        var = jnp.mean(jnp.square(xc), axis=reduce_ax) - jnp.square(mean)
        mean_out = mean_in * momentum + mean.astype(mean_in.dtype) * (1 - momentum)
        var_out = var_in * momentum + var.astype(var_in.dtype) * (1 - momentum)
        saved_mean, saved_var = mean, var
    inv_std = jax.lax.rsqrt(var.astype(x.dtype) + jnp.asarray(eps, x.dtype))
    y = (x - jnp.reshape(mean.astype(x.dtype), bshape)) * jnp.reshape(
        inv_std * scale, bshape
    ) + jnp.reshape(bias, bshape)
    # Under AMP, scale/bias stay f32 and the arithmetic above promotes; keep
    # activations in the network's compute dtype (bf16) for HBM bandwidth.
    y = y.astype(x.dtype)
    return {
        "Y": y,
        "MeanOut": mean_out,
        "VarianceOut": var_out,
        "SavedMean": saved_mean,
        "SavedVariance": saved_var,
    }


register_op(
    "batch_norm",
    inputs=["X", "Scale", "Bias", "Mean", "Variance"],
    outputs=["Y", "MeanOut", "VarianceOut", "SavedMean", "SavedVariance"],
    attrs={
        "epsilon": 1e-5,
        "momentum": 0.9,
        "is_test": False,
        "data_layout": "NCHW",
        "use_global_stats": False,
    },
    lower=_lower_batch_norm,
    no_grad_inputs=("Mean", "Variance"),
    intermediate_outputs=("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"),
)


def _lower_layer_norm(ctx, ins, attrs):
    x = ins["X"][0]
    begin = attrs.get("begin_norm_axis", 1)
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(begin, jnp.ndim(x)))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
    inv = jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype))
    y = (x - mean) * inv
    norm_shape = tuple(jnp.shape(x)[begin:])
    if "Scale" in ins and ins["Scale"]:
        y = y * jnp.reshape(ins["Scale"][0], norm_shape)
    if "Bias" in ins and ins["Bias"]:
        y = y + jnp.reshape(ins["Bias"][0], norm_shape)
    lead = tuple(jnp.shape(x)[:begin])
    return {
        "Y": y,
        "Mean": jnp.reshape(mean, lead),
        "Variance": jnp.reshape(var, lead),
    }


register_op(
    "layer_norm",
    inputs=["X", "Scale", "Bias"],
    outputs=["Y", "Mean", "Variance"],
    attrs={"epsilon": 1e-5, "begin_norm_axis": 1},
    lower=_lower_layer_norm,
    intermediate_outputs=("Mean", "Variance"),
)


def _lower_lrn(ctx, ins, attrs):
    x = ins["X"][0]
    n = attrs.get("n", 5)
    k = attrs.get("k", 2.0)
    alpha = attrs.get("alpha", 1e-4)
    beta = attrs.get("beta", 0.75)
    sq = jnp.square(x)
    # reference lrn_op.cc window: start = -(n-1)/2, i.e. offsets
    # [-(n-1)//2, n-1-(n-1)//2] — biased toward HIGHER channels for
    # even n (ADVICE r4: n//2 biased low; odd n, incl. the default 5,
    # is unaffected). native/src/interp.h mirrors this exactly.
    lo = (n - 1) // 2
    pad = jnp.pad(sq, [(0, 0), (lo, n - 1 - lo), (0, 0), (0, 0)])
    acc = sum(
        pad[:, i : i + jnp.shape(x)[1]] for i in range(n)
    )
    mid = k + alpha * acc
    return {"Out": x / jnp.power(mid, beta), "MidOut": mid}


register_op(
    "lrn",
    inputs=["X"],
    outputs=["Out", "MidOut"],
    attrs={"n": 5, "k": 2.0, "alpha": 1e-4, "beta": 0.75},
    lower=_lower_lrn,
    intermediate_outputs=("MidOut",),
)


def _lower_group_norm(ctx, ins, attrs):
    x = ins["X"][0]
    groups = attrs.get("groups", 1)
    eps = attrs.get("epsilon", 1e-5)
    n, c = jnp.shape(x)[0], jnp.shape(x)[1]
    rest = tuple(jnp.shape(x)[2:])
    xg = jnp.reshape(x, (n, groups, c // groups) + rest)
    axes = tuple(range(2, jnp.ndim(xg)))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=axes, keepdims=True)
    y = (xg - mean) * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype))
    y = jnp.reshape(y, jnp.shape(x))
    bshape = (1, c) + (1,) * len(rest)
    if "Scale" in ins and ins["Scale"]:
        y = y * jnp.reshape(ins["Scale"][0], bshape)
    if "Bias" in ins and ins["Bias"]:
        y = y + jnp.reshape(ins["Bias"][0], bshape)
    return {
        "Y": y,
        "Mean": jnp.reshape(mean, (n, groups)),
        "Variance": jnp.reshape(var, (n, groups)),
    }


register_op(
    "group_norm",
    inputs=["X", "Scale", "Bias"],
    outputs=["Y", "Mean", "Variance"],
    attrs={"epsilon": 1e-5, "groups": 1},
    lower=_lower_group_norm,
    intermediate_outputs=("Mean", "Variance"),
)


def _lower_im2sequence(ctx, ins, attrs):
    x = ins["X"][0]
    kernels = attrs.get("kernels", [1, 1])
    strides = attrs.get("strides", [1, 1])
    paddings = attrs.get("paddings", [0, 0, 0, 0])
    n, c, h, w = jnp.shape(x)
    xp = jnp.pad(
        x, [(0, 0), (0, 0), (paddings[0], paddings[2]), (paddings[1], paddings[3])]
    )
    patches = jax.lax.conv_general_dilated_patches(
        xp, kernels, strides, "VALID", dimension_numbers=_CONV_DN
    )
    # patches: [N, C*kh*kw, oh, ow] -> [N*oh*ow, C*kh*kw]
    _, ckk, oh, ow = jnp.shape(patches)
    out = jnp.transpose(patches, (0, 2, 3, 1))
    return jnp.reshape(out, (n * oh * ow, ckk))


register_op(
    "im2sequence",
    inputs=["X"],
    outputs=["Out"],
    attrs={"kernels": [1, 1], "strides": [1, 1], "paddings": [0, 0, 0, 0]},
    lower=_lower_im2sequence,
)


def _interp(x, out_h, out_w, method):
    n, c, h, w = jnp.shape(x)
    xt = jnp.transpose(x, (0, 2, 3, 1))
    out = jax.image.resize(xt, (n, out_h, out_w, c), method=method)
    return jnp.transpose(out, (0, 3, 1, 2)).astype(x.dtype)


register_op(
    "bilinear_interp",
    inputs=["X", "OutSize"],
    outputs=["Out"],
    attrs={"out_h": -1, "out_w": -1, "interp_method": "bilinear"},
    lower=lambda ctx, ins, attrs: _interp(
        ins["X"][0], attrs["out_h"], attrs["out_w"], "bilinear"
    ),
    no_grad_inputs=("OutSize",),
)

register_op(
    "nearest_interp",
    inputs=["X", "OutSize"],
    outputs=["Out"],
    attrs={"out_h": -1, "out_w": -1, "interp_method": "nearest"},
    lower=lambda ctx, ins, attrs: _interp(
        ins["X"][0], attrs["out_h"], attrs["out_w"], "nearest"
    ),
    no_grad_inputs=("OutSize",),
)


def _lower_conv3d_transpose(ctx, ins, attrs):
    """conv_transpose_op.cc (conv3d_transpose): same gradient-of-conv
    formulation as conv2d_transpose over three spatial dims."""
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = _pair(attrs.get("strides", [1, 1, 1]), 3)
    paddings = _pair(attrs.get("paddings", [0, 0, 0]), 3)
    dilations = _pair(attrs.get("dilations", [1, 1, 1]), 3)
    groups = attrs.get("groups", 1)
    ks = [
        (jnp.shape(w)[2 + i] - 1) * dilations[i] + 1 for i in range(3)
    ]
    extra = _transpose_extra_pad(
        attrs.get("output_size"), [jnp.shape(x)[2 + i] for i in range(3)],
        strides, paddings, ks,
    )
    pads = [(k - 1 - p, k - 1 - p + e)
            for k, p, e in zip(ks, paddings, extra)]
    return jax.lax.conv_general_dilated(
        x,
        _transpose_weight(w, groups, 3),
        window_strides=(1, 1, 1),
        padding=pads,
        lhs_dilation=strides,
        rhs_dilation=dilations,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        feature_group_count=groups,
    )


register_op(
    "conv3d_transpose",
    inputs=["Input", "Filter"],
    outputs=["Output"],
    attrs={
        "strides": [1, 1, 1],
        "paddings": [0, 0, 0],
        "dilations": [1, 1, 1],
        "groups": 1,
        "output_size": [],
    },
    lower=_lower_conv3d_transpose,
)
