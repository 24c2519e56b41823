"""Neural-net ops: matmul/mul, conv, pooling, normalisation, dropout.

References: paddle/fluid/operators/{mul,matmul,conv,pool,batch_norm,
layer_norm,group_norm,dropout}_op.* — rebuilt on lax conv/dot primitives so
XLA tiles them onto the MXU. Convs run in NCHW logical layout (the reference's
layout) but lax is free to relayout internally for TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..lowering import lowering_platform
from .common import IOSpec, out, register_op, x


@register_op("mul", inputs=["X", "Y"], outputs=["Out"],
             attrs={"x_num_col_dims": 1, "y_num_col_dims": 1})
def _mul(ctx, ins, attrs):
    """fc's matmul: X flattened to 2D at x_num_col_dims (reference mul_op.cc)."""
    xv, yv = x(ins, "X"), x(ins, "Y")
    xnc, ync = attrs["x_num_col_dims"], attrs["y_num_col_dims"]
    xs, ys = xv.shape, yv.shape
    x2 = xv.reshape((int(np.prod(xs[:xnc])), int(np.prod(xs[xnc:]))))
    y2 = yv.reshape((int(np.prod(ys[:ync])), int(np.prod(ys[ync:]))))
    res = x2 @ y2
    return out(res.reshape(xs[:xnc] + ys[ync:]))


@register_op("matmul", inputs=["X", "Y"], outputs=["Out"],
             attrs={"transpose_X": False, "transpose_Y": False, "alpha": 1.0,
                    "out_dtype": ""})
def _matmul(ctx, ins, attrs):
    xv, yv = x(ins, "X"), x(ins, "Y")
    if attrs["transpose_X"]:
        if xv.ndim == 1:
            pass
        else:
            xv = jnp.swapaxes(xv, -1, -2)
    if attrs["transpose_Y"]:
        if yv.ndim == 1:
            pass
        else:
            yv = jnp.swapaxes(yv, -1, -2)
    if attrs.get("out_dtype"):      # the accumulator's type, not the operands'
        from ..core.types import jnp_dtype

        res = jnp.matmul(xv, yv,
                         preferred_element_type=jnp_dtype(attrs["out_dtype"]))
    else:
        res = jnp.matmul(xv, yv)
    if attrs.get("alpha", 1.0) != 1.0:
        res = res * attrs["alpha"]
    return out(res)


def _conv_padding(padding, ksize, dilations):
    return [(p, p) for p in padding]


def _use_nhwc(ctx) -> bool:
    """TPU convs want channels on the 128-lane minor dim (NHWC). The API
    stays NCHW (the reference layout); the lowering transposes at the op
    boundary — consecutive conv/pool layers' transposes cancel in XLA, so
    steady-state compute runs NHWC end to end. ``auto`` follows the device
    the step is lowered for (``lowering.lowering_platform``)."""
    from .. import flags

    mode = flags.flag("conv_use_nhwc")
    if mode == "always":
        return True
    if mode == "never":
        return False
    return lowering_platform(ctx) == "tpu"


@register_op("conv2d", inputs=[IOSpec("Input"), IOSpec("Filter"),
                               IOSpec("Bias", optional=True)],
             outputs=["Output"],
             attrs={"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1],
                    "groups": 1, "use_cudnn": True, "data_format": "NCHW"})
def _conv2d(ctx, ins, attrs):
    inp, flt = x(ins, "Input"), x(ins, "Filter")
    pad = _conv_padding(attrs["paddings"], flt.shape[2:], attrs["dilations"])
    if _use_nhwc(ctx):
        res = jax.lax.conv_general_dilated(
            inp.transpose(0, 2, 3, 1), flt.transpose(2, 3, 1, 0),
            window_strides=attrs["strides"], padding=pad,
            rhs_dilation=attrs["dilations"],
            feature_group_count=attrs.get("groups", 1),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ).transpose(0, 3, 1, 2)
    else:
        res = jax.lax.conv_general_dilated(
            inp, flt,
            window_strides=attrs["strides"], padding=pad,
            rhs_dilation=attrs["dilations"],
            feature_group_count=attrs.get("groups", 1),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
        )
    b = x(ins, "Bias")
    if b is not None:
        res = res + b.reshape((1, -1, 1, 1))
    return {"Output": [res]}


@register_op("depthwise_conv2d", inputs=[IOSpec("Input"), IOSpec("Filter"),
                                         IOSpec("Bias", optional=True)],
             outputs=["Output"],
             attrs={"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1],
                    "groups": 1, "use_cudnn": False, "data_format": "NCHW"})
def _depthwise_conv2d(ctx, ins, attrs):
    return _conv2d(ctx, ins, attrs)


@register_op("conv2d_transpose", inputs=[IOSpec("Input"), IOSpec("Filter"),
                                         IOSpec("Bias", optional=True)],
             outputs=["Output"],
             attrs={"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1],
                    "groups": 1, "output_size": [], "data_format": "NCHW"})
def _conv2d_transpose(ctx, ins, attrs):
    """Transposed conv as an lhs-dilated forward conv with the spatially
    flipped kernel (reference conv_transpose_op.h col2im semantics):
    out = conv(x dilated by stride, flip(W), padding (k-1)*d - p).
    Verified against a scatter-add oracle (tests/test_ops_nn.py).
    Filter layout is the reference's (in, out/groups, kh, kw)."""
    inp, flt = x(ins, "Input"), x(ins, "Filter")
    strides = attrs["strides"]
    dil = attrs["dilations"]
    pads = attrs["paddings"]
    k = flt.shape[2:]
    pad = [((k[i] - 1) * dil[i] - pads[i],) * 2 for i in range(2)]
    groups = attrs.get("groups", 1)
    if groups != 1:
        raise NotImplementedError("conv2d_transpose groups>1 not supported")
    wf = jnp.flip(flt, (2, 3))
    if _use_nhwc(ctx):
        res = jax.lax.conv_general_dilated(
            inp.transpose(0, 2, 3, 1), wf.transpose(2, 3, 0, 1),
            window_strides=(1, 1), padding=pad,
            lhs_dilation=strides, rhs_dilation=dil,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ).transpose(0, 3, 1, 2)
    else:
        res = jax.lax.conv_general_dilated(
            inp, wf, window_strides=(1, 1), padding=pad,
            lhs_dilation=strides, rhs_dilation=dil,
            dimension_numbers=("NCHW", "IOHW", "NCHW"),
        )
    b = x(ins, "Bias")
    if b is not None:
        res = res + b.reshape((1, -1, 1, 1))
    return {"Output": [res]}


@register_op("pool2d", inputs=["X"], outputs=["Out"],
             attrs={"pooling_type": "max", "ksize": [2, 2], "strides": [2, 2],
                    "paddings": [0, 0], "global_pooling": False,
                    "exclusive": True, "adaptive": False, "ceil_mode": False,
                    "use_cudnn": True, "data_format": "NCHW"})
def _pool2d(ctx, ins, attrs):
    xv = x(ins)
    ksize = list(attrs["ksize"])
    strides = list(attrs["strides"])
    pads = list(attrs["paddings"])
    in_hw = list(xv.shape[2:])
    if attrs.get("global_pooling") or (attrs.get("adaptive")
                                       and ksize == [1, 1]):
        ksize = in_hw
        strides = list(ksize)
        pads = [0, 0]
    elif attrs.get("adaptive"):
        if all(d % o == 0 for d, o in zip(in_hw, ksize)):
            # uniform regions: adaptive == fixed-window pool (window = D/o)
            strides = [d // o for d, o in zip(in_hw, ksize)]
            ksize, pads = list(strides), [0, 0]
        else:
            return out(_adaptive_pool2d(xv, ksize, attrs["pooling_type"]))
    # ceil_mode adds right/bottom padding so the last partial window counts
    # (reference pooling.cc output size ceil((in - k + 2p)/s) + 1)
    extra = [0, 0]
    if attrs.get("ceil_mode") and not attrs.get("global_pooling"):
        for i in range(2):
            out_ceil = -(-(in_hw[i] - ksize[i] + 2 * pads[i]) // strides[i]) + 1
            extra[i] = max(
                0, (out_ceil - 1) * strides[i] + ksize[i]
                - (in_hw[i] + 2 * pads[i]))
    nhwc = _use_nhwc(ctx)
    if nhwc:
        xv = xv.transpose(0, 2, 3, 1)   # keep the conv chain in NHWC
        window = (1,) + tuple(ksize) + (1,)
        strd = (1,) + tuple(strides) + (1,)
        padding = ((0, 0),) + tuple(
            (p, p + e) for p, e in zip(pads, extra)) + ((0, 0),)
    else:
        window = (1, 1) + tuple(ksize)
        strd = (1, 1) + tuple(strides)
        padding = ((0, 0), (0, 0)) + tuple(
            (p, p + e) for p, e in zip(pads, extra))
    if attrs["pooling_type"] == "max":
        init = -jnp.inf
        res = jax.lax.reduce_window(xv, init, jax.lax.max, window, strd, padding)
    else:
        summed = jax.lax.reduce_window(xv, 0.0, jax.lax.add, window, strd, padding)
        if attrs.get("exclusive", True) and (any(p > 0 for p in pads)
                                             or any(e > 0 for e in extra)):
            ones = jnp.ones_like(xv)
            count = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strd, padding)
            res = summed / count
        else:
            res = summed / float(np.prod(ksize))
    if nhwc:
        res = res.transpose(0, 3, 1, 2)
    return out(res)


def _adaptive_pool2d(xv, out_hw, pooling_type):
    """General adaptive pooling: region i spans [floor(i*D/o), ceil((i+1)*D/o)).
    Regions are non-uniform, so reduce_window cannot express it; out_hw is a
    static attr, so a Python loop over output cells traces to a fixed graph."""
    in_h, in_w = xv.shape[2:]
    oh, ow = out_hw
    reduce_fn = jnp.max if pooling_type == "max" else jnp.mean
    rows = []
    for i in range(oh):
        h0, h1 = (i * in_h) // oh, -((-(i + 1) * in_h) // oh)
        cols = []
        for j in range(ow):
            w0, w1 = (j * in_w) // ow, -((-(j + 1) * in_w) // ow)
            cols.append(reduce_fn(xv[:, :, h0:h1, w0:w1], axis=(2, 3)))
        rows.append(jnp.stack(cols, axis=-1))
    return jnp.stack(rows, axis=-2)


@register_op("batch_norm",
             inputs=[IOSpec("X"), IOSpec("Scale"), IOSpec("Bias"),
                     IOSpec("Mean"), IOSpec("Variance")],
             outputs=["Y", "MeanOut", "VarianceOut", "SavedMean", "SavedVariance"],
             attrs={"momentum": 0.9, "epsilon": 1e-5, "is_test": False,
                    "use_global_stats": False, "data_layout": "NCHW"})
def _batch_norm(ctx, ins, attrs):
    """Reference batch_norm_op.cc. Running stats update happens by writing the
    MeanOut/VarianceOut outputs, which alias the Mean/Variance persistable
    vars in the program — the env-threading in lowering.py makes that an
    in-place-style update without mutation."""
    xv = x(ins, "X")
    scale, bias = x(ins, "Scale"), x(ins, "Bias")
    mean, var = x(ins, "Mean"), x(ins, "Variance")
    eps, mom = attrs["epsilon"], attrs["momentum"]
    layout = attrs.get("data_layout", "NCHW")
    axes = (0, 2, 3) if (xv.ndim == 4 and layout == "NCHW") else tuple(
        i for i in range(xv.ndim) if i != xv.ndim - 1
    ) if layout == "NHWC" else (0,)
    use_global = attrs.get("is_test") or attrs.get("use_global_stats")
    if use_global:
        use_mean, use_var = mean, var
        mean_out, var_out = mean, var
        saved_mean = jnp.zeros_like(mean)
        saved_var = jnp.zeros_like(var)
    else:
        use_mean = jnp.mean(xv, axis=axes)
        use_var = jnp.var(xv, axis=axes)
        mean_out = mean * mom + use_mean * (1 - mom)
        var_out = var * mom + use_var * (1 - mom)
        saved_mean = use_mean
        saved_var = 1.0 / jnp.sqrt(use_var + eps)
    bshape = [1] * xv.ndim
    c_axis = 1 if layout == "NCHW" else xv.ndim - 1
    bshape[c_axis] = xv.shape[c_axis]
    rs = lambda t: t.reshape(bshape)
    y = (xv - rs(use_mean)) * rs(1.0 / jnp.sqrt(use_var + eps)) * rs(scale) + rs(bias)
    return {"Y": [y], "MeanOut": [mean_out], "VarianceOut": [var_out],
            "SavedMean": [saved_mean], "SavedVariance": [saved_var]}


@register_op("layer_norm",
             inputs=[IOSpec("X"), IOSpec("Scale", optional=True),
                     IOSpec("Bias", optional=True)],
             outputs=["Y", "Mean", "Variance"],
             attrs={"epsilon": 1e-5, "begin_norm_axis": 1})
def _layer_norm(ctx, ins, attrs):
    xv = x(ins, "X")
    scale, bias = x(ins, "Scale"), x(ins, "Bias")
    bna = attrs["begin_norm_axis"]
    axes = tuple(range(bna, xv.ndim))
    mean = jnp.mean(xv, axis=axes, keepdims=True)
    var = jnp.var(xv, axis=axes, keepdims=True)
    y = (xv - mean) / jnp.sqrt(var + attrs["epsilon"])
    if scale is not None:
        y = y * scale.reshape((1,) * bna + xv.shape[bna:])
    if bias is not None:
        y = y + bias.reshape((1,) * bna + xv.shape[bna:])
    lead = int(np.prod(xv.shape[:bna]))
    return {"Y": [y], "Mean": [mean.reshape((lead,))],
            "Variance": [var.reshape((lead,))]}


@register_op("group_norm",
             inputs=[IOSpec("X"), IOSpec("Scale", optional=True),
                     IOSpec("Bias", optional=True)],
             outputs=["Y", "Mean", "Variance"],
             attrs={"epsilon": 1e-5, "groups": 1})
def _group_norm(ctx, ins, attrs):
    xv = x(ins, "X")
    n, c = xv.shape[0], xv.shape[1]
    g = attrs["groups"]
    xg = xv.reshape((n, g, c // g) + xv.shape[2:])
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    y = ((xg - mean) / jnp.sqrt(var + attrs["epsilon"])).reshape(xv.shape)
    scale, bias = x(ins, "Scale"), x(ins, "Bias")
    bshape = (1, c) + (1,) * (xv.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    return {"Y": [y], "Mean": [mean.reshape((n, g))],
            "Variance": [var.reshape((n, g))]}


@register_op("instance_norm",
             inputs=[IOSpec("X"), IOSpec("Scale", optional=True),
                     IOSpec("Bias", optional=True)],
             outputs=["Y", "SavedMean", "SavedVariance"],
             attrs={"epsilon": 1e-5})
def _instance_norm(ctx, ins, attrs):
    xv = x(ins, "X")
    axes = tuple(range(2, xv.ndim))
    mean = jnp.mean(xv, axis=axes, keepdims=True)
    var = jnp.var(xv, axis=axes, keepdims=True)
    y = (xv - mean) / jnp.sqrt(var + attrs["epsilon"])
    scale, bias = x(ins, "Scale"), x(ins, "Bias")
    bshape = (1, xv.shape[1]) + (1,) * (xv.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    n, c = xv.shape[0], xv.shape[1]
    return {"Y": [y], "SavedMean": [mean.reshape((n * c,))],
            "SavedVariance": [(1.0 / jnp.sqrt(var + attrs["epsilon"])).reshape((n * c,))]}


@register_op("dropout", inputs=["X"], outputs=["Out", "Mask"],
             attrs={"dropout_prob": 0.5, "is_test": False, "seed": 0,
                    "fix_seed": False,
                    "dropout_implementation": "downgrade_in_infer"},
             needs_rng=True)
def _dropout(ctx, ins, attrs):
    """The grad op recomputes this under vjp with the SAME ctx key (fwd uid is
    folded in), so the mask is bit-identical between forward and backward."""
    xv = x(ins)
    p = attrs["dropout_prob"]
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if attrs.get("is_test"):
        y = xv * (1.0 - p) if impl == "downgrade_in_infer" else xv
        return {"Out": [y], "Mask": [jnp.ones_like(xv)]}
    key = jax.random.key(attrs["seed"]) if attrs.get("fix_seed") else ctx.rng()
    keep = jax.random.bernoulli(key, 1.0 - p, xv.shape)
    mask = keep.astype(xv.dtype)
    y = xv * mask
    if impl == "upscale_in_train" and p < 1.0:
        y = y / (1.0 - p)
    return {"Out": [y], "Mask": [mask]}


@register_op("l2_normalize", inputs=["X"], outputs=["Out", "Norm"],
             attrs={"axis": -1, "epsilon": 1e-12})
def _l2_normalize(ctx, ins, attrs):
    xv = x(ins)
    norm = jnp.sqrt(jnp.sum(jnp.square(xv), axis=attrs["axis"], keepdims=True)
                    + attrs["epsilon"])
    return {"Out": [xv / norm], "Norm": [norm]}


@register_op("prelu", inputs=["X", "Alpha"], outputs=["Out"],
             attrs={"mode": "all"})
def _prelu(ctx, ins, attrs):
    xv, alpha = x(ins, "X"), x(ins, "Alpha")
    mode = attrs.get("mode", "all")
    if mode == "channel":
        alpha = alpha.reshape((1, -1) + (1,) * (xv.ndim - 2))
    elif mode == "element":
        alpha = alpha.reshape((1,) + xv.shape[1:])
    elif mode == "all":
        alpha = alpha.reshape(())
    return out(jnp.where(xv > 0, xv, alpha * xv))


@register_op("interpolate_nearest", inputs=["X"], outputs=["Out"],
             attrs={"out_h": 0, "out_w": 0, "align_corners": False})
def _interp_nearest(ctx, ins, attrs):
    xv = x(ins)
    n, c = xv.shape[:2]
    return out(jax.image.resize(
        xv, (n, c, attrs["out_h"], attrs["out_w"]), method="nearest"))


@register_op("bilinear_interp", inputs=["X"], outputs=["Out"],
             attrs={"out_h": 0, "out_w": 0, "align_corners": True})
def _bilinear_interp(ctx, ins, attrs):
    xv = x(ins)
    n, c = xv.shape[:2]
    return out(jax.image.resize(
        xv, (n, c, attrs["out_h"], attrs["out_w"]), method="bilinear"))
