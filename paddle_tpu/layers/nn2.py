"""Extended fluid.layers surface — the long tail of reference
python/paddle/fluid/layers/nn.py functions whose ops already exist in the
registry but had no layer-building wrapper, plus reference pure-python
composites (dice_loss, mse_loss, npair_loss, image_resize_short,
fsp_matrix). Signatures mirror the reference; each wrapper is the standard
LayerHelper -> append_op -> Variable pattern."""
from __future__ import annotations

import numpy as np

from ..initializer import Constant, Normal, Xavier
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr
from . import nn as _nn

__all__ = [
    "conv3d", "pool3d", "conv3d_transpose", "adaptive_pool2d", "lrn",
    "pad_constant_like", "label_smooth", "gather_nd", "scatter_nd_add",
    "scatter_nd", "crop", "crop_tensor", "affine_grid", "rank_loss",
    "margin_rank_loss", "pad2d", "sampling_id", "strided_slice", "maxout",
    "space_to_depth", "affine_channel", "hash", "grid_sampler",
    "add_position_encoding", "shuffle_channel", "temporal_shift",
    "kldiv_loss", "pixel_shuffle", "unique", "unique_with_counts",
    "unfold", "shard_index", "bpr_loss", "cross_entropy2", "random_crop",
    "similarity_focus", "teacher_student_sigmoid_loss", "roi_pool",
    "roi_align", "mean_iou", "bilinear_tensor_product", "multiplex",
    "im2sequence", "row_conv", "selu", "stanh", "brelu", "sign",
    "elementwise_mod", "elementwise_floordiv", "sum", "rank", "size",
    "dice_loss", "mse_loss", "npair_loss", "image_resize_short",
    "fsp_matrix", "uniform_random_batch_size_like",
    "gaussian_random_batch_size_like", "maxout", "center_loss",
    "data_norm", "spectral_norm", "deformable_conv", "deformable_roi_pooling",
    "psroi_pool", "prroi_pool", "merge_selected_rows",
    "get_tensor_from_selected_rows", "continuous_value_model",
    "sampled_softmax_with_cross_entropy", "py_func", "resize_trilinear",
    "lstm_unit", "autoincreased_step_counter", "adaptive_pool3d",
    "beam_search", "beam_search_decode", "filter_by_instag",
    "fused_decode_attention", "kv_cache_append", "kv_cache_fold",
    "sequence_gather",
    "rotary_embedding", "moe_experts", "slot_assign", "gated_delta_rule",
    "mamba2_scan", "rms_norm", "latent_attention",
    "hyper_connection_read", "hyper_connection_write",
    "sample_token", "spec_accept",
    "block_seed", "block_positions", "block_reveal",
]


def _one(op_type, inputs, attrs=None, dtype=None, n_out=1, out_slot="Out",
         extra_outs=(), name=None):
    """Generic single-main-output wrapper."""
    helper = LayerHelper(op_type, name=name)
    first = next(v for v in inputs.values()
                 if v is not None and not isinstance(v, (list, tuple)))
    dtype = dtype or first.dtype
    out = helper.create_variable_for_type_inference(dtype)
    outs = {out_slot: out}
    extras = []
    for slot, dt in extra_outs:
        ev = helper.create_variable_for_type_inference(dt or dtype,
                                                       stop_gradient=True)
        outs[slot] = ev
        extras.append(ev)
    helper.append_op(op_type,
                     inputs={k: v for k, v in inputs.items()
                             if v is not None},
                     outputs=outs, attrs=attrs or {})
    return (out, *extras) if extras else out


def _triple(v):
    return list(v) if isinstance(v, (list, tuple)) else [v] * 3


# -- 3D conv/pool -----------------------------------------------------------

def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None):
    helper = LayerHelper("conv3d", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    k = _triple(filter_size)
    num_channels = input.shape[1]
    std = (2.0 / (k[0] * k[1] * k[2] * num_channels)) ** 0.5
    w = helper.create_parameter(
        helper.param_attr, shape=[num_filters, num_channels // groups] + k,
        dtype=input.dtype, default_initializer=Normal(0.0, std))
    pre_bias = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("conv3d", inputs={"Input": input, "Filter": w},
                     outputs={"Output": pre_bias},
                     attrs={"strides": _triple(stride),
                            "paddings": _triple(padding),
                            "dilations": _triple(dilation),
                            "groups": groups})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def pool3d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, ceil_mode=False,
           exclusive=True, name=None):
    return _one("pool3d", {"X": input},
                {"pooling_type": pool_type, "ksize": _triple(pool_size),
                 "strides": _triple(pool_stride),
                 "paddings": _triple(pool_padding),
                 "global_pooling": global_pooling, "ceil_mode": ceil_mode,
                 "exclusive": exclusive}, name=name)


def conv3d_transpose(input, num_filters, output_size=None, filter_size=None,
                     stride=1, padding=0, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("conv3d_transpose", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    k = _triple(filter_size)
    num_channels = input.shape[1]
    w = helper.create_parameter(
        helper.param_attr, shape=[num_channels, num_filters // groups] + k,
        dtype=input.dtype, default_initializer=Xavier())
    pre_bias = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("conv3d_transpose",
                     inputs={"Input": input, "Filter": w},
                     outputs={"Output": pre_bias},
                     attrs={"strides": _triple(stride),
                            "paddings": _triple(padding),
                            "dilations": _triple(dilation),
                            "groups": groups})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def adaptive_pool2d(input, pool_size, pool_type="max", require_index=False,
                    name=None):
    if require_index:
        raise NotImplementedError(
            "adaptive_pool2d(require_index=True): XLA has no argmax-index "
            "pooling output; take argmax over unfold-ed windows instead")
    ps = pool_size if isinstance(pool_size, (list, tuple)) \
        else [pool_size, pool_size]
    return _one("pool2d", {"X": input},
                {"pooling_type": pool_type, "ksize": list(ps),
                 "adaptive": True}, name=name)


# -- image / tensor rearrangement ------------------------------------------

def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    out, _ = _one("lrn", {"X": input}, {"n": n, "k": k, "alpha": alpha,
                                        "beta": beta},
                  extra_outs=[("MidOut", None)], name=name)
    return out


def pad_constant_like(x, y, pad_value=0.0, name=None):
    return _one("pad_constant_like", {"X": x, "Y": y},
                {"pad_value": float(pad_value)}, name=name)


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    return _one("label_smooth", {"X": label, "PriorDist": prior_dist},
                {"epsilon": float(epsilon)}, name=name)


def gather_nd(input, index, name=None):
    return _one("gather_nd", {"X": input, "Index": index}, name=name)


def scatter_nd_add(ref, index, updates, name=None):
    return _one("scatter_nd_add",
                {"X": ref, "Index": index, "Updates": updates}, name=name)


def scatter_nd(index, updates, shape, name=None):
    """Composite (reference nn.py scatter_nd): scatter_nd_add onto zeros."""
    from .tensor import fill_constant

    zero = fill_constant(shape=list(shape), dtype=updates.dtype, value=0.0)
    return scatter_nd_add(zero, index, updates, name=name)


def crop(x, shape=None, offsets=None, name=None):
    attrs = {}
    ins = {"X": x}
    if isinstance(shape, (list, tuple)):
        attrs["shape"] = list(shape)
    elif shape is not None:
        ins["Y"] = shape
    if isinstance(offsets, (list, tuple)):
        attrs["offsets"] = list(offsets)
    elif offsets is not None:
        ins["Offsets"] = offsets
    return _one("crop", ins, attrs, name=name)


def crop_tensor(x, shape=None, offsets=None, name=None):
    attrs = {}
    ins = {"X": x}
    if isinstance(shape, (list, tuple)):
        attrs["shape"] = list(shape)
    elif shape is not None:
        ins["Shape"] = shape
    if isinstance(offsets, (list, tuple)):
        attrs["offsets"] = list(offsets)
    elif offsets is not None:
        ins["Offsets"] = offsets
    return _one("crop_tensor", ins, attrs, name=name)


def affine_grid(theta, out_shape=None, name=None):
    attrs = {}
    ins = {"Theta": theta}
    if isinstance(out_shape, (list, tuple)):
        attrs["output_shape"] = [int(v) for v in out_shape]
    elif out_shape is not None:
        ins["OutputShape"] = out_shape
    return _one("affine_grid", ins, attrs, out_slot="Output", name=name)


def pad2d(input, paddings=(0, 0, 0, 0), mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    return _one("pad2d", {"X": input},
                {"paddings": list(paddings), "mode": mode,
                 "pad_value": float(pad_value), "data_format": data_format},
                name=name)


def strided_slice(input, axes, starts, ends, strides, name=None):
    return _one("strided_slice", {"Input": input},
                {"axes": list(axes), "starts": list(starts),
                 "ends": list(ends), "strides": list(strides)}, name=name)


def maxout(x, groups, axis=1, name=None):
    return _one("maxout", {"X": x}, {"groups": groups, "axis": axis},
                name=name)


def space_to_depth(x, blocksize, name=None):
    return _one("space_to_depth", {"X": x}, {"blocksize": blocksize},
                name=name)


def affine_channel(x, scale=None, bias=None, data_layout="NCHW", name=None):
    return _one("affine_channel", {"X": x, "Scale": scale, "Bias": bias},
                {"data_layout": data_layout}, name=name)


def hash(input, hash_size, num_hash=1, name=None):
    return _one("hash", {"X": input},
                {"num_hash": num_hash, "mod_by": hash_size}, dtype="int64",
                name=name)


def grid_sampler(x, grid, name=None):
    return _one("grid_sampler", {"X": x, "Grid": grid},
                out_slot="Output", name=name)


def add_position_encoding(input, alpha=1.0, beta=1.0, name=None):
    return _one("add_position_encoding", {"X": input},
                {"alpha": float(alpha), "beta": float(beta)}, name=name)


def shuffle_channel(x, group, name=None):
    return _one("shuffle_channel", {"X": x}, {"group": group}, name=name)


def temporal_shift(x, seg_num, shift_ratio=0.25, name=None):
    return _one("temporal_shift", {"X": x},
                {"seg_num": seg_num, "shift_ratio": shift_ratio}, name=name)


def pixel_shuffle(x, upscale_factor, name=None):
    return _one("pixel_shuffle", {"X": x},
                {"upscale_factor": upscale_factor}, name=name)


def unique(x, dtype="int32", name=None):
    return _one("unique", {"X": x}, {"dtype": dtype},
                extra_outs=[("Index", dtype)], name=name)


def unique_with_counts(x, dtype="int32", name=None):
    return _one("unique_with_counts", {"X": x}, {"dtype": dtype},
                extra_outs=[("Index", dtype), ("Count", dtype)], name=name)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    pair = lambda v: list(v) if isinstance(v, (list, tuple)) else [v, v]
    return _one("unfold", {"X": x},
                {"kernel_sizes": pair(kernel_sizes),
                 "strides": pair(strides),
                 "paddings": pair(paddings) if not isinstance(
                     paddings, (list, tuple)) or len(paddings) != 4
                 else list(paddings),
                 "dilations": pair(dilations)}, out_slot="Y", name=name)


def shard_index(input, index_num, nshards, shard_id, ignore_value=-1,
                name=None):
    return _one("shard_index", {"X": input},
                {"index_num": index_num, "nshards": nshards,
                 "shard_id": shard_id, "ignore_value": ignore_value},
                name=name)


def random_crop(x, shape, seed=None, name=None):
    out, _ = _one("random_crop", {"X": x},
                  {"shape": list(shape),
                   "startup_seed": int(seed) if seed else 0},
                  extra_outs=[("SeedOut", "int64")], name=name)
    return out


def similarity_focus(input, axis, indexes, name=None):
    return _one("similarity_focus", {"X": input},
                {"axis": axis, "indexes": list(indexes)}, name=name)


def sampling_id(x, min=0.0, max=1.0, seed=0, dtype="int64", name=None):
    return _one("sampling_id", {"X": x},
                {"min": min, "max": max, "seed": seed}, dtype=dtype,
                name=name)


# -- losses -----------------------------------------------------------------

def rank_loss(label, left, right, name=None):
    return _one("rank_loss", {"Label": label, "Left": left, "Right": right},
                name=name)


def margin_rank_loss(label, left, right, margin=0.1, name=None):
    out, _ = _one("margin_rank_loss",
                  {"Label": label, "X1": left, "X2": right},
                  {"margin": float(margin)},
                  extra_outs=[("Activated", None)], name=name)
    return out


def kldiv_loss(x, target, reduction="mean", name=None):
    return _one("kldiv_loss", {"X": x, "Target": target},
                {"reduction": reduction}, out_slot="Loss", name=name)


def bpr_loss(input, label, name=None):
    return _one("bpr_loss", {"X": input, "Label": label}, out_slot="Y",
                name=name)


def cross_entropy2(input, label, ignore_index=-100, name=None):
    out, _, _ = _one("cross_entropy2", {"X": input, "Label": label},
                     {"ignore_index": ignore_index}, out_slot="Y",
                     extra_outs=[("XShape", None), ("MatchX", None)],
                     name=name)
    return out


def teacher_student_sigmoid_loss(input, label, soft_max_up_bound=15.0,
                                 soft_max_lower_bound=-15.0):
    return _one("teacher_student_sigmoid_loss",
                {"X": input, "Label": label},
                {"soft_max_up_bound": soft_max_up_bound,
                 "soft_max_lower_bound": soft_max_lower_bound},
                out_slot="Y")


def dice_loss(input, label, epsilon=1e-5):
    """Composite, reference nn.py dice_loss: 1 - 2|X*Y| / (|X|+|Y|)."""
    label = _nn.one_hot(label, input.shape[-1])
    reduce_dims = list(range(1, len(input.shape)))
    inse = _nn.reduce_sum(_nn.elementwise_mul(input, label),
                          dim=reduce_dims)
    dice_denominator = _nn.elementwise_add(
        _nn.reduce_sum(input, dim=reduce_dims),
        _nn.reduce_sum(label, dim=reduce_dims))
    dice_score = _nn.scale(
        _nn.elementwise_div(
            inse, _nn.scale(dice_denominator, scale=1.0, bias=epsilon)),
        scale=-2.0, bias=1.0)
    return _nn.reduce_mean(dice_score)


def mse_loss(input, label):
    """Composite, reference nn.py mse_loss."""
    return _nn.reduce_mean(_nn.square_error_cost(input, label))


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    """Composite, reference nn.py npair_loss (multi-class N-pair loss)."""
    batch = anchor.shape[0]
    labels = _nn.reshape(_nn.cast(labels, "float32"), [-1, 1])
    same = _nn.cast(_nn.equal(labels, _nn.transpose(labels, [1, 0])),
                    "float32")
    targets = _nn.elementwise_div(
        same, _nn.reduce_sum(same, dim=1, keep_dim=True))
    logits = _nn.matmul(anchor, positive, transpose_y=True)
    softmax_ce = _nn.reduce_mean(_nn.reduce_sum(
        _nn.elementwise_mul(_nn.scale(targets, scale=-1.0),
                            _nn.log_softmax(logits)), dim=1))
    reg = _nn.scale(
        _nn.elementwise_add(_nn.reduce_mean(_nn.reduce_sum(
            _nn.square(anchor), dim=1)),
            _nn.reduce_mean(_nn.reduce_sum(_nn.square(positive), dim=1))),
        scale=float(l2_reg) * 0.25)
    return _nn.elementwise_add(softmax_ce, reg)


def center_loss(input, label, num_classes, alpha, param_attr=None,
                update_center=True):
    helper = LayerHelper("center_loss", param_attr=param_attr)
    centers = helper.create_parameter(
        helper.param_attr, shape=[num_classes, input.shape[-1]],
        dtype=input.dtype, default_initializer=Constant(0.0))
    rate = helper.create_variable_for_type_inference("float32",
                                                     stop_gradient=True)
    helper.append_op("fill_constant", outputs={"Out": rate},
                     attrs={"shape": [1], "dtype": "float32",
                            "value": float(alpha)})
    c_out = helper.create_variable_for_type_inference(input.dtype,
                                                      stop_gradient=True)
    diff = helper.create_variable_for_type_inference(input.dtype,
                                                     stop_gradient=True)
    loss = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("center_loss",
                     inputs={"X": input, "Label": label,
                             "Centers": centers, "CenterUpdateRate": rate},
                     outputs={"CentersOut": c_out, "SampleCenterDiff": diff,
                              "Loss": loss},
                     attrs={"cluster_num": num_classes,
                            "need_update": update_center})
    return loss


# -- misc surface -----------------------------------------------------------

def roi_pool(input, rois, pooled_height=1, pooled_width=1,
             spatial_scale=1.0, rois_batch_idx=None, name=None):
    out, _ = _one("roi_pool",
                  {"X": input, "ROIs": rois,
                   "RoisBatchIdx": rois_batch_idx},
                  {"pooled_height": pooled_height,
                   "pooled_width": pooled_width,
                   "spatial_scale": spatial_scale},
                  extra_outs=[("Argmax", "int64")], name=name)
    return out


def roi_align(input, rois, pooled_height=1, pooled_width=1,
              spatial_scale=1.0, sampling_ratio=-1, rois_batch_idx=None,
              name=None):
    return _one("roi_align",
                {"X": input, "ROIs": rois, "RoisBatchIdx": rois_batch_idx},
                {"pooled_height": pooled_height,
                 "pooled_width": pooled_width,
                 "spatial_scale": spatial_scale,
                 "sampling_ratio": sampling_ratio}, name=name)


def mean_iou(input, label, num_classes):
    out, wrong, correct = _one(
        "mean_iou", {"Predictions": input, "Labels": label},
        {"num_classes": num_classes}, dtype="float32",
        out_slot="OutMeanIou",
        extra_outs=[("OutWrong", "int32"), ("OutCorrect", "int32")])
    return out, wrong, correct


def bilinear_tensor_product(x, y, size, act=None, name=None,
                            param_attr=None, bias_attr=None):
    helper = LayerHelper("bilinear_tensor_product", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    w = helper.create_parameter(
        helper.param_attr, shape=[size, x.shape[-1], y.shape[-1]],
        dtype=x.dtype)
    out = helper.create_variable_for_type_inference(x.dtype)
    ins = {"X": x, "Y": y, "Weight": w}
    if bias_attr is not False:
        b = helper.create_parameter(helper.bias_attr or ParamAttr(),
                                    shape=[1, size], dtype=x.dtype,
                                    is_bias=True)
        ins["Bias"] = b
    helper.append_op("bilinear_tensor_product", inputs=ins,
                     outputs={"Out": out})
    return helper.append_activation(out)


def multiplex(inputs, index):
    return _one("multiplex", {"Ids": index, "X": list(inputs)})


def im2sequence(input, filter_size=1, stride=1, padding=0, input_image_size=None,
                out_stride=1, name=None):
    pair = lambda v: list(v) if isinstance(v, (list, tuple)) else [v, v]
    pads = pair(padding)
    if len(pads) == 2:
        pads = pads + pads
    return _one("im2sequence", {"X": input, "Y": input_image_size},
                {"kernels": pair(filter_size), "strides": pair(stride),
                 "paddings": pads, "out_stride": pair(out_stride)},
                name=name)


def row_conv(input, future_context_size, param_attr=None, act=None):
    helper = LayerHelper("row_conv", param_attr=param_attr, act=act)
    w = helper.create_parameter(
        helper.param_attr,
        shape=[future_context_size + 1, input.shape[-1]],
        dtype=input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("row_conv", inputs={"X": input, "Filter": w},
                     outputs={"Out": out})
    return helper.append_activation(out)


def data_norm(input, act=None, epsilon=1e-5, param_attr=None, name=None):
    helper = LayerHelper("data_norm", param_attr=param_attr, act=act,
                         name=name)
    c = input.shape[-1]
    mk = lambda n, v: helper.create_parameter(
        ParamAttr(name=None), shape=[c], dtype=input.dtype,
        default_initializer=Constant(v))
    batch_size, batch_sum, batch_sq = mk("bs", 1e4), mk("bsum", 0.0), \
        mk("bsq", 1e4)
    y = helper.create_variable_for_type_inference(input.dtype)
    means = helper.create_variable_for_type_inference(input.dtype, True)
    scales = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op("data_norm",
                     inputs={"X": input, "BatchSize": batch_size,
                             "BatchSum": batch_sum,
                             "BatchSquareSum": batch_sq},
                     outputs={"Y": y, "Means": means, "Scales": scales},
                     attrs={"epsilon": epsilon})
    return helper.append_activation(y)


def spectral_norm(weight, dim=0, power_iters=1, eps=1e-12, name=None):
    helper = LayerHelper("spectral_norm", name=name)
    h = int(weight.shape[dim])
    w = int(np.prod([weight.shape[i] for i in range(len(weight.shape))
                     if i != dim]))
    import paddle_tpu.unique_name as un

    mk = lambda n, size: helper.create_parameter(
        ParamAttr(name=un.generate(n), trainable=False), shape=[size],
        dtype=weight.dtype, default_initializer=Normal(0.0, 1.0))
    u, v = mk("spectral_norm_u", h), mk("spectral_norm_v", w)
    out = helper.create_variable_for_type_inference(weight.dtype)
    helper.append_op("spectral_norm",
                     inputs={"Weight": weight, "U": u, "V": v},
                     outputs={"Out": out},
                     attrs={"dim": dim, "power_iters": power_iters,
                            "eps": eps})
    return out


def selu(x, scale=None, alpha=None, name=None):
    attrs = {}
    if scale is not None:
        attrs["scale"] = scale
    if alpha is not None:
        attrs["alpha"] = alpha
    return _one("selu", {"X": x}, attrs, name=name)


def stanh(x, scale_a=0.67, scale_b=1.7159, name=None):
    return _one("stanh", {"X": x},
                {"scale_a": scale_a, "scale_b": scale_b}, name=name)


def brelu(x, t_min=0.0, t_max=24.0, name=None):
    return _one("brelu", {"X": x}, {"t_min": t_min, "t_max": t_max},
                name=name)


def sign(x, name=None):
    return _one("sign", {"X": x}, name=name)


def elementwise_mod(x, y, axis=-1, name=None):
    return _one("elementwise_mod", {"X": x, "Y": y}, {"axis": axis},
                name=name)


def elementwise_floordiv(x, y, axis=-1, name=None):
    return _one("elementwise_floordiv", {"X": x, "Y": y}, {"axis": axis},
                name=name)


def sum(x):
    ins = list(x) if isinstance(x, (list, tuple)) else [x]
    return _one("sum", {"X": ins})


def rank(input):
    """Static rank as a constant tensor (reference nn.py rank)."""
    from .tensor import fill_constant

    return fill_constant(shape=[1], dtype="int32", value=len(input.shape))


def size(input):
    from .tensor import fill_constant

    return fill_constant(shape=[1], dtype="int64",
                         value=int(np.prod(
                             [d for d in input.shape if d != -1])))


def image_resize_short(input, out_short_len, resample="BILINEAR"):
    """Composite, reference nn.py image_resize_short: scale so the SHORT
    side equals out_short_len (static shapes at build time)."""
    h, w = int(input.shape[2]), int(input.shape[3])
    short = min(h, w)
    out_h = int(round(h * out_short_len / short))
    out_w = int(round(w * out_short_len / short))
    return _nn.image_resize(input, [out_h, out_w], resample)


def fsp_matrix(x, y):
    from ..contrib.slim.distillation import fsp_matrix as _fsp

    return _fsp(x, y)


def uniform_random_batch_size_like(input, shape, dtype="float32", min=-1.0,
                                   max=1.0, seed=0, input_dim_idx=0,
                                   output_dim_idx=0, name=None):
    helper = LayerHelper("uniform_random_batch_size_like", name=name)
    out = helper.create_variable_for_type_inference(dtype,
                                                    stop_gradient=True)
    sh = list(shape)
    sh[output_dim_idx] = -1  # batch-sized at runtime
    helper.append_op("uniform_random_batch_size_like",
                     inputs={"Input": input}, outputs={"Out": out},
                     attrs={"shape": sh, "min": min, "max": max,
                            "seed": seed, "dtype": dtype,
                            "input_dim_idx": input_dim_idx,
                            "output_dim_idx": output_dim_idx})
    return out


def gaussian_random_batch_size_like(input, shape, dtype="float32",
                                    mean=0.0, std=1.0, seed=0,
                                    input_dim_idx=0, output_dim_idx=0,
                                    name=None):
    helper = LayerHelper("gaussian_random_batch_size_like", name=name)
    out = helper.create_variable_for_type_inference(dtype,
                                                    stop_gradient=True)
    sh = list(shape)
    sh[output_dim_idx] = -1
    helper.append_op("gaussian_random_batch_size_like",
                     inputs={"Input": input}, outputs={"Out": out},
                     attrs={"shape": sh, "mean": mean, "std": std,
                            "seed": seed, "dtype": dtype,
                            "input_dim_idx": input_dim_idx,
                            "output_dim_idx": output_dim_idx})
    return out


# -- round-5 tail: deformable family, sequence tail, host callback ----------

def deformable_conv(input, offset, mask, num_filters, filter_size, stride=1,
                    padding=0, dilation=1, groups=1, deformable_groups=1,
                    im2col_step=64, param_attr=None, bias_attr=None,
                    modulated=True, name=None):
    """reference nn.py deformable_conv (v2 when modulated/mask given)."""
    helper = LayerHelper("deformable_conv", param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    pair = lambda v: list(v) if isinstance(v, (list, tuple)) else [v, v]
    k = pair(filter_size)
    num_channels = input.shape[1]
    std = (2.0 / (k[0] * k[1] * num_channels)) ** 0.5
    w = helper.create_parameter(
        helper.param_attr,
        shape=[num_filters, num_channels // groups] + k,
        dtype=input.dtype, default_initializer=Normal(0.0, std))
    pre_bias = helper.create_variable_for_type_inference(input.dtype)
    ins = {"Input": input, "Offset": offset, "Filter": w}
    if modulated and mask is not None:
        ins["Mask"] = mask
    helper.append_op("deformable_conv", inputs=ins,
                     outputs={"Output": pre_bias},
                     attrs={"strides": pair(stride),
                            "paddings": pair(padding),
                            "dilations": pair(dilation), "groups": groups,
                            "deformable_groups": deformable_groups,
                            "im2col_step": im2col_step})
    return helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)


def deformable_roi_pooling(input, rois, trans, no_trans=False,
                           spatial_scale=1.0, group_size=(1, 1),
                           pooled_height=1, pooled_width=1,
                           part_size=None, sample_per_part=1, trans_std=0.1,
                           position_sensitive=True, rois_batch_idx=None,
                           name=None):
    helper = LayerHelper("deformable_psroi_pooling", name=name)
    if not position_sensitive:
        raise NotImplementedError(
            "deformable_roi_pooling(position_sensitive=False): use "
            "roi_align + trans offsets; the PS path is the deformable "
            "detectors' configuration")
    gs = list(group_size)
    out_dim = input.shape[1] // (gs[0] * gs[1])
    ps = list(part_size) if part_size is not None \
        else [pooled_height, pooled_width]
    o = helper.create_variable_for_type_inference(input.dtype)
    cnt = helper.create_variable_for_type_inference(input.dtype,
                                                    stop_gradient=True)
    ins = {"Input": input, "ROIs": rois, "Trans": trans}
    if rois_batch_idx is not None:
        ins["RoisBatchIdx"] = rois_batch_idx
    helper.append_op("deformable_psroi_pooling",
                     inputs=ins,
                     outputs={"Output": o, "TopCount": cnt},
                     attrs={"no_trans": no_trans,
                            "spatial_scale": spatial_scale,
                            "output_dim": int(out_dim), "group_size": gs,
                            "pooled_height": pooled_height,
                            "pooled_width": pooled_width, "part_size": ps,
                            "sample_per_part": sample_per_part,
                            "trans_std": trans_std})
    return o


def psroi_pool(input, rois, output_channels, spatial_scale, pooled_height,
               pooled_width, rois_batch_idx=None, name=None):
    """``rois_batch_idx``: int tensor [R] mapping each ROI to its image in
    the batch (as roi_pool/roi_align accept); required when batch > 1."""
    return _one("psroi_pool", {"X": input, "ROIs": rois,
                               "RoisBatchIdx": rois_batch_idx},
                {"output_channels": output_channels,
                 "spatial_scale": spatial_scale,
                 "pooled_height": pooled_height,
                 "pooled_width": pooled_width}, name=name)


def prroi_pool(input, rois, output_channels=None, spatial_scale=1.0,
               pooled_height=1, pooled_width=1, batch_roi_nums=None,
               rois_batch_idx=None, name=None):
    """``batch_roi_nums``: int tensor [B] of ROI counts per image (the
    reference's prroi_pool signature) — counts must sum to the ROI count R,
    or trailing ROIs are silently mis-assigned (runtime data: unverifiable
    at trace time); ``rois_batch_idx``: int tensor [R] of per-ROI image
    indices. One of the two is required when batch > 1."""
    if batch_roi_nums is not None and rois_batch_idx is not None:
        raise ValueError(
            "prroi_pool: pass either batch_roi_nums or rois_batch_idx, "
            "not both — with conflicting values the op would silently "
            "follow rois_batch_idx")
    return _one("prroi_pool", {"X": input, "ROIs": rois,
                               "BatchRoINums": batch_roi_nums,
                               "RoisBatchIdx": rois_batch_idx},
                {"spatial_scale": spatial_scale,
                 "pooled_height": pooled_height,
                 "pooled_width": pooled_width}, name=name)


def merge_selected_rows(x, name=None):
    return _one("merge_selected_rows", {"X": x}, name=name)


def get_tensor_from_selected_rows(x, name=None):
    return _one("get_tensor_from_selected_rows", {"X": x}, name=name)


def continuous_value_model(input, cvm, use_cvm=True):
    return _one("cvm", {"X": input, "CVM": cvm}, {"use_cvm": use_cvm},
                out_slot="Y")


def sampled_softmax_with_cross_entropy(logits, label, num_samples,
                                       num_true=1, remove_accidental_hits=True,
                                       use_customized_samples=False,
                                       customized_samples=None,
                                       customized_probabilities=None,
                                       seed=0):
    if use_customized_samples:
        raise NotImplementedError(
            "sampled_softmax_with_cross_entropy(use_customized_samples): "
            "host-side alias tables; use the log-uniform sampler")
    out_loss, _, _ = _one(
        "sampled_softmax_with_cross_entropy",
        {"Logits": logits, "Label": label},
        {"num_samples": num_samples, "seed": seed,
         "remove_accidental_hits": remove_accidental_hits},
        out_slot="Loss",
        extra_outs=[("Samples", "int64"), ("Probabilities", None)])
    return out_loss


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """reference nn.py py_func: host python inside the graph, via
    jax.pure_callback. ``out`` vars carry the result shapes/dtypes (they
    must be created with concrete shapes). backward_func is unsupported —
    the callback is opaque to autodiff."""
    from ..ops.misc2 import register_py_func

    if backward_func is not None:
        raise NotImplementedError(
            "py_func(backward_func=...): the host callback is opaque to "
            "vjp; compute the backward inside the program instead")
    helper = LayerHelper("py_func")
    xs = list(x) if isinstance(x, (list, tuple)) else [x]
    outs = list(out) if isinstance(out, (list, tuple)) else [out]
    fid = register_py_func(func)
    helper.append_op(
        "py_func", inputs={"X": xs}, outputs={"Out": outs},
        attrs={"func_id": fid,
               "out_shapes": [[int(d) for d in v.shape] for v in outs],
               "out_dtypes": [str(v.dtype) for v in outs]})
    return out


def resize_trilinear(input, out_shape=None, scale=None, name=None,
                     align_corners=True):
    if out_shape is None:
        d, h, w = [int(s * scale) for s in input.shape[2:]]
    else:
        d, h, w = [int(v) for v in out_shape]
    return _one("trilinear_interp", {"X": input},
                {"out_d": d, "out_h": h, "out_w": w,
                 "align_corners": align_corners}, name=name)


def lstm_unit(x_t, hidden_t_prev, cell_t_prev, forget_bias=0.0,
              param_attr=None, bias_attr=None, name=None):
    """Composite (reference nn.py lstm_unit): one LSTM cell step built from
    fc over [x_t, h_prev] + the gate math."""
    concat_in = _nn.concat([x_t, hidden_t_prev], axis=1)
    hidden = hidden_t_prev.shape[-1]
    gates = _nn.fc(concat_in, 4 * hidden, param_attr=param_attr,
                   bias_attr=bias_attr)
    i, f, c_hat, o = _nn.split(gates, 4, dim=-1)
    f_act = _nn.sigmoid(_nn.scale(f, scale=1.0, bias=float(forget_bias)))
    new_cell = _nn.elementwise_add(
        _nn.elementwise_mul(f_act, cell_t_prev),
        _nn.elementwise_mul(_nn.sigmoid(i), _nn.tanh(c_hat)))
    new_hidden = _nn.elementwise_mul(_nn.sigmoid(o), _nn.tanh(new_cell))
    return new_hidden, new_cell


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    """reference nn.py autoincreased_step_counter: a persistable counter
    advanced by ``step`` each iteration, one counter per name."""
    from ..framework import default_main_program, default_startup_program

    name = counter_name or "@STEP_COUNTER@"
    main = default_main_program().global_block
    startup = default_startup_program().global_block
    if not main.has_var(name):
        main.create_var(name=name, shape=(1,), dtype="int64",
                        persistable=True, stop_gradient=True)
        startup.create_var(name=name, shape=(1,), dtype="int64",
                           persistable=True)
        startup.append_op("fill_constant", outputs={"Out": name},
                          attrs={"shape": [1], "dtype": "int64",
                                 "value": float(begin) - float(step)})
        main.prepend_op("increment", inputs={"X": name},
                        outputs={"Out": name},
                        attrs={"step": float(step),
                               "__op_role__": "lr_sched"})
    return main.var(name)


def adaptive_pool3d(input, pool_size, pool_type="max", require_index=False,
                    name=None):
    if require_index:
        raise NotImplementedError("adaptive_pool3d(require_index=True)")
    d, h, w = [int(v) for v in input.shape[2:]]
    ps = _triple(pool_size)
    if d % ps[0] or h % ps[1] or w % ps[2]:
        raise NotImplementedError(
            "adaptive_pool3d: input spatial dims must divide pool_size on "
            "TPU (static windows); pad the input or pick a divisor size")
    k = [d // ps[0], h // ps[1], w // ps[2]]
    return pool3d(input, pool_size=k, pool_type=pool_type, pool_stride=k)


def beam_search(pre_ids, pre_scores, ids, scores, beam_size, end_id,
                level=0, is_accumulated=True, name=None,
                return_parent_idx=False):
    """reference nn.py beam_search — wrapper over the beam_search op the
    seq2seq model drives inside While (models/seq2seq.py)."""
    helper = LayerHelper("beam_search", name=name)
    sel_ids = helper.create_variable_for_type_inference("int64")
    sel_scores = helper.create_variable_for_type_inference(
        pre_scores.dtype)
    parent = helper.create_variable_for_type_inference("int64",
                                                       stop_gradient=True)
    ins = {"pre_ids": pre_ids, "pre_scores": pre_scores, "scores": scores}
    if ids is not None:
        ins["ids"] = ids
    helper.append_op("beam_search", inputs=ins,
                     outputs={"selected_ids": sel_ids,
                              "selected_scores": sel_scores,
                              "parent_idx": parent},
                     attrs={"beam_size": beam_size, "end_id": end_id,
                            "level": level,
                            "is_accumulated": is_accumulated})
    if return_parent_idx:
        return sel_ids, sel_scores, parent
    return sel_ids, sel_scores


def beam_search_decode(ids, scores, beam_size, end_id, name=None):
    """reference nn.py beam_search_decode: backtrack the per-step beam
    arrays into full sentences."""
    helper = LayerHelper("beam_search_decode", name=name)
    s_ids = helper.create_variable_for_type_inference("int64")
    s_scores = helper.create_variable_for_type_inference("float32")
    helper.append_op("beam_search_decode",
                     inputs={"Ids": ids, "Scores": scores},
                     outputs={"SentenceIds": s_ids,
                              "SentenceScores": s_scores},
                     attrs={"beam_size": beam_size, "end_id": end_id})
    return s_ids, s_scores


def filter_by_instag(ins, ins_tag, filter_tag, is_lod=True):
    raise NotImplementedError(
        "filter_by_instag selects variable-size row subsets at runtime — "
        "dynamic shapes XLA cannot compile. Filter in the data pipeline "
        "(reader decorators) or mask rows with sequence_mask instead.")


def fused_decode_attention(q, k_new, v_new, cache_k, cache_v, positions,
                           scale=0.0, page_size=128, slot_mask=None,
                           window=0, name=None, whole_chunk=False,
                           sink=None):
    """One decode/verify chunk with the KV append fused in
    (ops/generation.py). q/k_new/v_new: [B, H, C, D] (C == 1 is the
    classic decode step; C <= 8 rides the chunk kernel); cache_k/cache_v:
    persistable paged caches [B, H, S_max, D]; positions: [B, 1] int —
    each sequence's length before this chunk. Query row i attends keys at
    positions < pos + i + 1 (causal within the chunk), or, with
    ``whole_chunk``, every row those at positions < pos + C (the chunk is
    a block-diffusion block, its rows see one another). ``slot_mask``
    [B, 1] (optional) gates the rows the append writes, so un-masked
    sequences' caches stay bit-untouched — the chunked-prefill /
    speculative dispatches run a subset of slots.
    The updated caches are written BACK INTO the cache vars (the single
    read+write op shape the donation proof needs), and the attended
    context [B, H, C, D] is returned. scale=0.0 means 1/sqrt(D).
    ``q`` may carry a whole multiple of the caches' heads (grouped-query
    attention). ``window`` > 0: the caches are a ring of
    ``min(window, max_seq)`` rows holding the last positions (C == 1).
    ``v_new``/``cache_v`` may be ``Dv`` wide beside keys of ``D`` (the
    context is then [B, H, C, Dv]); ``sink`` [H] float32 joins each query
    head's softmax as one more column with no value."""
    helper = LayerHelper("fused_decode_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    inputs = {"Q": q, "KNew": k_new, "VNew": v_new,
              "CacheK": cache_k, "CacheV": cache_v,
              "Positions": positions}
    if slot_mask is not None:
        inputs["SlotMask"] = slot_mask
    if sink is not None:
        inputs["Sink"] = sink
    attrs = {"scale": float(scale), "page_size": int(page_size)}
    if window:
        attrs["window"] = int(window)
    if whole_chunk:
        attrs["whole_chunk"] = True
    helper.append_op(
        "fused_decode_attention",
        inputs=inputs,
        outputs={"Out": out, "CacheKOut": cache_k, "CacheVOut": cache_v},
        attrs=attrs)
    return out


def rotary_embedding(x, positions, theta=10000.0, rotary_dim=0,
                     pairing="interleaved", yarn=None, name=None):
    """Rotary position embedding (ops/moe.py): ``x`` [B, heads, S, D],
    ``positions`` [B, S] int; same shape and type out. The first
    ``rotary_dim`` dims of a head turn (0: all of them), as ``interleaved``
    pairs ``(2i, 2i+1)`` or rotate-``half`` pairs ``(i, i + rotary_dim/2)``.
    ``yarn``: a model's ``rope_scaling`` of type ``yarn`` (``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``mscale``, ``mscale_all_dim``) makes the frequencies YaRN's at every
    position; None leaves the plain ones."""
    helper = LayerHelper("rotary_embedding", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    attrs = {"theta": float(theta)}
    if rotary_dim:
        attrs["rotary_dim"] = int(rotary_dim)
    if pairing != "interleaved":
        attrs["pairing"] = str(pairing)
    if yarn:
        attrs.update(
            yarn_factor=float(yarn["factor"]),
            yarn_original_max_position=int(
                yarn["original_max_position_embeddings"]),
            yarn_beta_fast=float(yarn.get("beta_fast", 32.0)),
            yarn_beta_slow=float(yarn.get("beta_slow", 1.0)),
            yarn_mscale=float(yarn.get("mscale", 1.0)),
            yarn_mscale_all_dim=float(yarn.get("mscale_all_dim", 0.0)))
    helper.append_op("rotary_embedding",
                     inputs={"X": x, "Positions": positions},
                     outputs={"Out": out}, attrs=attrs)
    return out


def rms_norm(x, scale, epsilon=1e-6, zero_centered=False, name=None):
    """``x / sqrt(mean(x^2) + epsilon) * s`` over the last dim in f32
    (ops/gdn.py); ``s`` is ``scale`` [D], or ``1 + scale`` where
    ``zero_centered``."""
    helper = LayerHelper("rms_norm", name=name)
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op("rms_norm", inputs={"X": x, "Scale": scale},
                     outputs={"Out": out},
                     attrs={"epsilon": float(epsilon),
                            "zero_centered": bool(zero_centered)})
    return out


def gated_delta_rule(x, conv_w, a, b, a_log, dt_bias, state, conv_state,
                     mask, num_k_heads, num_v_heads, head_k_dim, head_v_dim,
                     mode="scan", slots=None, slot_mask=None, name=None):
    """The gated delta rule of a Gated DeltaNet layer behind its causal
    convolution (ops/gdn.py). ``mode="scan"``: ``x`` [R, S, C] whole
    prompts, ``mask`` [R, S]; sequence ``i`` overwrites the state of slot
    ``slots[i]`` where ``slot_mask[i]`` > 0. ``mode="step"``: ``x``
    [slots, 1, C], ``mask`` [slots, 1] the decode gate. ``state`` and
    ``conv_state`` are written in place. Returns ``(out [R, S, Hv Dv] f32,
    stats [1] int32: rows the rule advanced)``."""
    helper = LayerHelper("gated_delta_rule", name=name)
    out = helper.create_variable_for_type_inference("float32")
    stats = helper.create_variable_for_type_inference("int32",
                                                      stop_gradient=True)
    inputs = {"X": x, "ConvW": conv_w, "A": a, "B": b, "ALog": a_log,
              "DtBias": dt_bias, "State": state, "ConvState": conv_state,
              "Mask": mask}
    if slots is not None:
        inputs["Slots"] = slots
    if slot_mask is not None:
        inputs["SlotMask"] = slot_mask
    helper.append_op(
        "gated_delta_rule", inputs=inputs,
        outputs={"Out": out, "StateOut": state, "ConvStateOut": conv_state,
                 "Stats": stats},
        attrs={"mode": str(mode), "num_k_heads": int(num_k_heads),
               "num_v_heads": int(num_v_heads),
               "head_k_dim": int(head_k_dim), "head_v_dim": int(head_v_dim)})
    return out, stats


def mamba2_scan(x, conv_w, conv_b, dt, a_log, dt_bias, d, state, conv_state,
                mask, num_heads, head_dim, state_dim, mode="scan", chunk=256,
                slots=None, slot_mask=None, name=None):
    """The selective scan of a Mamba-2 mixer behind its causal convolution
    (ops/ssd.py). ``mode="scan"``: ``x`` [R, S, H P + 2 N] whole prompts
    (``[x | B | C]`` before the convolution), ``dt`` [R, S, H], ``mask``
    [R, S]; sequence ``i`` overwrites the state of slot ``slots[i]`` where
    ``slot_mask[i]`` > 0. ``mode="step"``: ``x`` [slots, 1, C], ``mask``
    [slots, 1] the decode gate. ``state`` and ``conv_state`` are written in
    place. Returns ``(out [R, S, H P] f32: y + D x, stats [1] int32: rows
    the scan advanced)``."""
    helper = LayerHelper("mamba2_scan", name=name)
    out = helper.create_variable_for_type_inference("float32")
    stats = helper.create_variable_for_type_inference("int32",
                                                      stop_gradient=True)
    inputs = {"X": x, "ConvW": conv_w, "ConvB": conv_b, "Dt": dt,
              "ALog": a_log, "DtBias": dt_bias, "D": d, "State": state,
              "ConvState": conv_state, "Mask": mask}
    if slots is not None:
        inputs["Slots"] = slots
    if slot_mask is not None:
        inputs["SlotMask"] = slot_mask
    helper.append_op(
        "mamba2_scan", inputs=inputs,
        outputs={"Out": out, "StateOut": state, "ConvStateOut": conv_state,
                 "Stats": stats},
        attrs={"mode": str(mode), "num_heads": int(num_heads),
               "head_dim": int(head_dim), "state_dim": int(state_dim),
               "chunk": int(chunk)})
    return out, stats


def latent_attention(q, c, k_rope, kv_b_w, cache, positions, nope_dim,
                     mode="decode", page_size=128, slot_mask=None,
                     slots=None, scale=None, name=None):
    """Multi-head latent attention over a latent cache
    (ops/latent_attention.py). ``q`` [B, heads, S, dn + dr] (``nope_dim`` =
    dn; the rotary part turned), ``c`` [B, S, dc] and ``k_rope`` [B, S, dr]
    the rows to append, ``kv_b_w`` [dc, heads x (dn + dv)] the
    up-projection. ``cache`` [slots, 1, S_max, W] (rows ``[c | k_rope |
    0]``, ``W`` whole lane tiles) is written in place. ``mode="prefill"``:
    whole prompts written at row 0 of the slots ``slots`` names (where
    ``slot_mask`` > 0), keys and values expanded, the flash kernel.
    ``mode="decode"``: one row a slot at ``positions`` under the gate
    ``slot_mask``, the up-projection absorbed, attention over the latent
    rows. ``scale``: the softmax scale (None: ``(dn + dr)^-1/2``).
    Returns ``(out [B, heads, S, dv], stats [1] int32: cache rows the
    attention read)``."""
    helper = LayerHelper("latent_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    stats = helper.create_variable_for_type_inference("int32",
                                                      stop_gradient=True)
    inputs = {"Q": q, "C": c, "KRope": k_rope, "KVBW": kv_b_w,
              "Cache": cache, "Positions": positions}
    if slot_mask is not None:
        inputs["SlotMask"] = slot_mask
    if slots is not None:
        inputs["Slots"] = slots
    attrs = {"mode": str(mode), "nope_dim": int(nope_dim),
             "page_size": int(page_size)}
    if scale:
        attrs["scale"] = float(scale)
    helper.append_op(
        "latent_attention", inputs=inputs,
        outputs={"Out": out, "CacheOut": cache, "Stats": stats},
        attrs=attrs)
    return out, stats


def hyper_connection_read(x, proj, alpha, bias, sinkhorn_iters=20, eps=1e-6,
                          norm_eps=1e-6, clamp_min=-30.0, clamp_max=30.0,
                          name=None):
    """What a sublayer reads of an ``n``-stream residual path, and the
    coefficients its write needs (ops/hyper_connection.py: manifold-
    constrained hyper-connections). ``x`` [B, S, n, C] f32; ``proj``
    [n (n + 2), n C] (rows ``[P_pre^T | P_post^T | P_res^T]``), ``alpha``
    [3] and ``bias`` [n (n + 2)] the sublayer's parameters, f32. Returns
    ``(u [B, S, C], h_post [B, S, n], h_res [B, S, n, n], stats [2] f32:
    token rows mixed, the largest |row or column sum - 1| of an h_res)``:
    ``u = sigmoid(..) X``, ``h_post = 2 sigmoid(..)``, ``h_res`` the
    ``sinkhorn_iters`` Sinkhorn rounds (denominators ``sum + eps``) of
    ``exp(clamp(..))``, all from the RMS-normed flattened ``x``
    (``norm_eps``), per token."""
    helper = LayerHelper("hyper_connection_read", name=name)
    mk = lambda: helper.create_variable_for_type_inference("float32")
    u, post, res = mk(), mk(), mk()
    stats = helper.create_variable_for_type_inference("float32",
                                                      stop_gradient=True)
    helper.append_op(
        "hyper_connection_read",
        inputs={"X": x, "Proj": proj, "Alpha": alpha, "Bias": bias},
        outputs={"Out": u, "HPost": post, "HRes": res, "Stats": stats},
        attrs={"sinkhorn_iters": int(sinkhorn_iters), "eps": float(eps),
               "norm_eps": float(norm_eps), "clamp_min": float(clamp_min),
               "clamp_max": float(clamp_max)})
    return u, post, res, stats


def hyper_connection_write(x, y, h_post, h_res, name=None):
    """What a sublayer writes back into an ``n``-stream residual path
    (ops/hyper_connection.py): ``h_res X + h_post^T y`` [B, S, n, C] from
    the stream ``x`` [B, S, n, C], the sublayer's output ``y`` [B, S, C]
    and the coefficients ``hyper_connection_read`` gave, all f32."""
    return _one("hyper_connection_write",
                {"X": x, "Y": y, "HPost": h_post, "HRes": h_res},
                dtype="float32", name=name)


def moe_experts(x, router_w, gate_w, up_w, down_w, num_experts, top_k,
                expert_offset=0, token_mask=None, score_fn="sigmoid",
                select_bias=None, route_scale=1.0, name=None):
    """The routed experts a chip holds (ops/moe.py): routes ``x`` [..., H]
    (f32) over all ``num_experts`` by ``router_w`` [H, num_experts] and
    returns ``(out, stats)``: the part of the routed sum that the experts
    ``expert_offset .. expert_offset + gate_w.shape[0] - 1`` give (f32),
    and an int32 vector of the assignments each of them received, all
    assignments made, and assignments dropped (always 0). ``token_mask``
    (``x``'s leading shape, > 0 = a real token) keeps padding out of the
    routing. ``score_fn``: ``sigmoid`` of each expert's logit, or
    ``softmax`` over all of them. ``select_bias`` [num_experts] f32: the
    experts are chosen by ``score + bias`` while the weights stay the
    unbiased scores; ``route_scale`` multiplies the normalised weights."""
    helper = LayerHelper("moe_experts", name=name)
    out = helper.create_variable_for_type_inference("float32")
    stats = helper.create_variable_for_type_inference("int32",
                                                      stop_gradient=True)
    inputs = {"X": x, "RouterW": router_w, "GateW": gate_w, "UpW": up_w,
              "DownW": down_w}
    if token_mask is not None:
        inputs["TokenMask"] = token_mask
    if select_bias is not None:
        inputs["SelectBias"] = select_bias
    attrs = {"num_experts": int(num_experts), "top_k": int(top_k),
             "expert_offset": int(expert_offset), "score_fn": str(score_fn)}
    if route_scale != 1.0:
        attrs["route_scale"] = float(route_scale)
    helper.append_op("moe_experts", inputs=inputs,
                     outputs={"Out": out, "Stats": stats}, attrs=attrs)
    return out, stats


def kv_cache_append(cache, new, positions, slot_mask=None, slots=None,
                    name=None):
    """Bulk KV write into a paged cache var (ops/generation.py): ``new``
    [B, H, L, D] lands at per-sequence ``positions`` [B, 1]; with
    ``slot_mask`` [B, 1] only masked sequences' rows change (the
    continuous-batching refill). With ``slots`` [B', 1], ``new`` carries
    B' <= B sequences and sequence ``i`` goes to the cache's row
    ``slots[i]``. Writes in place into ``cache`` (returns the same var)."""
    helper = LayerHelper("kv_cache_append", name=name)
    inputs = {"Cache": cache, "New": new, "Positions": positions}
    if slot_mask is not None:
        inputs["SlotMask"] = slot_mask
    if slots is not None:
        inputs["Slots"] = slots
    helper.append_op("kv_cache_append", inputs=inputs,
                     outputs={"Out": cache})
    return cache


def kv_cache_fold(cache, new, lengths, slot_mask=None, slots=None,
                  name=None):
    """A whole prompt past a window layer's ring, folded into it
    (ops/generation.py): ``new`` [R, H, S, D] of ``lengths`` [R, 1] tokens
    into ``cache`` [B, H, W, D] with ``W < S``; ring row ``r`` takes the
    last position ``p < length`` with ``p % W == r``, in the slot
    ``slots[i]`` names, where ``slot_mask[i]`` > 0. Writes in place into
    ``cache``; returns ``(cache, stats [2] int32: rows kept, rows
    dropped)``."""
    helper = LayerHelper("kv_cache_fold", name=name)
    stats = helper.create_variable_for_type_inference("int32",
                                                      stop_gradient=True)
    inputs = {"Cache": cache, "New": new, "Lengths": lengths}
    if slot_mask is not None:
        inputs["SlotMask"] = slot_mask
    if slots is not None:
        inputs["Slots"] = slots
    helper.append_op("kv_cache_fold", inputs=inputs,
                     outputs={"Out": cache, "Stats": stats})
    return cache, stats


def slot_assign(x, slots, updates, mask=None, name=None):
    """``x`` [B, ...] with row ``slots[i]`` replaced by ``updates[i]``
    wherever ``mask[i]`` > 0 (ops/generation.py); written in place into
    ``x`` (returns the same var)."""
    helper = LayerHelper("slot_assign", name=name)
    inputs = {"X": x, "Slots": slots, "Updates": updates}
    if mask is not None:
        inputs["Mask"] = mask
    helper.append_op("slot_assign", inputs=inputs, outputs={"Out": x})
    return x


def sequence_gather(x, index, name=None):
    """Out[b] = x[b, index[b]] — gather one position per sequence along
    axis 1 (x: [B, S, ...], index: [B, 1] int, clamped into range)."""
    helper = LayerHelper("sequence_gather", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("sequence_gather", inputs={"X": x, "Index": index},
                     outputs={"Out": out})
    return out


def spec_accept(sampled, drafts, start, name=None):
    """Speculative-decoding accept rule (ops/generation.py): from the
    target's per-position tokens ``sampled`` [B, k] and the draft's
    proposals ``drafts`` [B, k-1], accept the longest agreeing prefix m
    plus the target's bonus token. Returns ``(accept_len [B,1],
    new_tok [B,1], new_pos [B,1])`` — all int64; ``new_pos = start + m +
    1`` is the committed sequence length."""
    helper = LayerHelper("spec_accept", name=name)
    accept = helper.create_variable_for_type_inference("int64")
    new_tok = helper.create_variable_for_type_inference("int64")
    new_pos = helper.create_variable_for_type_inference("int64")
    helper.append_op("spec_accept",
                     inputs={"Sampled": sampled, "Drafts": drafts,
                             "Start": start},
                     outputs={"AcceptLen": accept, "NewTok": new_tok,
                              "NewPos": new_pos})
    return accept, new_tok, new_pos


def block_seed(prompt_ids, prompt_len, block_length, mask_id, name=None):
    """What a block-diffusion prefill leaves for the decode phase
    (ops/block_diffusion.py): from ``prompt_ids`` [R, S] and ``prompt_len``
    [R, 1] = P, ``(tokens [R, L], revealed_at [R, L], start [R, 1], seated
    [R, S] f32)``: the first block with the prompt's ``P % L`` left-over
    tokens known, the row it starts at, ``(P // L) * L``, and a mask of
    the rows before it."""
    helper = LayerHelper("block_seed", name=name)
    mk = helper.create_variable_for_type_inference
    toks, at, start = (mk("int64", stop_gradient=True) for _ in range(3))
    seated = mk("float32", stop_gradient=True)
    helper.append_op("block_seed",
                     inputs={"PromptIds": prompt_ids,
                             "PromptLen": prompt_len},
                     outputs={"Tokens": toks, "RevealedAt": at,
                              "Start": start, "Seated": seated},
                     attrs={"block_length": int(block_length),
                            "mask_id": int(mask_id)})
    return toks, at, start, seated


def block_positions(start, block_length, name=None):
    """``start`` [B, 1] int -> [B, L]: the rows of the block that starts
    there (ops/block_diffusion.py)."""
    helper = LayerHelper("block_positions", name=name)
    out = helper.create_variable_for_type_inference(start.dtype,
                                                    stop_gradient=True)
    helper.append_op("block_positions", inputs={"Start": start},
                     outputs={"Out": out},
                     attrs={"block_length": int(block_length)})
    return out


def block_reveal(logits, tokens, revealed_at, start, step, active, mask_id,
                 denoising_steps, max_seq, name=None):
    """The end of one block-diffusion decode forward
    (ops/block_diffusion.py): a block with masked positions reveals its
    most confident ones, a block with none commits and moves on. The
    state (``tokens``, ``revealed_at`` [B, L]; ``start``, ``step`` [B, 1])
    is written in place under the gate ``active``. Returns ``(emitted
    [B, L], emitted_at [B, L], emit_count [B, 1])``: the tokens a commit
    yields, the forward of the block each was revealed at, and how many
    they are (0 on a denoise forward)."""
    helper = LayerHelper("block_reveal", name=name)
    mk = helper.create_variable_for_type_inference
    emitted, emitted_at, count = (mk("int64", stop_gradient=True)
                                  for _ in range(3))
    helper.append_op(
        "block_reveal",
        inputs={"Logits": logits, "Tokens": tokens,
                "RevealedAt": revealed_at, "Start": start, "Step": step,
                "Active": active},
        outputs={"TokensOut": tokens, "RevealedAtOut": revealed_at,
                 "StartOut": start, "StepOut": step, "Emitted": emitted,
                 "EmittedAt": emitted_at, "EmitCount": count},
        attrs={"mask_id": int(mask_id),
               "denoising_steps": int(denoising_steps),
               "max_seq": int(max_seq)})
    return emitted, emitted_at, count


def sample_token(logits, strategy="greedy", temperature=1.0, top_k=0,
                 name=None):
    """Next-token selection from [B, V] logits -> [B, 1] int64
    (ops/generation.py): 'greedy' argmax, or seeded 'sample' with
    temperature and optional top-k truncation — deterministic for a fixed
    program.random_seed."""
    helper = LayerHelper("sample_token", name=name)
    out = helper.create_variable_for_type_inference("int64")
    helper.append_op("sample_token", inputs={"Logits": logits},
                     outputs={"Out": out},
                     attrs={"strategy": strategy,
                            "temperature": float(temperature),
                            "top_k": int(top_k)})
    return out
