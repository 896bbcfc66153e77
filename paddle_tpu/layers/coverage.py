"""Layer-DSL coverage: wrappers for every remaining reference layer name.

Reference analog: the tail of ``python/paddle/fluid/layers/nn.py`` /
``detection.py`` / ``tensor.py`` / ``io.py`` / ``layer_function_generator.py``
— the ops behind these wrappers already exist in this build (see
ops/parity_ops.py, ops/detection_ops.py, ops/vision_ops.py,
ops/coverage_ops.py); this module closes the name-for-name layer surface so
`fluid.layers.<anything the reference exports>` resolves (tested by
tests/test_api_parity.py::test_fluid_layers_names_exist).

Wrappers are table-driven where the op is a plain slots+attrs emission, and
hand-written where the reference layer is a composite (detection_output,
ssd_loss, multi_box_head, image_resize) or creates state
(autoincreased_step_counter, py_reader).
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from ..core.program import Variable, default_main_program, default_startup_program
from ..layer_helper import LayerHelper
from . import tensor as _tensor


# single-output ops whose result shape equals the (first) input's — lets
# downstream layers (fc etc.) keep best-effort shape metadata
_SAME_SHAPE_OPS = {
    "brelu", "selu", "stanh", "affine_channel", "label_smooth",
    "random_crop", "ones_like", "shuffle_channel", "temporal_shift",
    "add_position_encoding", "grid_sampler", "reverse", "lod_reset",
    "pixel_shuffle_inverse", "scale",
}


def _emit(op_type, ins, attrs=None, outs=("Out",), dtype=None, name=None,
          out_shape=None):
    """Append one op; ins: {slot: Variable | [Variable] | None}.
    `out_shape` (for single-output calls) sets the best-effort static shape
    metadata of the result; same-shape ops inherit the input's."""
    helper = LayerHelper(op_type, name=name)
    in_map, first = {}, None
    for slot, vs in ins.items():
        if vs is None:
            continue
        vs = vs if isinstance(vs, (list, tuple)) else [vs]
        if vs and first is None:
            first = vs[0]
        in_map[slot] = [v.name for v in vs]
    if out_shape is None and op_type in _SAME_SHAPE_OPS             and first is not None:
        out_shape = first.shape
    out_vars = {s: helper.create_variable_for_type_inference(
        dtype or (first.dtype if first is not None else "float32"),
        shape=out_shape if len(outs) == 1 else None)
        for s in outs}
    helper.append_op(type=op_type, inputs=in_map,
                     outputs={s: [v.name] for s, v in out_vars.items()},
                     attrs=attrs or {})
    if len(outs) == 1:
        return out_vars[outs[0]]
    return tuple(out_vars[s] for s in outs)


# -- activations / simple elementwise ---------------------------------------

def brelu(x, t_min=0.0, t_max=24.0, name=None):
    return _emit("brelu", {"X": x}, {"t_min": t_min, "t_max": t_max}, name=name)


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772, name=None):
    return _emit("selu", {"X": x}, {"scale": scale, "alpha": alpha}, name=name)


def stanh(x, scale_a=0.67, scale_b=1.7159, name=None):
    return _emit("stanh", {"X": x},
                 {"scale_a": scale_a, "scale_b": scale_b}, name=name)


def soft_relu(x, threshold=40.0, name=None):
    """activation_op.cc SoftRelu: log(1 + exp(clip(x)))."""
    from . import nn as _nn
    from . import ops as _ops
    clipped = _nn.clip(x, -threshold, threshold)
    return _ops.log(_emit("scale", {"X": _ops.exp(clipped)},
                          {"scale": 1.0, "bias": 1.0}))


def maxout(x, groups, name=None, axis=1):
    shp = None
    if x.shape is not None:
        shp = list(x.shape)
        shp[axis] = shp[axis] // groups if shp[axis] and shp[axis] > 0 else shp[axis]
    return _emit("maxout", {"X": x}, {"groups": groups, "axis": axis},
                 name=name, out_shape=tuple(shp) if shp else None)


# -- losses -----------------------------------------------------------------

def bpr_loss(input, label, name=None):
    return _emit("bpr_loss", {"X": input, "Label": label}, outs=("Y",),
                 name=name)


def center_loss(input, label, num_classes, alpha, param_attr=None,
                update_center=True):
    helper = LayerHelper("center_loss")
    centers = helper.create_parameter(
        param_attr, shape=[num_classes, input.shape[-1]], dtype=input.dtype)
    rate = _tensor.fill_constant([1], "float32", alpha)
    loss, diff, cout = _emit(
        "center_loss",
        {"X": input, "Label": label, "Centers": centers,
         "CenterUpdateRate": rate},
        {"need_update": update_center},
        outs=("Loss", "SampleCenterDiff", "CentersOut"))
    return loss


def huber_loss(input, label, delta):
    out, _ = _emit("huber_loss", {"X": input, "Y": label}, {"delta": delta},
                   outs=("Out", "Residual"))
    return out


def kldiv_loss(x, target, reduction="mean", name=None):
    return _emit("kldiv_loss", {"X": x, "Target": target},
                 {"reduction": reduction}, name=name)


def log_loss(input, label, epsilon=1e-4, name=None):
    return _emit("log_loss", {"Predicted": input, "Labels": label},
                 {"epsilon": epsilon}, outs=("Loss",), name=name)


def margin_rank_loss(label, left, right, margin=0.1, name=None):
    out, _ = _emit("margin_rank_loss",
                   {"Label": label, "X1": left, "X2": right},
                   {"margin": margin}, outs=("Out", "Activated"), name=name)
    return out


def rank_loss(label, left, right, name=None):
    return _emit("rank_loss", {"Label": label, "Left": left, "Right": right},
                 name=name)


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    ins = {"X": x, "Y": y, "InsideWeight": inside_weight,
           "OutsideWeight": outside_weight}
    out, _ = _emit("smooth_l1_loss", ins,
                   {"sigma": 1.0 if sigma is None else sigma},
                   outs=("Out", "Diff"))
    return out


def teacher_student_sigmoid_loss(input, label, soft_max_up_bound=15.0,
                                 soft_max_lower_bound=-15.0):
    return _emit("teacher_student_sigmoid_loss",
                 {"X": input, "Label": label},
                 {"soft_max_up_bound": soft_max_up_bound,
                  "soft_max_lower_bound": soft_max_lower_bound}, outs=("Y",))


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    """nn.py npair_loss: cross entropy over anchor·positiveᵀ similarities +
    l2 on embeddings (composite of existing layers, as in the reference)."""
    from . import nn as _nn
    from .reduce import reduce_mean, reduce_sum
    from . import ops as _ops
    labels = _tensor.reshape(labels, [-1, 1])
    labf = _tensor.cast(labels, "float32")
    same = _emit("equal", {"X": labf,
                           "Y": _tensor.transpose(labf, [1, 0])}, {},
                 dtype="bool")
    same = _tensor.cast(same, "float32")
    norm = _emit("scale", {"X": same}, {"scale": 1.0})
    tgt = _emit("elementwise_div", {"X": same, "Y": reduce_sum(norm, dim=1,
                                                              keep_dim=True)})
    sim = _nn.matmul(anchor, positive, transpose_y=True)
    ce = _nn.softmax_with_cross_entropy(sim, tgt, soft_label=True)
    l2 = reduce_sum(_ops.square(anchor)) + reduce_sum(_ops.square(positive))
    l2 = _emit("scale", {"X": l2}, {"scale": l2_reg})
    return _emit("elementwise_add", {"X": reduce_mean(ce), "Y": l2})


def dice_loss(input, label, epsilon=1e-5):
    """nn.py dice_loss composite: 1 − 2·|X∩Y| / (|X|+|Y|)."""
    from .reduce import reduce_sum
    label = _tensor.cast(label, input.dtype)
    inter = reduce_sum(_emit("elementwise_mul", {"X": input, "Y": label}))
    union = _emit("elementwise_add", {"X": reduce_sum(input),
                                      "Y": reduce_sum(label)})
    num = _emit("scale", {"X": inter}, {"scale": 2.0, "bias": epsilon})
    den = _emit("scale", {"X": union}, {"scale": 1.0, "bias": epsilon})
    frac = _emit("elementwise_div", {"X": num, "Y": den})
    return _emit("scale", {"X": frac}, {"scale": -1.0, "bias": 1.0})


def fsp_matrix(x, y):
    """nn.py fsp_matrix (flow of solution procedure, distillation): per
    sample, xᵀ·y over spatial positions / (H·W)."""
    from . import nn as _nn
    b, cx, h, w = x.shape
    cy = y.shape[1]
    xf = _tensor.reshape(x, [b, cx, h * w])
    yf = _tensor.transpose(_tensor.reshape(y, [b, cy, h * w]), [0, 2, 1])
    return _emit("scale", {"X": _nn.matmul(xf, yf)}, {"scale": 1.0 / (h * w)})


# -- vision / misc transforms ----------------------------------------------

def affine_channel(x, scale=None, bias=None, data_layout="NCHW", name=None,
                   act=None):
    helper = LayerHelper("affine_channel", name=name)
    out = _emit("affine_channel", {"X": x, "Scale": scale, "Bias": bias},
                {"data_layout": data_layout})
    return helper.append_activation(out, act)


def affine_grid(theta, out_shape, name=None):
    ins = {"Theta": theta}
    attrs = {}
    if isinstance(out_shape, Variable):
        ins["OutputShape"] = out_shape
    else:
        attrs["output_shape"] = list(out_shape)
    return _emit("affine_grid", ins, attrs, outs=("Output",), name=name)


def grid_sampler(x, grid, name=None):
    return _emit("grid_sampler", {"X": x, "Grid": grid}, outs=("Output",),
                 name=name)


def add_position_encoding(input, alpha, beta, name=None):
    return _emit("add_position_encoding", {"X": input},
                 {"alpha": alpha, "beta": beta}, name=name)


def crop(x, shape=None, offsets=None, name=None):
    attrs = {}
    if shape is not None and not isinstance(shape, Variable):
        attrs["shape"] = list(shape)
    if offsets is not None and not isinstance(offsets, Variable):
        attrs["offsets"] = list(offsets)
    return _emit("crop", {"X": x}, attrs, name=name)


def pad(x, paddings, pad_value=0.0, name=None):
    shp = None
    if x.shape is not None and len(paddings) == 2 * len(x.shape):
        shp = tuple(
            None if d is None or int(d) < 0
            else int(d) + paddings[2 * i] + paddings[2 * i + 1]
            for i, d in enumerate(x.shape))
    return _emit("pad", {"X": x},
                 {"paddings": list(paddings), "pad_value": pad_value},
                 name=name, out_shape=shp)


def pad2d(input, paddings=(0, 0, 0, 0), mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    shp = None
    if input.shape is not None and len(input.shape) == 4 \
            and not isinstance(paddings, Variable):
        t, b, l, r = paddings
        n, c, h, w = input.shape
        shp = (n, c, (h + t + b) if h and h > 0 else h,
               (w + l + r) if w and w > 0 else w)
    return _emit("pad2d", {"X": input},
                 {"paddings": list(paddings), "mode": mode,
                  "pad_value": pad_value, "data_format": data_format},
                 name=name, out_shape=shp)


def pad_constant_like(x, y, pad_value=0.0, name=None):
    return _emit("pad_constant_like", {"X": x, "Y": y},
                 {"pad_value": pad_value}, name=name)


def pixel_shuffle(x, upscale_factor):
    return _emit("pixel_shuffle", {"X": x},
                 {"upscale_factor": upscale_factor})


def shuffle_channel(x, group, name=None):
    return _emit("shuffle_channel", {"X": x}, {"group": group}, name=name)


def space_to_depth(x, blocksize, name=None):
    shp = None
    if x.shape is not None and len(x.shape) == 4:
        n, c, h, w = x.shape
        bs = int(blocksize)
        shp = (n, None if c is None else c * bs * bs,
               None if h is None else h // bs,
               None if w is None else w // bs)
    return _emit("space_to_depth", {"X": x}, {"blocksize": blocksize},
                 name=name, out_shape=shp)


def temporal_shift(x, seg_num, shift_ratio=0.25, name=None):
    return _emit("temporal_shift", {"X": x},
                 {"seg_num": seg_num, "shift_ratio": shift_ratio}, name=name)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    def _l(v, n=2):
        return list(v) if isinstance(v, (list, tuple)) else [v] * n
    return _emit("unfold", {"X": x},
                 {"kernel_sizes": _l(kernel_sizes), "strides": _l(strides),
                  "paddings": _l(paddings, 4) if isinstance(paddings, (list, tuple)) and len(paddings) == 4 else _l(paddings),
                  "dilations": _l(dilations)}, outs=("Y",), name=name)


def similarity_focus(input, axis, indexes, name=None):
    return _emit("similarity_focus", {"X": input},
                 {"axis": axis, "indexes": list(indexes)}, name=name)


def random_crop(x, shape, seed=None):
    return _emit("random_crop", {"X": x}, {"shape": list(shape)})


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    out, _ = _emit("lrn", {"X": input},
                   {"n": n, "k": k, "alpha": alpha, "beta": beta},
                   outs=("Out", "MidOut"), name=name)
    return out


def image_resize(input, out_shape=None, scale=None, name=None,
                 resample="BILINEAR", actual_shape=None, align_corners=True,
                 align_mode=1):
    """nn.py image_resize → {bilinear,nearest,trilinear}_interp ops."""
    op = {"BILINEAR": "bilinear_interp", "NEAREST": "nearest_interp",
          "TRILINEAR": "trilinear_interp"}.get(resample.upper())
    if op is None:
        raise ValueError(f"resample must be BILINEAR/NEAREST/TRILINEAR, "
                         f"got {resample}")
    attrs = {"align_corners": align_corners, "align_mode": align_mode}
    if out_shape is not None:
        attrs["out_h"], attrs["out_w"] = int(out_shape[0]), int(out_shape[1])
        if len(out_shape) == 3:
            attrs["out_d"] = int(out_shape[0])
            attrs["out_h"], attrs["out_w"] = int(out_shape[1]), int(out_shape[2])
    elif scale is not None:
        attrs["scale"] = float(scale)
    return _emit(op, {"X": input}, attrs, name=name)


def resize_bilinear(input, out_shape=None, scale=None, name=None,
                    actual_shape=None, align_corners=True, align_mode=1):
    return image_resize(input, out_shape, scale, name, "BILINEAR",
                        actual_shape, align_corners, align_mode)


def resize_nearest(input, out_shape=None, scale=None, name=None,
                   actual_shape=None, align_corners=True):
    return image_resize(input, out_shape, scale, name, "NEAREST",
                        actual_shape, align_corners)


def resize_trilinear(input, out_shape=None, scale=None, name=None,
                     actual_shape=None, align_corners=True, align_mode=1):
    return image_resize(input, out_shape, scale, name, "TRILINEAR",
                        actual_shape, align_corners, align_mode)


def image_resize_short(input, out_short_len, resample="BILINEAR"):
    _, _, h, w = input.shape
    short, long_ = (h, w) if h < w else (w, h)
    ratio = out_short_len / short
    out = [int(round(h * ratio)), int(round(w * ratio))]
    return image_resize(input, out_shape=out, resample=resample)


# -- conv/pool family -------------------------------------------------------

def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None):
    helper = LayerHelper("conv3d", name=name)

    def _t(v):
        return list(v) if isinstance(v, (list, tuple)) else [v] * 3

    fd, fh, fw = _t(filter_size)
    c = input.shape[1]
    w = helper.create_parameter(
        param_attr, shape=[num_filters, c // groups, fd, fh, fw],
        dtype=input.dtype)
    out = _emit("conv3d", {"Input": input, "Filter": w},
                {"strides": _t(stride), "paddings": _t(padding),
                 "dilations": _t(dilation), "groups": groups})
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[num_filters],
                                    dtype=input.dtype, is_bias=True)
        out = _emit("elementwise_add", {"X": out, "Y": b}, {"axis": 1})
    return helper.append_activation(out, act)


def conv3d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None):
    helper = LayerHelper("conv3d_transpose", name=name)

    def _t(v):
        return list(v) if isinstance(v, (list, tuple)) else [v] * 3

    fd, fh, fw = _t(filter_size)
    c = input.shape[1]
    w = helper.create_parameter(
        param_attr, shape=[c, num_filters // groups, fd, fh, fw],
        dtype=input.dtype)
    attrs = {"strides": _t(stride), "paddings": _t(padding),
             "dilations": _t(dilation), "groups": groups}
    if output_size is not None:
        attrs["output_size"] = _t(output_size)
    out = _emit("conv3d_transpose", {"Input": input, "Filter": w}, attrs)
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[num_filters],
                                    dtype=input.dtype, is_bias=True)
        out = _emit("elementwise_add", {"X": out, "Y": b}, {"axis": 1})
    return helper.append_activation(out, act)


def pool3d(input, pool_size=2, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, exclusive=True, name=None):
    def _t(v):
        return list(v) if isinstance(v, (list, tuple)) else [v] * 3
    return _emit("pool3d", {"X": input},
                 {"ksize": _t(pool_size), "strides": _t(pool_stride),
                  "paddings": _t(pool_padding), "pooling_type": pool_type,
                  "global_pooling": global_pooling, "exclusive": exclusive},
                 name=name)


def adaptive_pool2d(input, pool_size, pool_type="max", require_index=False,
                    name=None):
    ps = pool_size if isinstance(pool_size, (list, tuple)) else [pool_size] * 2
    shp = (tuple(input.shape[:2]) + tuple(ps)
           if input.shape is not None and len(input.shape) == 4 else None)
    return _emit("adaptive_pool2d", {"X": input},
                 {"pooling_size": pool_size, "pooling_type": pool_type},
                 name=name, out_shape=shp)


def adaptive_pool3d(input, pool_size, pool_type="max", require_index=False,
                    name=None):
    ps = pool_size if isinstance(pool_size, (list, tuple)) else [pool_size] * 3
    shp = (tuple(input.shape[:2]) + tuple(ps)
           if input.shape is not None and len(input.shape) == 5 else None)
    return _emit("adaptive_pool3d", {"X": input},
                 {"pooling_size": pool_size, "pooling_type": pool_type},
                 name=name, out_shape=shp)


def deformable_conv(input, offset, mask, num_filters, filter_size, stride=1,
                    padding=0, dilation=1, groups=1, deformable_groups=1,
                    im2col_step=1, param_attr=None, bias_attr=None,
                    modulated=True, name=None):
    helper = LayerHelper("deformable_conv", name=name)
    fh, fw = (filter_size if isinstance(filter_size, (list, tuple))
              else (filter_size, filter_size))
    c = input.shape[1]
    w = helper.create_parameter(
        param_attr, shape=[num_filters, c // groups, fh, fw],
        dtype=input.dtype)

    def _p(v):
        return list(v) if isinstance(v, (list, tuple)) else [v, v]
    out = _emit("deformable_conv",
                {"Input": input, "Offset": offset, "Mask": mask, "Filter": w},
                {"strides": _p(stride), "paddings": _p(padding),
                 "dilations": _p(dilation), "groups": groups,
                 "deformable_groups": deformable_groups}, outs=("Output",))
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[num_filters],
                                    dtype=input.dtype, is_bias=True)
        out = _emit("elementwise_add", {"X": out, "Y": b}, {"axis": 1})
    return out


def deformable_roi_pooling(input, rois, trans, no_trans=False,
                           spatial_scale=1.0, group_size=(1, 1),
                           pooled_height=1, pooled_width=1, part_size=None,
                           sample_per_part=1, trans_std=0.1, position_sensitive=False,
                           name=None):
    out, _ = _emit("deformable_psroi_pooling",
                   {"Input": input, "ROIs": rois,
                    "Trans": None if no_trans else trans},
                   {"pooled_height": pooled_height,
                    "pooled_width": pooled_width,
                    "spatial_scale": spatial_scale, "trans_std": trans_std},
                   outs=("Output", "TopCount"), name=name)
    return out


def row_conv(input, future_context_size, param_attr=None, act=None):
    helper = LayerHelper("row_conv")
    d = input.shape[-1]
    w = helper.create_parameter(param_attr,
                                shape=[future_context_size + 1, d],
                                dtype=input.dtype)
    out = _emit("row_conv", {"X": input, "Filter": w})
    return helper.append_activation(out, act)


def bilinear_tensor_product(x, y, size, act=None, name=None, param_attr=None,
                            bias_attr=None):
    helper = LayerHelper("bilinear_tensor_product", name=name)
    w = helper.create_parameter(
        param_attr, shape=[size, x.shape[-1], y.shape[-1]], dtype=x.dtype)
    ins = {"X": x, "Y": y, "Weight": w}
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[1, size], dtype=x.dtype,
                                    is_bias=True)
        ins["Bias"] = b
    out = _emit("bilinear_tensor_product", ins)
    return helper.append_activation(out, act)


def spectral_norm(weight, dim=0, power_iters=1, eps=1e-12, name=None):
    helper = LayerHelper("spectral_norm", name=name)
    h = weight.shape[dim]
    w = int(np.prod(weight.shape)) // h
    from ..initializer import NormalInitializer
    u = helper.create_parameter(None, shape=[h], dtype=weight.dtype,
                                default_initializer=NormalInitializer(0, 1))
    v = helper.create_parameter(None, shape=[w], dtype=weight.dtype,
                                default_initializer=NormalInitializer(0, 1))
    u.stop_gradient = v.stop_gradient = True
    return _emit("spectral_norm", {"Weight": weight, "U": u, "V": v},
                 {"dim": dim, "power_iters": power_iters, "eps": eps})


def tree_conv(nodes_vector, edge_set, output_size, num_filters=1,
              max_depth=8, act="tanh", param_attr=None, bias_attr=None,
              name=None):
    helper = LayerHelper("tree_conv", name=name)
    d = nodes_vector.shape[-1]
    w = helper.create_parameter(
        param_attr, shape=[d, 3, output_size * num_filters],
        dtype=nodes_vector.dtype)
    out = _emit("tree_conv",
                {"NodesVector": nodes_vector, "EdgeSet": edge_set,
                 "Filter": w}, {"max_depth": max_depth})
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr,
                                    shape=[output_size * num_filters],
                                    dtype=nodes_vector.dtype, is_bias=True)
        out = _emit("elementwise_add", {"X": out, "Y": b}, {"axis": -1})
    return helper.append_activation(out, act)


def var_conv_2d(input, row, col, input_channel, output_channel, filter_size,
                stride=1, param_attr=None, act=None, name=None):
    helper = LayerHelper("var_conv_2d", name=name)
    fh, fw = (filter_size if isinstance(filter_size, (list, tuple))
              else (filter_size, filter_size))
    w = helper.create_parameter(
        param_attr,
        shape=[output_channel, input_channel * fh * fw], dtype=input.dtype)
    sh, sw = (stride if isinstance(stride, (list, tuple))
              else (stride, stride))
    out = _emit("var_conv_2d",
                {"X": input, "W": w, "LengthX": row, "LengthY": col},
                {"kernel_h": fh, "kernel_w": fw, "stride_h": sh,
                 "stride_w": sw})
    return helper.append_activation(out, act)


def data_norm(input, act=None, epsilon=1e-5, param_attr=None,
              data_layout="NCHW", in_place=False, name=None,
              moving_mean_name=None, moving_variance_name=None,
              do_model_average_for_mean_and_var=False):
    helper = LayerHelper("data_norm", name=name)
    c = input.shape[-1]
    from ..initializer import ConstantInitializer
    bsize = helper.create_parameter(
        None, shape=[c], dtype=input.dtype,
        default_initializer=ConstantInitializer(1e4))
    bsum = helper.create_parameter(
        None, shape=[c], dtype=input.dtype,
        default_initializer=ConstantInitializer(0.0))
    bsq = helper.create_parameter(
        None, shape=[c], dtype=input.dtype,
        default_initializer=ConstantInitializer(1e4))
    y, _, _ = _emit("data_norm",
                    {"X": input, "BatchSize": bsize, "BatchSum": bsum,
                     "BatchSquareSum": bsq}, {"epsilon": epsilon},
                    outs=("Y", "Means", "Scales"))
    return helper.append_activation(y, act)


def dynamic_lstmp(input, size, proj_size, param_attr=None, bias_attr=None,
                  use_peepholes=True, is_reverse=False,
                  gate_activation="sigmoid", cell_activation="tanh",
                  candidate_activation="tanh", proj_activation="tanh",
                  dtype="float32", name=None, length=None):
    """nn.py dynamic_lstmp → lstmp op (projected LSTM). `input` is the
    pre-projected [B, T, 4*hidden] tensor, reference contract."""
    helper = LayerHelper("dynamic_lstmp", name=name)
    hidden = size // 4
    w = helper.create_parameter(param_attr, shape=[proj_size, 4 * hidden],
                                dtype=dtype)
    wp = helper.create_parameter(None, shape=[hidden, proj_size], dtype=dtype)
    nb = 7 * hidden if use_peepholes else 4 * hidden
    b = helper.create_parameter(bias_attr, shape=[1, nb], dtype=dtype,
                                is_bias=True)
    ins = {"Input": input, "Weight": w, "ProjWeight": wp, "Bias": b}
    if length is not None:
        ins["Length"] = length
    proj, cell = _emit("lstmp", ins,
                       {"use_peepholes": use_peepholes,
                        "is_reverse": is_reverse,
                        "gate_activation": gate_activation,
                        "cell_activation": cell_activation,
                        "candidate_activation": candidate_activation,
                        "proj_activation": proj_activation},
                       outs=("Projection", "Cell"))
    return proj, cell


# -- detection extras -------------------------------------------------------

def anchor_generator(input, anchor_sizes=None, aspect_ratios=None,
                     variance=(0.1, 0.1, 0.2, 0.2), stride=None, offset=0.5,
                     name=None):
    return _emit("anchor_generator", {"Input": input},
                 {"anchor_sizes": list(anchor_sizes or [64.0]),
                  "aspect_ratios": list(aspect_ratios or [1.0]),
                  "variances": list(variance), "stride": list(stride or [16.0, 16.0]),
                  "offset": offset}, outs=("Anchors", "Variances"), name=name)


def bipartite_match(dist_matrix, match_type=None, dist_threshold=None,
                    name=None):
    return _emit("bipartite_match", {"DistMat": dist_matrix},
                 {"match_type": match_type or "bipartite",
                  "dist_threshold": dist_threshold or 0.5},
                 outs=("ColToRowMatchIndices", "ColToRowMatchDist"),
                 name=name)


def box_clip(input, im_info, name=None):
    return _emit("box_clip", {"Input": input, "ImInfo": im_info},
                 outs=("Output",), name=name)


def box_decoder_and_assign(prior_box, prior_box_var, target_box, box_score,
                           box_clip, name=None):
    return _emit("box_decoder_and_assign",
                 {"PriorBox": prior_box, "PriorBoxVar": prior_box_var,
                  "TargetBox": target_box, "BoxScore": box_score},
                 {"box_clip": box_clip},
                 outs=("DecodeBox", "OutputAssignBox"), name=name)


def collect_fpn_proposals(multi_rois, multi_scores, min_level, max_level,
                          post_nms_top_n, name=None):
    out, _ = _emit("collect_fpn_proposals",
                   {"MultiLevelRois": list(multi_rois),
                    "MultiLevelScores": list(multi_scores)},
                   {"post_nms_topN": post_nms_top_n},
                   outs=("FpnRois", "RoisNum"), name=name)
    return out


def distribute_fpn_proposals(fpn_rois, min_level, max_level, refer_level,
                             refer_scale, name=None):
    helper = LayerHelper("distribute_fpn_proposals", name=name)
    n = max_level - min_level + 1
    outs = [helper.create_variable_for_type_inference(fpn_rois.dtype)
            for _ in range(n)]
    masks = [helper.create_variable_for_type_inference("int32")
             for _ in range(n)]
    restore = helper.create_variable_for_type_inference("int32")
    helper.append_op(
        type="distribute_fpn_proposals",
        inputs={"FpnRois": [fpn_rois.name]},
        outputs={"MultiFpnRois": [o.name for o in outs],
                 "MultiLevelMask": [m.name for m in masks],
                 "RestoreIndex": [restore.name]},
        attrs={"min_level": min_level, "max_level": max_level,
               "refer_level": refer_level, "refer_scale": refer_scale})
    return outs, restore


def density_prior_box(input, image, densities=None, fixed_sizes=None,
                      fixed_ratios=None, variance=(0.1, 0.1, 0.2, 0.2),
                      clip=False, steps=(0.0, 0.0), offset=0.5,
                      flatten_to_2d=False, name=None):
    return _emit("density_prior_box", {"Input": input, "Image": image},
                 {"densities": list(densities or []),
                  "fixed_sizes": list(fixed_sizes or []),
                  "fixed_ratios": list(fixed_ratios or []),
                  "variances": list(variance), "clip": clip,
                  "step_w": steps[0], "step_h": steps[1], "offset": offset},
                 outs=("Boxes", "Variances"), name=name)


def iou_similarity(x, y, box_normalized=True, name=None):
    return _emit("iou_similarity", {"X": x, "Y": y},
                 {"box_normalized": box_normalized}, name=name)


def multiclass_nms(bboxes, scores, score_threshold, nms_top_k, keep_top_k,
                   nms_threshold=0.3, normalized=True, nms_eta=1.0,
                   background_label=0, name=None):
    return _emit("multiclass_nms", {"BBoxes": bboxes, "Scores": scores},
                 {"score_threshold": score_threshold, "nms_top_k": nms_top_k,
                  "keep_top_k": keep_top_k, "nms_threshold": nms_threshold,
                  "background_label": background_label}, name=name)


def polygon_box_transform(input, name=None):
    return _emit("polygon_box_transform", {"Input": input}, outs=("Output",),
                 name=name)


def psroi_pool(input, rois, output_channels, spatial_scale, pooled_height,
               pooled_width, name=None):
    return _emit("psroi_pool", {"X": input, "ROIs": rois},
                 {"output_channels": output_channels,
                  "spatial_scale": spatial_scale,
                  "pooled_height": pooled_height,
                  "pooled_width": pooled_width}, name=name)


def roi_pool(input, rois, pooled_height=1, pooled_width=1, spatial_scale=1.0,
             rois_lod=None):
    return _emit("roi_pool", {"X": input, "ROIs": rois},
                 {"pooled_height": pooled_height,
                  "pooled_width": pooled_width,
                  "spatial_scale": spatial_scale})


def roi_perspective_transform(input, rois, transformed_height,
                              transformed_width, spatial_scale=1.0):
    return _emit("roi_perspective_transform", {"X": input, "ROIs": rois},
                 {"transformed_height": transformed_height,
                  "transformed_width": transformed_width,
                  "spatial_scale": spatial_scale})


def rpn_target_assign(bbox_pred, cls_logits, anchor_box, anchor_var,
                      gt_boxes, is_crowd=None, im_info=None,
                      rpn_batch_size_per_im=256, rpn_straddle_thresh=0.0,
                      rpn_fg_fraction=0.5, rpn_positive_overlap=0.7,
                      rpn_negative_overlap=0.3, use_random=True):
    si, li, tl, tb, biw = _emit(
        "rpn_target_assign", {"Anchor": anchor_box, "GtBoxes": gt_boxes},
        {"rpn_batch_size_per_im": rpn_batch_size_per_im,
         "rpn_fg_fraction": rpn_fg_fraction,
         "rpn_positive_overlap": rpn_positive_overlap,
         "rpn_negative_overlap": rpn_negative_overlap},
        outs=("ScoreIndex", "LocationIndex", "TargetLabel", "TargetBBox",
              "BBoxInsideWeight"))
    return si, li, tl, tb


def target_assign(input, matched_indices, negative_indices=None,
                  mismatch_value=None, name=None):
    return _emit("target_assign",
                 {"X": input, "MatchIndices": matched_indices,
                  "NegIndices": negative_indices},
                 {"mismatch_value": mismatch_value or 0},
                 outs=("Out", "OutWeight"), name=name)


def yolov3_loss(x, gt_box, gt_label, anchors, anchor_mask, class_num,
                ignore_thresh, downsample_ratio, gt_score=None,
                use_label_smooth=True, name=None):
    return _emit("yolov3_loss", {"X": x, "GTBox": gt_box, "GTLabel": gt_label},
                 {"anchors": list(anchors), "anchor_mask": list(anchor_mask),
                  "class_num": class_num, "ignore_thresh": ignore_thresh,
                  "downsample_ratio": downsample_ratio},
                 outs=("Loss",), name=name)


def detection_output(loc, scores, prior_box, prior_box_var,
                     background_label=0, nms_threshold=0.3, nms_top_k=400,
                     keep_top_k=200, score_threshold=0.01, nms_eta=1.0):
    """detection.py detection_output composite: decode by box_coder then
    multiclass_nms (reference layers/detection.py:detection_output)."""
    from .detection import box_coder
    decoded = box_coder(prior_box, prior_box_var, loc,
                        code_type="decode_center_size")
    # scores arrive [N, prior, class] — nms expects [N, class, prior]
    scores_t = _tensor.transpose(scores, [0, 2, 1])
    return multiclass_nms(decoded, scores_t,
                          score_threshold=score_threshold,
                          nms_top_k=nms_top_k, keep_top_k=keep_top_k,
                          nms_threshold=nms_threshold,
                          background_label=background_label)


def ssd_loss(location, confidence, gt_box, gt_label, prior_box,
             prior_box_var=None, background_label=0, overlap_threshold=0.5,
             neg_pos_ratio=3.0, neg_overlap=0.5, loc_loss_weight=1.0,
             conf_loss_weight=1.0, match_type="per_prediction",
             mining_type="max_negative", normalize=True,
             sample_size=None):
    """detection.py ssd_loss, composed of the same primitive ops the
    reference uses (iou → bipartite_match → target_assign → smooth-l1 +
    softmax losses). Simplified mining: all positives + all negatives
    weighted, no hard-negative sampling (static shapes for XLA)."""
    from . import nn as _nn
    from .reduce import reduce_sum
    iou = iou_similarity(gt_box, prior_box)
    matched, _ = bipartite_match(iou, "per_prediction", overlap_threshold)
    loc_tgt, loc_w = target_assign(gt_box, matched, mismatch_value=0)
    lbl_tgt, lbl_w = target_assign(gt_label, matched,
                                   mismatch_value=background_label)
    loc_l = smooth_l1(location, loc_tgt)
    loc_l = _emit("elementwise_mul", {"X": loc_l, "Y": loc_w})
    conf_l = _nn.softmax_with_cross_entropy(
        confidence, _tensor.cast(lbl_tgt, "int64"))
    loss = _emit("elementwise_add",
                 {"X": _emit("scale", {"X": reduce_sum(loc_l)},
                             {"scale": loc_loss_weight}),
                  "Y": _emit("scale", {"X": reduce_sum(conf_l)},
                             {"scale": conf_loss_weight})})
    return loss


def multi_box_head(inputs, image, base_size, num_classes, aspect_ratios,
                   min_ratio=None, max_ratio=None, min_sizes=None,
                   max_sizes=None, steps=None, step_w=None, step_h=None,
                   offset=0.5, variance=(0.1, 0.1, 0.2, 0.2), flip=True,
                   clip=False, kernel_size=1, pad=0, stride=1, name=None,
                   min_max_aspect_ratios_order=False):
    """detection.py multi_box_head: per feature map, prior_box + conv heads
    for loc/conf, flattened and concatenated (SSD head)."""
    from . import nn as _nn
    from .detection import prior_box as _prior_box
    n_layer = len(inputs)
    if min_sizes is None:
        # reference ratio schedule (detection.py multi_box_head)
        min_ratio, max_ratio = min_ratio or 20, max_ratio or 90
        step = int((max_ratio - min_ratio) / (n_layer - 2))
        min_sizes, max_sizes = [base_size * 0.1], [base_size * 0.2]
        for r in range(min_ratio, max_ratio + 1, step):
            min_sizes.append(base_size * r / 100.0)
            max_sizes.append(base_size * (r + step) / 100.0)
        min_sizes, max_sizes = min_sizes[:n_layer], max_sizes[:n_layer]

    locs, confs, boxes_l, vars_l = [], [], [], []
    for i, inp in enumerate(inputs):
        mins = min_sizes[i] if isinstance(min_sizes[i], (list, tuple)) \
            else [min_sizes[i]]
        maxs = (max_sizes[i] if isinstance(max_sizes[i], (list, tuple))
                else [max_sizes[i]]) if max_sizes else None
        ar = aspect_ratios[i] if isinstance(aspect_ratios[i], (list, tuple)) \
            else [aspect_ratios[i]]
        box, var = _prior_box(inp, image, mins, maxs, ar, list(variance),
                              flip, clip,
                              steps[i] if steps else [step_w or 0.0,
                                                      step_h or 0.0],
                              offset)
        box = _tensor.reshape(box, [-1, 4])
        var = _tensor.reshape(var, [-1, 4])
        boxes_l.append(box)
        vars_l.append(var)
        num_boxes = box.shape[0]
        loc = _nn.conv2d(inp, num_boxes // (inp.shape[2] * inp.shape[3]) * 4,
                         kernel_size, padding=pad, stride=stride)
        loc = _tensor.transpose(loc, [0, 2, 3, 1])
        locs.append(_tensor.reshape(loc, [loc.shape[0], -1, 4]))
        conf = _nn.conv2d(
            inp, num_boxes // (inp.shape[2] * inp.shape[3]) * num_classes,
            kernel_size, padding=pad, stride=stride)
        conf = _tensor.transpose(conf, [0, 2, 3, 1])
        confs.append(_tensor.reshape(conf,
                                     [conf.shape[0], -1, num_classes]))
    mbox_locs = _tensor.concat(locs, axis=1)
    mbox_confs = _tensor.concat(confs, axis=1)
    boxes = _tensor.concat(boxes_l, axis=0)
    variances = _tensor.concat(vars_l, axis=0)
    return mbox_locs, mbox_confs, boxes, variances


# -- tensor / creation ------------------------------------------------------

def eye(num_rows, num_columns=None, batch_shape=None, dtype="float32"):
    return _emit("eye", {}, {"num_rows": num_rows,
                             "num_columns": num_columns or num_rows,
                             "dtype": dtype}, dtype=dtype)


def diag(diagonal):
    return _emit("diag", {"Diagonal": diagonal})


def linspace(start, stop, num, dtype="float32"):
    attrs, ins = {}, {}
    for key, slot, v in (("start", "Start", start), ("stop", "Stop", stop),
                         ("num", "Num", num)):
        if isinstance(v, Variable):
            ins[slot] = v
        else:
            attrs[key] = int(v) if key == "num" else float(v)
            ins[slot] = _tensor.fill_constant(
                [1], "int32" if key == "num" else dtype, float(v))
    return _emit("linspace", ins, attrs, dtype=dtype)


def range(start, end, step, dtype="float32"):
    attrs, ins = {}, {}
    for key, slot, v in (("start", "Start", start), ("end", "End", end),
                         ("step", "Step", step)):
        if isinstance(v, Variable):
            ins[slot] = v
        else:
            attrs[key] = float(v)
            ins[slot] = _tensor.fill_constant([1], dtype, float(v))
    return _emit("range", ins, attrs, dtype=dtype)


def gaussian_random(shape, mean=0.0, std=1.0, seed=0, dtype="float32"):
    return _emit("gaussian_random", {},
                 {"shape": list(shape), "mean": mean, "std": std,
                  "dtype": dtype}, dtype=dtype)


def gaussian_random_batch_size_like(input, shape, input_dim_idx=0,
                                    output_dim_idx=0, mean=0.0, std=1.0,
                                    seed=0, dtype="float32"):
    return _emit("gaussian_random_batch_size_like", {"Input": input},
                 {"shape": list(shape), "mean": mean, "std": std,
                  "input_dim_idx": input_dim_idx,
                  "output_dim_idx": output_dim_idx, "dtype": dtype},
                 dtype=dtype)


def uniform_random_batch_size_like(input, shape, dtype="float32",
                                   input_dim_idx=0, output_dim_idx=0,
                                   min=-1.0, max=1.0, seed=0):
    return _emit("uniform_random_batch_size_like", {"Input": input},
                 {"shape": list(shape), "min": min, "max": max,
                  "input_dim_idx": input_dim_idx,
                  "output_dim_idx": output_dim_idx, "dtype": dtype},
                 dtype=dtype)


def ones_like(x, out=None):
    return _emit("ones_like", {"X": x})


def shape(input):
    return _emit("shape", {"Input": input}, dtype="int32")


def rank(input):
    """nn.py rank: the static rank as a constant tensor."""
    return _tensor.fill_constant([1], "int32", len(input.shape))


def size(input):
    return _emit("size", {"Input": input}, dtype="int64")


def reverse(x, axis):
    return _emit("reverse", {"X": x},
                 {"axis": list(axis) if isinstance(axis, (list, tuple))
                  else [axis]})


def multiplex(inputs, index):
    return _emit("multiplex", {"X": list(inputs), "Ids": index})


def sum(x):
    return _emit("sum", {"X": list(x) if isinstance(x, (list, tuple))
                         else [x]})


sums = sum


def scatter_nd_add(ref, index, updates, name=None):
    return _emit("scatter_nd_add",
                 {"X": ref, "Index": index, "Updates": updates}, name=name)


def scatter_nd(index, updates, shape, name=None):
    return _emit("scatter_nd", {"Index": index, "Updates": updates},
                 {"shape": list(shape)}, name=name)


def shard_index(input, index_num, nshards, shard_id, ignore_value=-1):
    return _emit("shard_index", {"X": input},
                 {"index_num": index_num, "nshards": nshards,
                  "shard_id": shard_id, "ignore_value": ignore_value})


def hash(input, hash_size, num_hash=1, name=None):
    return _emit("hash", {"X": input},
                 {"mod_by": hash_size, "num_hash": num_hash},
                 dtype="int64", name=name)


def unique(x, dtype="int32"):
    out, idx, _ = _emit("unique", {"X": x}, {"dtype": dtype},
                        outs=("Out", "Index", "Count"))
    return out, idx


def unique_with_counts(x, dtype="int32"):
    out, idx, counts, _ = _emit("unique_with_counts", {"X": x},
                                {"dtype": dtype},
                                outs=("Out", "Index", "Counts", "Count"))
    return out, idx, counts


def isfinite(x):
    return _emit("isfinite", {"X": x}, dtype="bool")


def has_inf(x):
    return _emit("has_inf", {"X": x}, dtype="bool")


def has_nan(x):
    return _emit("has_nan", {"X": x}, dtype="bool")


def is_empty(x, cond=None):
    return _emit("is_empty", {"X": x}, dtype="bool")


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    return _emit("label_smooth", {"X": label, "PriorDist": prior_dist},
                 {"epsilon": epsilon}, name=name)


def mean_iou(input, label, num_classes):
    return _emit("mean_iou", {"Predictions": input, "Labels": label},
                 {"num_classes": num_classes},
                 outs=("OutMeanIou", "OutWrong", "OutCorrect"))


def sampling_id(x, min=0.0, max=1.0, seed=0, dtype="float32"):
    return _emit("sampling_id", {"X": x}, {"seed": seed}, dtype="int64")


def sigmoid_focal_loss(x, label, fg_num, gamma=2.0, alpha=0.25):
    return _emit("sigmoid_focal_loss",
                 {"X": x, "Label": label, "FgNum": fg_num},
                 {"gamma": gamma, "alpha": alpha})


def edit_distance(input, label, normalized=True, ignored_tokens=None,
                  input_length=None, label_length=None):
    ins = {"Hyps": input, "Refs": label}
    if input_length is not None:
        ins["HypsLength"] = input_length
    if label_length is not None:
        ins["RefsLength"] = label_length
    return _emit("edit_distance", ins, {"normalized": normalized},
                 outs=("Out", "SequenceNum"))


def chunk_eval(input, label, chunk_scheme, num_chunk_types,
               excluded_chunk_types=None, seq_length=None):
    ins = {"Inference": input, "Label": label}
    if seq_length is not None:
        ins["Length"] = seq_length
    return _emit("chunk_eval", ins,
                 {"chunk_scheme": chunk_scheme,
                  "num_chunk_types": num_chunk_types},
                 outs=("Precision", "Recall", "F1-Score", "NumInferChunks",
                       "NumLabelChunks", "NumCorrectChunks"))


def ctc_greedy_decoder(input, blank, name=None):
    """nn.py ctc_greedy_decoder → argmax + ctc_align (merge repeats, strip
    blanks; padded output, -1 fill)."""
    am = _tensor.argmax(input, axis=-1)
    return _emit("ctc_align", {"Input": am}, {"blank": blank,
                                              "merge_repeated": True},
                 dtype="int64", name=name)


def continuous_value_model(input, cvm, use_cvm=True):
    return _emit("cvm", {"X": input, "CVM": cvm}, {"use_cvm": use_cvm},
                 outs=("Y",))


def filter_by_instag(ins, ins_tag, filter_tag, is_lod=True):
    return _emit("filter_by_instag",
                 {"Ins": ins, "Ins_tag": ins_tag, "Filter_tag": filter_tag},
                 outs=("Out", "LossWeight", "IndexMap"))


def match_matrix_tensor(x, y, channel_num, length_x=None, length_y=None,
                        act=None, param_attr=None, dtype="float32",
                        name=None):
    helper = LayerHelper("match_matrix_tensor", name=name)
    dx, dy = x.shape[-1], y.shape[-1]
    w = helper.create_parameter(param_attr, shape=[dx, channel_num, dy],
                                dtype=dtype)
    ins = {"X": x, "Y": y, "W": w}
    if length_x is not None:
        ins["LengthX"] = length_x
    if length_y is not None:
        ins["LengthY"] = length_y
    out, tmp = _emit("match_matrix_tensor", ins, {"dim_t": channel_num},
                     outs=("Out", "Tmp"))
    return helper.append_activation(out, act), tmp


def im2sequence(input, filter_size=1, stride=1, padding=0, input_image_size=None,
                out_stride=1, name=None):
    def _p(v):
        return list(v) if isinstance(v, (list, tuple)) else [v, v]
    return _emit("im2sequence", {"X": input},
                 {"kernels": _p(filter_size), "strides": _p(stride),
                  "paddings": _p(padding) * 2 if not isinstance(padding, (list, tuple)) or len(_p(padding)) == 2 else list(padding)},
                 name=name)


def warpctc(input, label, blank=0, norm_by_times=False, input_length=None,
            label_length=None):
    ins = {"Logits": input, "Label": label}
    if input_length is not None:
        ins["LogitsLength"] = input_length
    if label_length is not None:
        ins["LabelLength"] = label_length
    return _emit("warpctc", ins,
                 {"blank": blank, "norm_by_times": norm_by_times},
                 outs=("Loss",))


def sequence_expand(x, y, ref_level=-1, name=None):
    return _emit("sequence_expand", {"X": x, "Y": y},
                 {"ref_level": ref_level}, name=name)


def sequence_first_step(input, length=None):
    from .sequence import sequence_pool
    return sequence_pool(input, "first", length=length)


def sequence_last_step(input, length=None):
    from .sequence import sequence_pool
    return sequence_pool(input, "last", length=length)


def lod_reset(x, y=None, target_lod=None):
    ins = {"X": x}
    if y is not None:
        ins["Y"] = y
    return _emit("lod_reset", ins,
                 {"target_lod": list(target_lod or [])})


def lod_append(x, level):
    """LoD metadata is a dense Length tensor here; appending a level is an
    annotation-only operation — returns x (documented no-op)."""
    return x


def reorder_lod_tensor_by_rank(x, rank_table):
    return _emit("reorder_lod_tensor_by_rank",
                 {"X": x, "RankTable": rank_table})


def Print(input, first_n=-1, message=None, summarize=20, print_tensor_name=True,
          print_tensor_type=True, print_tensor_shape=True,
          print_tensor_lod=True, print_phase="both"):
    return _emit("print", {"In": input},
                 {"first_n": first_n, "message": message or "",
                  "summarize": summarize}, outs=("Out",))


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """nn.py py_func (py_func_op.cc): host-python escape hatch. The out
    var(s) must carry a FULLY-specified shape+dtype — the host callback
    crosses the jit boundary (jax.pure_callback), so XLA needs the result
    signature up front (the reference infers it at run time; static
    shapes are the TPU contract).

    ``backward_func`` makes the op differentiable (py_func_op.cc:198 grad
    maker): it is called as ``backward_func(*kept_fwd_inputs,
    *kept_fwd_outputs, *out_grads)`` and must return one grad per forward
    input (``None`` → zeros); vars listed in
    ``skip_vars_in_backward_input`` are withheld from its arguments
    (output grads can never be skipped).

    Runtime support: host callbacks need a PJRT runtime with host
    send/recv (the CPU and TPU runtimes have it; the reference's py_func
    was CPU-kernel-only too, py_func_op.cc)."""
    xs = x if isinstance(x, (list, tuple)) else [x]
    outs = out if isinstance(out, (list, tuple)) else [out]
    from ..core.dtypes import dtype_str
    shapes, dtypes = [], []
    for v in outs:
        shp = list(v.shape or [])
        if not shp or any(d is None or int(d) < 0 for d in shp):
            raise ValueError(
                f"py_func: out var {v.name!r} needs a fully-specified "
                f"shape (got {v.shape}) — the host callback's result "
                f"signature must be static for XLA")
        shapes.append([int(d) for d in shp])
        dtypes.append(dtype_str(v.dtype))
    # resolve skip_vars_in_backward_input (vars or names) to positional
    # keep-lists over (fwd inputs, fwd outputs) — reference semantics
    # (py_func_op.cc:220): skipped fwd ins/outs are not handed to
    # backward_func; output grads can never be skipped.
    skip = set()
    if skip_vars_in_backward_input is not None:
        sv = (skip_vars_in_backward_input
              if isinstance(skip_vars_in_backward_input, (list, tuple))
              else [skip_vars_in_backward_input])
        skip = {v.name if hasattr(v, "name") else str(v) for v in sv}
        known = {v.name for v in xs} | {v.name for v in outs}
        unknown = skip - known
        if unknown:
            raise ValueError(
                f"py_func: skip_vars_in_backward_input names "
                f"{sorted(unknown)} are neither forward inputs nor "
                f"outputs of this py_func")
    attrs = {"func": func, "backward_func": backward_func,
             "out_shapes": shapes, "out_dtypes": dtypes}
    if backward_func is not None:
        attrs["bwd_keep_in"] = [i for i, v in enumerate(xs)
                                if v.name not in skip]
        attrs["bwd_keep_out"] = [i for i, v in enumerate(outs)
                                 if v.name not in skip]
    helper = LayerHelper("py_func")
    helper.append_op(type="py_func", inputs={"X": [v.name for v in xs]},
                     outputs={"Out": [v.name for v in outs]}, attrs=attrs)
    return out


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    """nn.py autoincreased_step_counter: persistable int counter bumped
    every executor run."""
    main = default_main_program()
    startup = default_startup_program()
    name = counter_name or "@STEP_COUNTER@"
    block = main.global_block()
    counter = block.create_var(name=name, shape=(1,), dtype="int64",
                               persistable=True)
    sb = startup.global_block()
    sb.create_var(name=name, shape=(1,), dtype="int64", persistable=True)
    sb.append_op(type="fill_constant", inputs={},
                 outputs={"Out": [name]},
                 attrs={"shape": [1], "dtype": "int64",
                        "value": float(begin - step)})
    block.append_op(type="increment", inputs={"X": [name]},
                    outputs={"Out": [name]}, attrs={"step": float(step)})
    counter.stop_gradient = True
    return counter


# -- reader-layer surface ---------------------------------------------------

def py_reader(capacity, shapes, dtypes, lod_levels=None, name=None,
              use_double_buffer=True):
    """layers/io.py py_reader: returns a PyReader-compatible object (the
    reader variable of the reference maps to the host-side PyReader here;
    use_double_buffer engages the dataio.DeviceLoader prefetch stage)."""
    from ..reader import PyReader
    return PyReader(feed_list=None, capacity=capacity, shapes=shapes,
                    dtypes=dtypes, name=name,
                    use_double_buffer=use_double_buffer)


def create_py_reader_by_data(capacity, feed_list, name=None,
                             use_double_buffer=True):
    from ..reader import PyReader
    return PyReader(feed_list=feed_list, capacity=capacity, name=name,
                    use_double_buffer=use_double_buffer)


def double_buffer(reader, place=None, name=None):
    """buffered_reader.cc parity: wrap a batch reader so conversion +
    device_put of the next batch run on a dataio.DeviceLoader worker
    while the current step computes. Returns a reader callable; each
    call is one prefetched epoch."""
    from ..dataio import DeviceLoader

    def double_buffered():
        loader = DeviceLoader(reader, capacity=2,
                              name=name or "double_buffer")
        yield from loader

    return double_buffered


def read_file(reader):
    """layers/io.py read_file: our readers yield feed dicts directly."""
    return reader


def load(out, file_path, load_as_fp16=None):
    helper = LayerHelper("load")
    helper.append_op(type="load", inputs={},
                     outputs={"Out": [out.name]},
                     attrs={"file_path": file_path})
    return out


# -- doc/codegen utilities (layer_function_generator.py parity) -------------

def autodoc(comment=""):
    def deco(func):
        func.__doc__ = (func.__doc__ or "") + comment
        return func
    return deco


def templatedoc(op_type=None):
    def deco(func):
        return func
    return deco


def deprecated(since="", instead="", reason=""):
    def deco(func):
        return func
    return deco


def generate_layer_fn(op_type):
    """layer_function_generator.py: one-op layer factory over the registry."""
    def layer(*args, **kwargs):
        name = kwargs.pop("name", None)
        ins = {}
        if args:
            ins["X"] = list(args) if len(args) > 1 else args[0]
        attrs = {k: v for k, v in kwargs.items()
                 if not isinstance(v, Variable)}
        for k, v in kwargs.items():
            if isinstance(v, Variable):
                ins[k] = v
        return _emit(op_type, ins, attrs, name=name)
    layer.__name__ = op_type
    return layer


def generate_activation_fn(op_type):
    def layer(x, name=None):
        return _emit(op_type, {"X": x}, name=name)
    layer.__name__ = op_type
    return layer


# -- RCNN / RetinaNet tails -------------------------------------------------

def generate_proposals(scores, bbox_deltas, im_info, anchors, variances=None,
                       pre_nms_top_n=6000, post_nms_top_n=1000,
                       nms_thresh=0.5, min_size=0.1, eta=1.0, name=None):
    rois, probs = _emit("generate_proposals",
                        {"Scores": scores, "BboxDeltas": bbox_deltas,
                         "ImInfo": im_info, "Anchors": anchors,
                         "Variances": variances},
                        {"pre_nms_topN": pre_nms_top_n,
                         "post_nms_topN": post_nms_top_n,
                         "nms_thresh": nms_thresh},
                        outs=("RpnRois", "RpnRoiProbs"), name=name)
    return rois, probs


def generate_proposal_labels(rpn_rois, gt_classes, is_crowd, gt_boxes,
                             im_info, batch_size_per_im=256,
                             fg_fraction=0.25, fg_thresh=0.25,
                             bg_thresh_hi=0.5, bg_thresh_lo=0.0,
                             bbox_reg_weights=(0.1, 0.1, 0.2, 0.2),
                             class_nums=None, use_random=True,
                             is_cls_agnostic=False, is_cascade_rcnn=False):
    return _emit("generate_proposal_labels",
                 {"RpnRois": rpn_rois, "GtClasses": gt_classes,
                  "GtBoxes": gt_boxes},
                 {"batch_size_per_im": batch_size_per_im,
                  "fg_fraction": fg_fraction, "fg_thresh": fg_thresh,
                  "bg_thresh_hi": bg_thresh_hi, "bg_thresh_lo": bg_thresh_lo,
                  "class_nums": class_nums or 81},
                 outs=("Rois", "LabelsInt32", "BboxTargets",
                       "BboxInsideWeights", "BboxOutsideWeights"))


def generate_mask_labels(im_info, gt_classes, is_crowd, gt_segms, rois,
                         labels_int32, num_classes, resolution):
    return _emit("generate_mask_labels",
                 {"Rois": rois, "GtSegms": gt_segms,
                  "LabelsInt32": labels_int32},
                 {"resolution": resolution, "num_classes": num_classes},
                 outs=("MaskRois", "RoiHasMaskInt32", "MaskInt32"))


def retinanet_detection_output(bboxes, scores, im_info, score_threshold=0.05,
                               nms_top_k=1000, keep_top_k=100,
                               nms_threshold=0.3, nms_eta=1.0):
    b = bboxes[0] if isinstance(bboxes, (list, tuple)) else bboxes
    s = scores[0] if isinstance(scores, (list, tuple)) else scores
    return _emit("retinanet_detection_output",
                 {"BBoxes": b, "Scores": s, "ImInfo": im_info},
                 {"score_threshold": score_threshold, "nms_top_k": nms_top_k,
                  "keep_top_k": keep_top_k, "nms_threshold": nms_threshold})


def retinanet_target_assign(bbox_pred, cls_logits, anchor_box, anchor_var,
                            gt_boxes, gt_labels, is_crowd, im_info,
                            num_classes=1, positive_overlap=0.5,
                            negative_overlap=0.4):
    tl, tb, biw, fg = _emit(
        "retinanet_target_assign",
        {"Anchor": anchor_box, "GtBoxes": gt_boxes, "GtLabels": gt_labels},
        {"positive_overlap": positive_overlap,
         "negative_overlap": negative_overlap},
        outs=("TargetLabel", "TargetBBox", "BBoxInsideWeight",
              "ForegroundNumber"))
    return bbox_pred, cls_logits, tb, tl, biw, fg


def get_tensor_from_selected_rows(x, name=None):
    return _emit("get_tensor_from_selected_rows", {"X": x}, name=name)


def merge_selected_rows(x, name=None):
    return _emit("merge_selected_rows", {"X": x}, name=name)


def tensor_array_to_tensor(input, axis=1, name=None, use_stack=False):
    xs = input if isinstance(input, (list, tuple)) else [input]
    return _emit("tensor_array_to_tensor", {"X": list(xs)},
                 {"axis": axis, "use_stack": use_stack},
                 outs=("Out", "OutIndex"), name=name)
