"""Layers DSL — the user-facing graph-building API.

Reference analog: ``python/paddle/fluid/layers/nn.py`` (184 layers; SURVEY
§2.3). Each function appends ops to the current program block and returns the
output Variable(s). Shape metadata is best-effort (execution shapes come from
the actual arrays at trace time; XLA owns layout).
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence, Union

import numpy as np

from ..core.dtypes import convert_dtype, dtype_str
from ..core.program import Variable, default_main_program
from ..initializer import ConstantInitializer, NormalInitializer, XavierInitializer
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr


def _pair(v):
    return tuple(v) if isinstance(v, (list, tuple)) else (v, v)


def _conv_out_dim(size, k, pad, stride, dilation=1):
    if size is None or size < 0:
        return -1
    eff = dilation * (k - 1) + 1
    return (size + 2 * pad - eff) // stride + 1


def data(name: str, shape: Sequence[int], dtype="float32", lod_level: int = 0,
         append_batch_size: bool = True) -> Variable:
    """Input placeholder (reference layers/io.py data). With
    append_batch_size=True a leading -1 batch dim is added (paddle behavior)."""
    shape = list(shape)
    if append_batch_size and (not shape or shape[0] != -1):
        shape = [-1] + shape
    block = default_main_program().global_block()
    return block.create_var(name=name, shape=shape, dtype=convert_dtype(dtype),
                            is_data=True, stop_gradient=True, lod_level=lod_level)


def fc(input: Variable, size: int, num_flatten_dims: int = 1, param_attr=None,
       bias_attr=None, act: Optional[str] = None, name: Optional[str] = None) -> Variable:
    """Fully-connected (reference layers/nn.py fc): flattens input at
    num_flatten_dims, gemm on the MXU, optional bias + activation."""
    helper = LayerHelper("fc", name=name)
    in_shape = input.shape
    reduced = int(np.prod([d for d in in_shape[num_flatten_dims:]])) if in_shape else None
    w = helper.create_parameter(param_attr, shape=[reduced, size], dtype=input.dtype)
    out = helper.create_variable_for_type_inference(
        input.dtype, shape=tuple(in_shape[:num_flatten_dims]) + (size,) if in_shape else None)
    helper.append_op(
        type="mul", inputs={"X": [input.name], "Y": [w.name]},
        outputs={"Out": [out.name]},
        attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1})
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[size], dtype=input.dtype, is_bias=True)
        tmp = helper.create_variable_for_type_inference(input.dtype, out.shape)
        helper.append_op(type="elementwise_add",
                         inputs={"X": [out.name], "Y": [b.name]},
                         outputs={"Out": [tmp.name]}, attrs={"axis": -1})
        out = tmp
    return helper.append_activation(out, act)


def embedding(input: Variable, size: Sequence[int], is_sparse: bool = False,
              is_distributed: bool = False, padding_idx: Optional[int] = None,
              param_attr=None, dtype="float32", name=None,
              row_pack: bool = False) -> Variable:
    """layers/nn.py embedding → lookup_table op. is_sparse is accepted for API
    parity; on TPU the gradient is an XLA scatter-add either way.

    row_pack=True (TPU extension, no reference analog): store the table as
    a [vocab, 128] uint16 packed row-major array — each row bit-splits up
    to 64 f32 values (embedding + optional optimizer state columns) into
    lane-aligned u16 pairs, making per-step touched-row scatter updates
    ~3x cheaper than the column-major f32 layout the unpacked table is
    forced into (see ops/deferred_rows.py "packed row-major tables").
    Requires is_sparse=True and a *_row_packed optimizer
    (SGD/Adagrad/Adam with packed_rows=...); size[-1] counts the f32
    values per row INCLUDING state columns."""
    helper = LayerHelper("embedding", name=name)
    attrs = {"padding_idx": -1 if padding_idx is None else padding_idx,
             "is_sparse": is_sparse, "is_distributed": is_distributed}
    if row_pack:
        from ..ops.deferred_rows import PACK_LANES
        from ..initializer import RowPackInitializer
        if not is_sparse:
            raise ValueError("row_pack=True requires is_sparse=True")
        w = helper.create_parameter(
            param_attr, shape=[size[0], PACK_LANES], dtype="uint16",
            default_initializer=RowPackInitializer(size[-1], size[-1]))
        attrs["row_pack_dt"] = int(size[-1])
    else:
        w = helper.create_parameter(param_attr, shape=list(size), dtype=dtype,
                                    default_initializer=XavierInitializer())
    out_shape = None
    if input.shape is not None:
        ids_shape = input.shape[:-1] if input.shape[-1] == 1 else input.shape
        out_shape = tuple(ids_shape) + (size[-1],)
    out = helper.create_variable_for_type_inference(
        "float32" if row_pack else dtype, out_shape)
    helper.append_op(
        type="lookup_table", inputs={"W": [w.name], "Ids": [input.name]},
        outputs={"Out": [out.name]}, attrs=attrs)
    return out


def conv2d(input: Variable, num_filters: int, filter_size, stride=1, padding=0,
           dilation=1, groups: int = 1, param_attr=None, bias_attr=None,
           use_cudnn: bool = True, act: Optional[str] = None, name=None,
           data_format: str = "NCHW") -> Variable:
    helper = LayerHelper("conv2d", name=name)
    fh, fw = _pair(filter_size)
    num_channels = input.shape[1] if input.shape else None
    # fan-in init (reference layers/nn.py:2404: std = sqrt(2/(k*k*C_in)))
    w = helper.create_parameter(
        param_attr, shape=[num_filters, num_channels // groups, fh, fw],
        dtype=input.dtype,
        default_initializer=NormalInitializer(0.0, (2.0 / (fh * fw * num_channels)) ** 0.5))
    sh, sw = _pair(stride)
    ph, pw = _pair(padding)
    dh, dw = _pair(dilation)
    out_shape = None
    if input.shape is not None and len(input.shape) == 4:
        out_shape = (input.shape[0], num_filters,
                     _conv_out_dim(input.shape[2], fh, ph, sh, dh),
                     _conv_out_dim(input.shape[3], fw, pw, sw, dw))
    out = helper.create_variable_for_type_inference(input.dtype, out_shape)
    helper.append_op(
        type="conv2d", inputs={"Input": [input.name], "Filter": [w.name]},
        outputs={"Out": [out.name]},
        attrs={"strides": [sh, sw], "paddings": [ph, pw],
               "dilations": [dh, dw], "groups": groups})
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[num_filters], dtype=input.dtype, is_bias=True)
        tmp = helper.create_variable_for_type_inference(input.dtype, out_shape)
        helper.append_op(type="elementwise_add",
                         inputs={"X": [out.name], "Y": [b.name]},
                         outputs={"Out": [tmp.name]}, attrs={"axis": 1})
        out = tmp
    return helper.append_activation(out, act)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     stride=1, padding=0, dilation=1, groups=1, param_attr=None,
                     bias_attr=None, act=None, name=None) -> Variable:
    helper = LayerHelper("conv2d_transpose", name=name)
    if filter_size is None:
        # reference rule: infer the kernel from output_size
        # (out = (in−1)·stride − 2·pad + dil·(f−1) + 1)
        if output_size is None or input.shape is None or len(input.shape) != 4:
            raise ValueError(
                "conv2d_transpose: filter_size is required unless "
                "output_size is given and the input has static NCHW shape "
                "metadata to infer it from")
        st, pd, dl = _pair(stride), _pair(padding), _pair(dilation)
        osz = _pair(output_size)
        filter_size = []
        for i in range(2):
            num = osz[i] - (input.shape[2 + i] - 1) * st[i] + 2 * pd[i] - 1
            if num % dl[i] or num < 0:
                raise ValueError(
                    f"conv2d_transpose: no integer filter_size yields "
                    f"output_size[{i}]={osz[i]} from input "
                    f"{input.shape[2 + i]} with stride {st[i]}, padding "
                    f"{pd[i]}, dilation {dl[i]}")
            filter_size.append(num // dl[i] + 1)
    fh, fw = _pair(filter_size)
    num_channels = input.shape[1]
    w = helper.create_parameter(param_attr, shape=[num_channels, num_filters // groups, fh, fw],
                                dtype=input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    attrs = {"strides": list(_pair(stride)), "paddings": list(_pair(padding)),
             "dilations": list(_pair(dilation)), "groups": groups}
    if output_size is not None:
        attrs["output_size"] = list(_pair(output_size))
    helper.append_op(
        type="conv2d_transpose", inputs={"Input": [input.name], "Filter": [w.name]},
        outputs={"Out": [out.name]}, attrs=attrs)
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[num_filters], dtype=input.dtype, is_bias=True)
        tmp = helper.create_variable_for_type_inference(input.dtype)
        helper.append_op(type="elementwise_add", inputs={"X": [out.name], "Y": [b.name]},
                         outputs={"Out": [tmp.name]}, attrs={"axis": 1})
        out = tmp
    return helper.append_activation(out, act)


def pool2d(input: Variable, pool_size=2, pool_type: str = "max", pool_stride=None,
           pool_padding=0, global_pooling: bool = False, use_cudnn: bool = True,
           ceil_mode: bool = False, exclusive: bool = True, name=None) -> Variable:
    helper = LayerHelper("pool2d", name=name)
    kh, kw = _pair(pool_size)
    sh, sw = _pair(pool_stride if pool_stride is not None else pool_size)
    ph, pw = _pair(pool_padding)
    out_shape = None
    if input.shape is not None and len(input.shape) == 4:
        if global_pooling:
            out_shape = (input.shape[0], input.shape[1], 1, 1)
        else:
            out_shape = (input.shape[0], input.shape[1],
                         _conv_out_dim(input.shape[2], kh, ph, sh),
                         _conv_out_dim(input.shape[3], kw, pw, sw))
    out = helper.create_variable_for_type_inference(input.dtype, out_shape)
    helper.append_op(
        type="pool2d", inputs={"X": [input.name]}, outputs={"Out": [out.name]},
        attrs={"pooling_type": pool_type, "ksize": [kh, kw],
               "strides": [sh, sw], "paddings": [ph, pw],
               "global_pooling": global_pooling, "exclusive": exclusive})
    return out


def batch_norm(input: Variable, act: Optional[str] = None, is_test: bool = False,
               momentum: float = 0.9, epsilon: float = 1e-5, param_attr=None,
               bias_attr=None, data_layout: str = "NCHW", name=None,
               moving_mean_name=None, moving_variance_name=None,
               use_global_stats: bool = False) -> Variable:
    helper = LayerHelper("batch_norm", name=name)
    c = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    scale = helper.create_parameter(param_attr, shape=[c], dtype=input.dtype,
                                    default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(bias_attr, shape=[c], dtype=input.dtype, is_bias=True)
    mean = helper.create_global_variable([c], input.dtype, name=moving_mean_name,
                                         initializer=ConstantInitializer(0.0))
    var = helper.create_global_variable([c], input.dtype, name=moving_variance_name,
                                        initializer=ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(input.dtype,
                                                    shape=input.shape)
    saved_mean = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    saved_var = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    # relu folds into the op itself (fused_bn_add_activation analog): the
    # Pallas training-BN kernel applies it in the same HBM pass instead of a
    # separate elementwise op the compiler can't fuse into the kernel.
    fold_act = act if act == "relu" else None
    helper.append_op(
        type="batch_norm",
        inputs={"X": [input.name], "Scale": [scale.name], "Bias": [bias.name],
                "Mean": [mean.name], "Variance": [var.name]},
        outputs={"Y": [out.name], "MeanOut": [mean.name], "VarianceOut": [var.name],
                 "SavedMean": [saved_mean.name], "SavedVariance": [saved_var.name]},
        attrs={"momentum": momentum, "epsilon": epsilon, "act": fold_act or "",
               "is_test": is_test or use_global_stats, "data_layout": data_layout})
    return out if fold_act else helper.append_activation(out, act)


def layer_norm(input: Variable, scale: bool = True, shift: bool = True,
               begin_norm_axis: int = 1, epsilon: float = 1e-5,
               param_attr=None, bias_attr=None, act=None, name=None) -> Variable:
    helper = LayerHelper("layer_norm", name=name)
    if input.shape is None:
        raise ValueError(
            f"layer_norm needs input shape metadata to size its scale/bias "
            f"(input var {input.name} has none — ensure upstream layers "
            f"propagate shapes)")
    norm_shape = [int(np.prod(input.shape[begin_norm_axis:]))]
    ins = {"X": [input.name]}
    if scale:
        s = helper.create_parameter(param_attr, shape=norm_shape, dtype=input.dtype,
                                    default_initializer=ConstantInitializer(1.0))
        ins["Scale"] = [s.name]
    if shift:
        b = helper.create_parameter(bias_attr, shape=norm_shape, dtype=input.dtype, is_bias=True)
        ins["Bias"] = [b.name]
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    mean = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    var = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op(type="layer_norm", inputs=ins,
                     outputs={"Y": [out.name], "Mean": [mean.name], "Variance": [var.name]},
                     attrs={"begin_norm_axis": begin_norm_axis, "epsilon": epsilon})
    return helper.append_activation(out, act)


def rms_norm(input: Variable, epsilon: float = 1e-5, gate: Variable = None,
             group_size: int = 0, param_attr=None, name=None,
             gate_after: str = "") -> Variable:
    """RMS normalisation over the last axis with a learned scale (TPU
    extension, no reference analog): `x / sqrt(mean(x^2) + eps) * scale`.
    With `gate` the input is `x * silu(gate)` first; with `group_size` each
    consecutive group of that many channels is normalised by itself
    (Mamba-2's gated norm). With `gate` and `gate_after` (an activation's
    name, "sigmoid") the gate multiplies the RESULT instead:
    `norm(x) * scale * act(gate)`. Statistics in float32 under AMP."""
    helper = LayerHelper("rms_norm", name=name)
    s = helper.create_parameter(param_attr, shape=[int(input.shape[-1])],
                                dtype=input.dtype,
                                default_initializer=ConstantInitializer(1.0))
    ins = {"X": [input.name], "Scale": [s.name]}
    if gate is not None:
        ins["Gate"] = [gate.name]
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    attrs = {"epsilon": epsilon, "group_size": int(group_size)}
    if gate_after:      # absent otherwise: a gate-first op is as it was
        if gate is None:
            raise ValueError("rms_norm: gate_after needs a gate")
        attrs["gate_after"] = gate_after
    helper.append_op(type="rms_norm", inputs=ins, outputs={"Out": [out.name]},
                     attrs=attrs)
    return out


def rotary_embedding(x: Variable, num_heads: int, theta: float = 10000.0,
                     interleaved: bool = False,
                     rotary_dim: Optional[int] = None, yarn=None,
                     attention_factor: Optional[float] = None,
                     name=None) -> Variable:
    """Rotary position embedding on packed heads [B, T, H*D] (TPU
    extension): every head's channel pairs are rotated by
    `t * theta^(-2j/D)` at position t, j < D/2, angles and multiply-adds in
    float32, one rounding to x's dtype. Two conventions for which channels
    make pair j: rotate-half, (j, j + D/2), the default; and, with
    `interleaved=True`, neighbours (2j, 2j + 1). For q and k of one fused
    product, hand both in as one [B, T, 2*H*D] tensor with `num_heads=2*H`;
    a model that turns a part of each head hands that part in as heads of
    its own. The op computes `out = x C + partner(x) S` on whole heads (C,
    S: [T, D] tables of cosines and signed sines; `partner` brings every
    channel its pair's other channel by a product with a constant 0/1
    matrix: no half-head slices, no concatenate); its backward rule is the
    same pass at the negative angle and keeps no residual.

    A rotation on a part of each head: `rotary_dim` (even, at most the head
    size) turns the first that many channels, pairs within them, and passes
    the rest (C = 1, S = 0 on their lanes: the same pass on whole heads).
    `yarn` = {"factor", "original_max_position_embeddings", "beta_fast",
    "beta_slow"} takes YaRN's blended inverse frequencies
    (`ops.nn_ops.yarn_inv_freq`) in the place of `theta^(-2j/D)`;
    `attention_factor` multiplies cosines and sines, so the turning channels
    and not the passing ones (with `yarn` and none given: YaRN's default,
    `0.1 ln(factor) + 1`)."""
    helper = LayerHelper("rotary_embedding", name=name)
    if x.shape is not None and int(x.shape[-1]) % (2 * num_heads):
        raise ValueError(f"rotary_embedding: {x.shape[-1]} channels are not "
                         f"{num_heads} heads of an even size")
    if rotary_dim is not None and (
            rotary_dim < 2 or rotary_dim % 2 or (
                x.shape is not None
                and rotary_dim > int(x.shape[-1]) // num_heads)):
        raise ValueError(f"rotary_embedding: rotary_dim {rotary_dim} is not "
                         f"an even part of a head")
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    attrs = {"num_heads": int(num_heads), "theta": float(theta)}
    if interleaved:     # absent otherwise: a rotate-half op is as it was
        attrs["interleaved"] = True
    if rotary_dim is not None:
        attrs["rotary_dim"] = int(rotary_dim)
    if yarn is not None:
        attrs["yarn"] = [float(yarn[key]) for key in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow")]
        if attention_factor is None:
            attention_factor = 0.1 * math.log(attrs["yarn"][0]) + 1.0
    if attention_factor is not None:
        attrs["attention_factor"] = float(attention_factor)
    helper.append_op(type="rotary_embedding", inputs={"X": [x.name]},
                     outputs={"Out": [out.name]}, attrs=attrs)
    return out


def swiglu(gate: Variable, up: Variable, name=None) -> Variable:
    """`silu(gate) * up`, the gated MLP's activation (TPU extension): one op,
    the product in float32 under AMP."""
    helper = LayerHelper("swiglu", name=name)
    out = helper.create_variable_for_type_inference(gate.dtype, gate.shape)
    helper.append_op(type="swiglu", inputs={"X": [gate.name],
                                            "Y": [up.name]},
                     outputs={"Out": [out.name]}, attrs={})
    return out


def loop_exit_gate(states: Variable, param_attr=None, bias_attr=None,
                   name=None) -> Variable:
    """The exit distribution of a looped (weight-shared, multi-exit) decoder
    (TPU extension): `states` [P, B, T, D] holds the state after each pass;
    a learned gate `sigmoid(x . w + b)` a position and pass says how much of
    what has not left yet leaves there, the last pass takes the remainder.
    Creates the gate's weight [D, 1] and bias [1].
    Returns p [P, B, T], float32, summing to 1 over P."""
    helper = LayerHelper("loop_exit_gate", name=name)
    d = int(states.shape[-1])
    w = helper.create_parameter(param_attr, shape=[d, 1], dtype=states.dtype)
    b = helper.create_parameter(bias_attr, shape=[1], dtype=states.dtype,
                                is_bias=True)
    out = helper.create_variable_for_type_inference(
        "float32", tuple(states.shape[:-1]))
    helper.append_op(type="loop_exit_gate",
                     inputs={"X": [states.name], "W": [w.name],
                             "Bias": [b.name]},
                     outputs={"Out": [out.name]}, attrs={})
    return out


def loop_exit_loss(p: Variable, ce: Variable, beta: float = 0.0, name=None):
    """A looped decoder's objective (TPU extension): the mean over the
    positions of `sum_t p_t ce_t - beta H(p)`. `p` [P, B, T] from
    `loop_exit_gate`, `ce` [P, B, T(, 1)] each exit's per-position loss.
    Returns (loss [], exit share [P] = mean p_t, exit entropy [] = mean
    H(p)); the last two carry no gradient."""
    helper = LayerHelper("loop_exit_loss", name=name)
    loss = helper.create_variable_for_type_inference("float32", shape=())
    share = helper.create_variable_for_type_inference(
        "float32", shape=(p.shape[0],), stop_gradient=True)
    entropy = helper.create_variable_for_type_inference(
        "float32", shape=(), stop_gradient=True)
    helper.append_op(type="loop_exit_loss",
                     inputs={"P": [p.name], "CE": [ce.name]},
                     outputs={"Loss": [loss.name], "ExitShare": [share.name],
                              "ExitEntropy": [entropy.name]},
                     attrs={"beta": float(beta)})
    return loss, share, entropy


def causal_conv1d(input: Variable, filter_size: int, act: str = "",
                  param_attr=None, bias_attr=None, name=None) -> Variable:
    """Depthwise causal convolution along the time axis of [B, T, C] (TPU
    extension): channel c at time t sees its own last `filter_size` inputs.
    Filter [C, filter_size], bias [C] (`bias_attr=False`: none); `act` ""
    or "silu" is applied inside the op."""
    helper = LayerHelper("causal_conv1d", name=name)
    c = int(input.shape[-1])
    w = helper.create_parameter(param_attr, shape=[c, filter_size],
                                dtype=input.dtype)
    ins = {"X": [input.name], "Filter": [w.name]}
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[c], dtype=input.dtype,
                                    is_bias=True)
        ins["Bias"] = [b.name]
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    helper.append_op(type="causal_conv1d", inputs=ins,
                     outputs={"Out": [out.name]}, attrs={"activation": act})
    return out


def ssd_scan(x: Variable, dt: Variable, b: Variable, c: Variable,
             num_heads: int, n_groups: int, chunk: int = 128,
             a_log_attr=None, d_attr=None, dt_bias_attr=None,
             name=None) -> Variable:
    """Mamba-2's state-space scan (TPU extension; ops/ssm_ops.py). x
    [B, T, H*P], dt [B, T, H] (raw: the op adds `dt_bias` and applies
    softplus), b, c [B, T, G*N]. Creates the per-head parameters A_log
    (A = -exp(A_log)), D (the skip) and dt_bias, each [H]. T must be a
    multiple of `chunk`.

    Which form computes it is decided when the op is lowered, by the backend
    and the shapes alone: on a TPU, with `chunk`, N and a group's
    (H / G)·P multiples of 128 and P a divisor of 128, two Pallas kernels
    (ops/pallas_kernels/ssd_scan.py; per data shard under a mesh); anywhere
    else XLA einsums (ops/ssm_ops.py). Both give the same numbers."""
    helper = LayerHelper("ssd_scan", name=name)
    a_log = helper.create_parameter(a_log_attr, shape=[num_heads],
                                    dtype=x.dtype,
                                    default_initializer=ConstantInitializer(0.0))
    d = helper.create_parameter(d_attr, shape=[num_heads], dtype=x.dtype,
                                default_initializer=ConstantInitializer(1.0))
    dt_bias = helper.create_parameter(dt_bias_attr, shape=[num_heads],
                                      dtype=x.dtype, is_bias=True)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    helper.append_op(
        type="ssd_scan",
        inputs={"X": [x.name], "Dt": [dt.name], "ALog": [a_log.name],
                "B": [b.name], "C": [c.name], "D": [d.name],
                "DtBias": [dt_bias.name]},
        outputs={"Out": [out.name]},
        attrs={"num_heads": int(num_heads), "n_groups": int(n_groups),
               "chunk": int(chunk)})
    return out


def gated_delta_rule(q: Variable, k: Variable, v: Variable, g: Variable,
                     beta: Variable, chunk: int = 64,
                     return_decay_floor: bool = False, qk_l2norm: float = 0.0,
                     a_log: Variable = None, dt_bias: Variable = None,
                     name=None):
    """The chunked gated delta rule with a decay per channel, Kimi Delta
    Attention's state update (TPU extension; ops/linear_attn_ops.py has the
    equations and the sub-block scheme that holds under strong decay). q, k
    [B, T, H, K], v [B, T, H, V], g [B, T, H, K] the log-decay a channel
    (<= 0), beta [B, T, H]; T whole chunks of `chunk`, a multiple of 16; the
    output's scale is K^-1/2. Returns o [B, T, H, V] in q's dtype; with
    `return_decay_floor` also the most negative cumulative log-decay a chunk
    reaches, a float32 scalar without gradient. Decays, beta and the states
    are float32 inside whatever arrives; the products take q's dtype.

    Two parts of a KDA layer can be taken into the op, where they are made a
    group of chunks at a time and never held for a whole layer: `qk_l2norm`
    (an epsilon) divides q and k by max(their norm, epsilon) a head and
    position; `a_log` [H] and `dt_bias` [H * K] (parameters) make the
    log-decay inside, `-exp(a_log) softplus(g + dt_bias)`, from the decay
    gate's raw values handed in as `g`."""
    helper = LayerHelper("gated_delta_rule", name=name)
    out = helper.create_variable_for_type_inference(q.dtype, v.shape)
    floor = helper.create_variable_for_type_inference(
        "float32", shape=(), stop_gradient=True)
    ins = {"Q": [q.name], "K": [k.name], "V": [v.name], "G": [g.name],
           "Beta": [beta.name]}
    if (a_log is None) != (dt_bias is None):
        raise ValueError("gated_delta_rule: a_log and dt_bias come together")
    if a_log is not None:
        ins.update(ALog=[a_log.name], DtBias=[dt_bias.name])
    attrs = {"chunk": int(chunk)}
    if qk_l2norm:
        attrs["qk_l2norm"] = float(qk_l2norm)
    helper.append_op(
        type="gated_delta_rule", inputs=ins,
        outputs={"Out": [out.name], "DecayFloor": [floor.name]}, attrs=attrs)
    return (out, floor) if return_decay_floor else out


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, name=None) -> Variable:
    helper = LayerHelper("group_norm", name=name)
    c = input.shape[1]
    ins = {"X": [input.name]}
    if param_attr is not False:
        s = helper.create_parameter(param_attr, shape=[c], dtype=input.dtype,
                                    default_initializer=ConstantInitializer(1.0))
        ins["Scale"] = [s.name]
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[c], dtype=input.dtype, is_bias=True)
        ins["Bias"] = [b.name]
    out = helper.create_variable_for_type_inference(input.dtype)
    mean = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    var = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op(type="group_norm", inputs=ins,
                     outputs={"Y": [out.name], "Mean": [mean.name], "Variance": [var.name]},
                     attrs={"groups": groups, "epsilon": epsilon})
    return helper.append_activation(out, act)


def dropout(x: Variable, dropout_prob: float, is_test: bool = False, seed=None,
            name=None, dropout_implementation: str = "downgrade_in_infer") -> Variable:
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    mask = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op(type="dropout", inputs={"X": [x.name]},
                     outputs={"Out": [out.name], "Mask": [mask.name]},
                     attrs={"dropout_prob": dropout_prob, "is_test": is_test,
                            "dropout_implementation": dropout_implementation})
    return out


def softmax(input: Variable, axis: int = -1, use_cudnn: bool = False, name=None) -> Variable:
    helper = LayerHelper("softmax", name=name)
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    helper.append_op(type="softmax", inputs={"X": [input.name]},
                     outputs={"Out": [out.name]}, attrs={"axis": axis})
    return out


def matmul(x: Variable, y: Variable, transpose_x: bool = False,
           transpose_y: bool = False, alpha: float = 1.0, name=None) -> Variable:
    helper = LayerHelper("matmul", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="matmul", inputs={"X": [x.name], "Y": [y.name]},
                     outputs={"Out": [out.name]},
                     attrs={"transpose_X": transpose_x, "transpose_Y": transpose_y,
                            "alpha": alpha})
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None) -> Variable:
    helper = LayerHelper("mul", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="mul", inputs={"X": [x.name], "Y": [y.name]},
                     outputs={"Out": [out.name]},
                     attrs={"x_num_col_dims": x_num_col_dims, "y_num_col_dims": y_num_col_dims})
    return out


def cos_sim(X: Variable, Y: Variable, name=None) -> Variable:
    """Cosine similarity along the last axis (reference nn.py cos_sim →
    cos_sim_op.cc). Returns [..., 1]."""
    helper = LayerHelper("cos_sim", name=name)
    out = helper.create_variable_for_type_inference(X.dtype)
    xn = helper.create_variable_for_type_inference(X.dtype)
    yn = helper.create_variable_for_type_inference(X.dtype)
    helper.append_op(type="cos_sim", inputs={"X": [X.name], "Y": [Y.name]},
                     outputs={"Out": [out.name], "XNorm": [xn.name],
                              "YNorm": [yn.name]}, attrs={})
    return out


# -- losses -----------------------------------------------------------------

def cross_entropy(input: Variable, label: Variable, soft_label: bool = False,
                  ignore_index: int = -100) -> Variable:
    helper = LayerHelper("cross_entropy")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="cross_entropy",
                     inputs={"X": [input.name], "Label": [label.name]},
                     outputs={"Out": [out.name]},
                     attrs={"soft_label": soft_label, "ignore_index": ignore_index})
    return out


def softmax_with_cross_entropy(logits: Variable, label: Variable,
                               soft_label: bool = False, ignore_index: int = -100,
                               return_softmax: bool = False, axis: int = -1):
    helper = LayerHelper("softmax_with_cross_entropy")
    loss = helper.create_variable_for_type_inference(logits.dtype)
    sm = helper.create_variable_for_type_inference(logits.dtype)
    helper.append_op(type="softmax_with_cross_entropy",
                     inputs={"Logits": [logits.name], "Label": [label.name]},
                     outputs={"Loss": [loss.name], "Softmax": [sm.name]},
                     attrs={"soft_label": soft_label, "ignore_index": ignore_index,
                            "axis": axis})
    if return_softmax:
        return loss, sm
    return loss


def linear_softmax_with_cross_entropy(input: Variable, label: Variable,
                                      size: int, ignore_index: int = -100,
                                      param_attr=None, bias_attr=None,
                                      return_rows: bool = False, name=None,
                                      tied_table: bool = False):
    """`softmax_with_cross_entropy(fc(input, size, num_flatten_dims=rank-1),
    label, ignore_index=ignore_index)` as one op (TPU extension, no
    reference analog): the same per-position loss [..., 1] and the same
    gradients, but only the positions whose label is not `ignore_index` are
    projected onto the [hidden, size] matrix, a chunk of rows at a time, so
    [positions, size] logits never exist: a chunk's are held, and made again
    in the backward pass (ops/nn_ops.py `_linear_ce`; the rows of a chunk
    follow from the shapes, `linear_ce_chunk_rows`). For a masked-LM head,
    where 85% of the labels are ignored, and for a vocabulary whose logits
    over all positions do not fit. The op is its own rematerialisation (it
    keeps its inputs and a float32 scalar a row), so a remat policy does not
    checkpoint it again. The parameters are those `fc` would create (weight
    [hidden, size], then bias [size]; `bias_attr=False`: no bias).
    `tied_table=True`: the weight is [size, hidden], an embedding's table
    (name it in `param_attr`: a name the program already holds is that
    parameter), read transposed; its one gradient is the lookup's rows plus
    the projection's. `return_rows=True` also returns the rows the forward
    pass projected (whole chunks) and the labelled count, both int32
    scalars."""
    helper = LayerHelper("fc", name=name)
    hidden = input.shape[-1]
    w = helper.create_parameter(
        param_attr, shape=[size, hidden] if tied_table else [hidden, size],
        dtype=input.dtype)
    ins = {"X": [input.name], "W": [w.name], "Label": [label.name]}
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[size],
                                    dtype=input.dtype, is_bias=True)
        ins["Bias"] = [b.name]
    loss = helper.create_variable_for_type_inference(
        input.dtype, shape=tuple(input.shape[:-1]) + (1,))
    rows = helper.create_variable_for_type_inference("int32", shape=())
    labelled = helper.create_variable_for_type_inference("int32", shape=())
    helper.append_op(type="linear_softmax_with_cross_entropy",
                     inputs=ins,
                     outputs={"Loss": [loss.name],
                              "RowsComputed": [rows.name],
                              "Labelled": [labelled.name]},
                     attrs={"ignore_index": ignore_index,
                            "transpose_w": bool(tied_table)})
    if return_rows:
        return loss, rows, labelled
    return loss


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100, name=None,
                                      normalize=False) -> Variable:
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="sigmoid_cross_entropy_with_logits",
                     inputs={"X": [x.name], "Label": [label.name]},
                     outputs={"Out": [out.name]},
                     attrs={"ignore_index": ignore_index, "normalize": normalize})
    return out


def square_error_cost(input: Variable, label: Variable) -> Variable:
    helper = LayerHelper("square_error_cost")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="square_error_cost",
                     inputs={"X": [input.name], "Label": [label.name]},
                     outputs={"Out": [out.name]}, attrs={})
    return out


def mean(x: Variable, name=None) -> Variable:
    helper = LayerHelper("mean", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=())
    helper.append_op(type="mean", inputs={"X": [x.name]}, outputs={"Out": [out.name]}, attrs={})
    return out


# -- misc nn ----------------------------------------------------------------

def relu(x, name=None):
    from .ops import _activation_layer
    return _activation_layer("relu", x, {}, name)


def topk(input: Variable, k: int, name=None):
    helper = LayerHelper("top_k", name=name)
    values = helper.create_variable_for_type_inference(input.dtype)
    indices = helper.create_variable_for_type_inference("int64", stop_gradient=True)
    helper.append_op(type="top_k", inputs={"X": [input.name]},
                     outputs={"Out": [values.name], "Indices": [indices.name]},
                     attrs={"k": k})
    return values, indices


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="l2_normalize", inputs={"X": [x.name]},
                     outputs={"Out": [out.name]}, attrs={"axis": axis, "epsilon": epsilon})
    return out


def clip(x, min, max, name=None):
    helper = LayerHelper("clip", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="clip", inputs={"X": [x.name]}, outputs={"Out": [out.name]},
                     attrs={"min": min, "max": max})
    return out


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="clip_by_norm", inputs={"X": [x.name]},
                     outputs={"Out": [out.name]}, attrs={"max_norm": max_norm})
    return out


def one_hot(input: Variable, depth: int, allow_out_of_range: bool = False) -> Variable:
    helper = LayerHelper("one_hot")
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op(type="one_hot", inputs={"X": [input.name]},
                     outputs={"Out": [out.name]}, attrs={"depth": depth})
    return out


def prelu(x, mode: str = "all", param_attr=None, name=None) -> Variable:
    helper = LayerHelper("prelu", name=name)
    if mode == "all":
        alpha_shape = [1]
    elif mode == "channel":
        alpha_shape = [x.shape[1]]
    else:
        alpha_shape = list(x.shape[1:])
    alpha = helper.create_parameter(param_attr, shape=alpha_shape, dtype=x.dtype,
                                    default_initializer=ConstantInitializer(0.25))
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="prelu", inputs={"X": [x.name], "Alpha": [alpha.name]},
                     outputs={"Out": [out.name]}, attrs={"mode": mode})
    return out


def linear_chain_crf(input, label, length=None, param_attr=None, name=None):
    """CRF log-likelihood (reference nn.py linear_chain_crf over
    linear_chain_crf_op.cc). input: emissions [B, T, D]; label [B, T] (or
    [B, T, 1]); length [B]. Transition param is [D+2, D] (row0 start, row1
    end). Returns negative log-likelihood [B, 1] suitable for mean()."""
    helper = LayerHelper("linear_chain_crf", name=name)
    D = input.shape[-1]
    transition = helper.create_parameter(param_attr, shape=[D + 2, D],
                                         dtype=input.dtype)
    ll = helper.create_variable_for_type_inference(input.dtype)
    em_exps = helper.create_variable_for_type_inference(input.dtype)
    tr_exps = helper.create_variable_for_type_inference(input.dtype)
    alpha = helper.create_variable_for_type_inference(input.dtype)
    inputs = {"Emission": [input.name], "Transition": [transition.name],
              "Label": [label.name]}
    if length is not None:
        inputs["Length"] = [length.name]
    helper.append_op(
        type="linear_chain_crf", inputs=inputs,
        outputs={"LogLikelihood": [ll.name], "EmissionExps": [em_exps.name],
                 "TransitionExps": [tr_exps.name], "Alpha": [alpha.name]},
        attrs={})
    neg = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="scale", inputs={"X": [ll.name]},
                     outputs={"Out": [neg.name]},
                     attrs={"scale": -1.0, "bias": 0.0})
    return neg


def crf_decoding(input, param_attr=None, length=None, label=None, name=None):
    """Viterbi decode [B, T] int64 (crf_decoding_op.cc). param_attr must name
    the transition parameter trained by linear_chain_crf."""
    helper = LayerHelper("crf_decoding", name=name)
    from ..param_attr import ParamAttr
    attr = ParamAttr._to_attr(param_attr)
    if attr is None or attr.name is None:
        raise ValueError("crf_decoding needs param_attr naming the trained "
                         "transition parameter")
    blk = helper.main_program.global_block()
    if not blk.has_var(attr.name):
        D = input.shape[-1]
        helper.create_parameter(attr, shape=[D + 2, D], dtype=input.dtype)
    path = helper.create_variable_for_type_inference("int64")
    inputs = {"Emission": [input.name], "Transition": [attr.name]}
    if length is not None:
        inputs["Length"] = [length.name]
    if label is not None:
        inputs["Label"] = [label.name]
    helper.append_op(type="crf_decoding", inputs=inputs,
                     outputs={"ViterbiPath": [path.name]}, attrs={})
    return path


def flash_attention(q: Variable, k: Variable, v: Variable,
                    attn_bias: Optional[Variable] = None,
                    causal: bool = False, dropout_prob: float = 0.0,
                    is_test: bool = False, num_heads: Optional[int] = None,
                    num_kv_heads: Optional[int] = None,
                    window: Optional[int] = None,
                    name=None) -> Variable:
    """Fused memory-efficient attention.

    TPU-native replacement for the matmul→softmax→dropout→matmul attention
    pattern (no reference analog — the reference materializes the [B,H,T,T]
    score tensor). Pallas kernel on TPU; blockwise JAX elsewhere.

    Two layouts:
    - [B, H, T, D] 4D q/k/v; `attn_bias` broadcastable to [B, H, T, T].
    - packed [B, T, H·D] 3D q/k/v with `num_heads` (required for 3D) — the
      convenience form for fused-qkv models; adapted internally to the
      folded kernel layout. `attn_bias` is the [B, 1, T] mask.

    Grouped-query attention: k and v may have fewer heads than q, a divisor
    of q's count (4D: their head dim says so; packed: `num_kv_heads`, with
    k [B, T, num_kv_heads·D]). Query head h reads key/value head
    h // (H / Hkv); the kernels index the shared head, nothing is copied.

    Two head sizes: q and k share D, the width the scores contract over and
    the one the softmax scale D^-1/2 is taken from; v may have a head size
    Dv of its own, said by its shape (4D: [B, Hkv, T, Dv]; packed:
    [B, T, num_kv_heads·Dv]), and the result then has it too ([B, H, T, Dv]
    or [B, T, H·Dv]): latent attention's 192-wide keys beside 128-wide
    values.

    A sliding window: with `causal=True`, `window=W` lets query i see keys
    i - W < j <= i (its own and the W - 1 before it); the kernels visit the
    band's tiles only. A window as long as the sequence is the causal call."""
    helper = LayerHelper("flash_attention", name=name)
    out_shape = q.shape
    if q.shape is not None and v.shape is not None:
        width = None            # of the result's last axis, where v says it
        if len(q.shape) == 4:
            width = v.shape[3]
        elif num_heads is not None:
            width = int(num_heads) * (int(v.shape[2])
                                      // int(num_kv_heads or num_heads))
        if width is not None and width != q.shape[-1]:
            out_shape = tuple(q.shape[:-1]) + (width,)
    out = helper.create_variable_for_type_inference(q.dtype, shape=out_shape)
    inputs = {"Q": [q.name], "K": [k.name], "V": [v.name]}
    if attn_bias is not None:
        inputs["BiasQK"] = [attn_bias.name]
    attrs = {"causal": causal, "dropout_prob": dropout_prob,
             "is_test": is_test}
    if num_heads is not None:
        attrs["num_heads"] = int(num_heads)
    if num_kv_heads is not None and num_kv_heads != num_heads:
        attrs["num_kv_heads"] = int(num_kv_heads)
    if window is not None:      # absent otherwise: an op without is as it was
        attrs["window"] = int(window)
    helper.append_op(type="flash_attention", inputs=inputs,
                     outputs={"Out": [out.name]}, attrs=attrs)
    return out


def fused_conv_bn(input: Variable, num_filters: int, stride: int = 1,
                  act: Optional[str] = None,
                  residual: Optional[Variable] = None,
                  is_test: bool = False, momentum: float = 0.9,
                  epsilon: float = 1e-5, param_attr=None, bn_param_attr=None,
                  bn_bias_attr=None, moving_mean_name=None,
                  moving_variance_name=None, name=None) -> Variable:
    """Fused 1×1 conv (no bias) + batch_norm (+relu, +residual) as ONE op.

    The training analog of the inference conv_bn_fuse pass, for the resnet
    bottleneck tail where conv→BN→(+shortcut)→relu dominates HBM traffic;
    lowered to the Pallas conv+BN kernel on TPU and to a bitwise-equal XLA
    composition elsewhere (ops/pallas_kernels/fused_bn.py). Used by
    models/resnet.py when ``PDTPU_CONV_BN_FUSION`` is enabled."""
    helper = LayerHelper("fused_conv_bn", name=name)
    num_channels = input.shape[1]
    w = helper.create_parameter(
        param_attr, shape=[num_filters, num_channels, 1, 1],
        dtype=input.dtype,
        default_initializer=NormalInitializer(
            0.0, (2.0 / num_channels) ** 0.5))
    scale = helper.create_parameter(
        bn_param_attr, shape=[num_filters], dtype=input.dtype,
        default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(bn_bias_attr, shape=[num_filters],
                                   dtype=input.dtype, is_bias=True)
    mean = helper.create_global_variable(
        [num_filters], input.dtype, name=moving_mean_name,
        initializer=ConstantInitializer(0.0))
    var = helper.create_global_variable(
        [num_filters], input.dtype, name=moving_variance_name,
        initializer=ConstantInitializer(1.0))
    out_shape = None
    if input.shape is not None and len(input.shape) == 4:
        out_shape = (input.shape[0], num_filters,
                     _conv_out_dim(input.shape[2], 1, 0, stride),
                     _conv_out_dim(input.shape[3], 1, 0, stride))
    out = helper.create_variable_for_type_inference(input.dtype, out_shape)
    saved_mean = helper.create_variable_for_type_inference(
        input.dtype, stop_gradient=True)
    saved_var = helper.create_variable_for_type_inference(
        input.dtype, stop_gradient=True)
    ins = {"Input": [input.name], "Filter": [w.name], "Scale": [scale.name],
           "Bias": [bias.name], "Mean": [mean.name], "Variance": [var.name]}
    if residual is not None:
        ins["Residual"] = [residual.name]
    helper.append_op(
        type="fused_conv_bn", inputs=ins,
        outputs={"Y": [out.name], "MeanOut": [mean.name],
                 "VarianceOut": [var.name], "SavedMean": [saved_mean.name],
                 "SavedVariance": [saved_var.name]},
        attrs={"stride": int(stride), "epsilon": epsilon,
               "momentum": momentum, "act": act or "", "is_test": is_test})
    return out


def flash_attention_sparse(q: Variable, k: Variable, v: Variable,
                           num_heads: int, q_seg: Variable, k_seg: Variable,
                           causal: bool = False, dropout_prob: float = 0.0,
                           is_test: bool = False, name=None) -> Variable:
    """Block-sparse packed-segment attention on [B, T, H·D] rows.

    Instead of a dense additive [B, 1, Tq, Tk] mask this takes the packed
    segment-id rows themselves (reader.pack_by_tokens layout: 1-based
    contiguous ids, 0 = pad tail); visibility is carried as a compact
    per-row k-range descriptor and fully-masked key blocks are skipped in
    both forward and backward grids — work scales with real tokens, not
    padding. See ops/pallas_kernels/flash_attention.py."""
    helper = LayerHelper("flash_attention_sparse", name=name)
    out = helper.create_variable_for_type_inference(q.dtype, shape=q.shape)
    helper.append_op(
        type="flash_attention_sparse",
        inputs={"Q": [q.name], "K": [k.name], "V": [v.name],
                "QSeg": [q_seg.name], "KSeg": [k_seg.name]},
        outputs={"Out": [out.name]},
        attrs={"num_heads": int(num_heads), "causal": causal,
               "dropout_prob": dropout_prob, "is_test": is_test})
    return out


def moe_ffn(input: Variable, num_experts: int, hidden_size: int, k: int = 2,
            act: str = "gelu", ep_axis: str = "ep", param_attr=None,
            bias_attr=None, experts_held=None, scoring: str = "softmax",
            correction_bias: bool = False, norm_topk: bool = True,
            routed_scaling: float = 1.0, return_counts: bool = False,
            gated: bool = False, name=None):
    """Mixture-of-Experts feed-forward block (no reference analog — the
    reference predates MoE; exposed like its fused composite ops).

    Top-k routing over all `num_experts` in float32 (`scoring` "softmax", or
    "sigmoid" with, under `correction_bias=True`, a selection-only bias
    parameter `<name>.corr_bias`; the chosen scores divided by their sum
    under `norm_topk`, times `routed_scaling`). An expert is
    `act(x·W1)·W2`, or with `gated=True` `(act(x·W1) ⊙ x·W3)·W2` with a third
    parameter `<name>.w3` shaped like `w1`. Dropless: the (token,
    expert) pairs are sorted by expert and each expert multiplies exactly
    the tokens routed to it (parallel/moe.py) — no capacity, no dropped
    token. `experts_held = (first, count)` makes the layer hold that range
    of the experts only (a chip's share of an expert-parallel deployment):
    it creates their weights alone, and pairs on absent experts add
    nothing. Under a compiled mesh with an `ep` axis a layer that holds all
    its experts shards them over the axis. `bias_attr=False` leaves the
    experts without biases.

    Returns (out, aux_loss): add `aux_loss` (Switch load-balance term,
    scaled by your coefficient) to the training loss. `return_counts=True`
    also returns the pairs on each held expert [count] and the pairs held
    (int32) — fetch them where the loss is fetched."""
    helper = LayerHelper("moe_ffn", name=name)
    d = input.shape[-1]
    first, held = experts_held or (0, num_experts)

    def _attr(base, suffix):
        # distinct parameters: clone the user attr per param (a shared
        # ParamAttr instance would be renamed on first use and alias them)
        import copy
        a = copy.copy(ParamAttr._to_attr(base))
        if a.name is not None:
            a.name = f"{a.name}.{suffix}"
        return a

    gate = helper.create_parameter(_attr(param_attr, "gate"),
                                   shape=[d, num_experts], dtype=input.dtype)
    w1 = helper.create_parameter(_attr(param_attr, "w1"),
                                 shape=[held, d, hidden_size],
                                 dtype=input.dtype)
    w2 = helper.create_parameter(_attr(param_attr, "w2"),
                                 shape=[held, hidden_size, d],
                                 dtype=input.dtype)
    ins = {"X": [input.name], "GateW": [gate.name], "W1": [w1.name],
           "W2": [w2.name]}
    if gated:
        w3 = helper.create_parameter(_attr(param_attr, "w3"),
                                     shape=[held, d, hidden_size],
                                     dtype=input.dtype)
        ins["W3"] = [w3.name]
    if bias_attr is not False:
        base = param_attr if bias_attr is None else bias_attr
        b1 = helper.create_parameter(_attr(base, "b1"),
                                     shape=[held, hidden_size],
                                     dtype=input.dtype, is_bias=True)
        b2 = helper.create_parameter(_attr(base, "b2"), shape=[held, d],
                                     dtype=input.dtype, is_bias=True)
        ins.update(B1=[b1.name], B2=[b2.name])
    if correction_bias:
        cb_attr = _attr(param_attr, "corr_bias")
        cb_attr.trainable = False       # balanced outside the gradient
        cb_attr.initializer = ConstantInitializer(0.0)
        cb = helper.create_parameter(cb_attr, shape=[num_experts],
                                     dtype=input.dtype, is_bias=True)
        ins["CorrectionBias"] = [cb.name]
    out = helper.create_variable_for_type_inference(input.dtype, shape=input.shape)
    aux = helper.create_variable_for_type_inference(input.dtype, shape=())
    tokens = helper.create_variable_for_type_inference(
        "int32", shape=(held,), stop_gradient=True)
    pairs = helper.create_variable_for_type_inference(
        "int32", shape=(), stop_gradient=True)
    helper.append_op(
        type="moe_ffn", inputs=ins,
        outputs={"Out": [out.name], "AuxLoss": [aux.name],
                 "TokensPerExpert": [tokens.name], "PairsHeld": [pairs.name]},
        attrs={"k": k, "act": act, "ep_axis": ep_axis,
               "experts_first": int(first), "scoring": scoring,
               "norm_topk": bool(norm_topk),
               "routed_scaling": float(routed_scaling)})
    if return_counts:
        return out, aux, tokens, pairs
    return out, aux


def nce(input: Variable, label: Variable, num_total_classes: int,
        sample_weight=None, param_attr=None, bias_attr=None,
        num_neg_samples: int = 10, name=None, sampler: str = "uniform",
        custom_dist=None, seed: int = 0, is_sparse: bool = False) -> Variable:
    """Noise-contrastive estimation loss (reference layers/nn.py nce →
    nce_op.cc). Samplers: uniform, log_uniform (Zipfian), custom_dist (a
    probability list over classes) — each with its own noise correction
    (nce_op.h:51). Returns per-row cost [B, 1]."""
    samplers = {"uniform": 0, "log_uniform": 1, "custom_dist": 2}
    if sampler not in samplers:
        raise ValueError(f"nce: unknown sampler {sampler!r}; "
                         f"choose from {sorted(samplers)}")
    if sampler == "custom_dist" and custom_dist is None:
        raise ValueError("nce: sampler='custom_dist' needs custom_dist")
    if custom_dist is not None and sampler != "custom_dist":
        raise ValueError(
            f"nce: custom_dist was given but sampler={sampler!r} — it "
            f"would be silently ignored; pass sampler='custom_dist'")
    if sample_weight is not None:
        raise NotImplementedError("nce: sample_weight is not supported")
    helper = LayerHelper("nce", name=name)
    dim = input.shape[-1]
    w = helper.create_parameter(param_attr, shape=[num_total_classes, dim],
                                dtype=input.dtype)
    inputs = {"Input": [input.name], "Weight": [w.name],
              "Label": [label.name]}
    if sampler == "custom_dist":
        from . import tensor as _tensor
        probs = _tensor.assign(
            np.asarray(custom_dist, dtype="float32").reshape(-1))
        inputs["CustomDistProbs"] = [probs.name]
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[num_total_classes],
                                    dtype=input.dtype, is_bias=True)
        inputs["Bias"] = [b.name]
    cost = helper.create_variable_for_type_inference(input.dtype)
    sample_logits = helper.create_variable_for_type_inference(
        input.dtype, stop_gradient=True)
    sample_labels = helper.create_variable_for_type_inference(
        "int64", stop_gradient=True)
    helper.append_op(
        type="nce", inputs=inputs,
        outputs={"Cost": [cost.name], "SampleLogits": [sample_logits.name],
                 "SampleLabels": [sample_labels.name]},
        attrs={"num_total_classes": num_total_classes,
               "num_neg_samples": num_neg_samples, "seed": seed,
               "sampler": samplers[sampler],
               "is_sparse": is_sparse})
    return cost


def hsigmoid(input: Variable, label: Variable, num_classes: int,
             param_attr=None, bias_attr=None, name=None,
             path_table=None, path_code=None, is_custom: bool = False,
             is_sparse: bool = False) -> Variable:
    """Hierarchical sigmoid (reference layers/nn.py hsigmoid →
    hierarchical_sigmoid_op.cc): default complete binary tree, or a custom
    tree via `path_table`/`path_code` [B, L] variables (node ids with −1
    padding / branch bits — matrix_bit_code.h CustomCode)."""
    if is_custom and (path_table is None or path_code is None):
        raise ValueError("hsigmoid: is_custom=True needs both path_table "
                         "and path_code")
    helper = LayerHelper("hierarchical_sigmoid", name=name)
    dim = input.shape[-1]
    w = helper.create_parameter(param_attr, shape=[num_classes - 1, dim],
                                dtype=input.dtype)
    inputs = {"X": [input.name], "W": [w.name], "Label": [label.name]}
    if path_table is not None:
        inputs["PathTable"] = [path_table.name]
        inputs["PathCode"] = [path_code.name]
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[num_classes - 1],
                                    dtype=input.dtype, is_bias=True)
        inputs["Bias"] = [b.name]
    out = helper.create_variable_for_type_inference(input.dtype)
    pre = helper.create_variable_for_type_inference(input.dtype,
                                                    stop_gradient=True)
    helper.append_op(type="hierarchical_sigmoid", inputs=inputs,
                     outputs={"Out": [out.name], "PreOut": [pre.name]},
                     attrs={"num_classes": num_classes})
    return out


def sampled_softmax_with_cross_entropy(logits: Variable, label: Variable,
                                       num_samples: int,
                                       num_true: int = 1,
                                       remove_accidental_hits: bool = True,
                                       use_customized_samples: bool = False,
                                       seed: int = 0, name=None) -> Variable:
    """Sampled softmax CE (reference layers/nn.py
    sampled_softmax_with_cross_entropy → sample_logits_op.cc + softmax CE
    over [true + sampled] classes)."""
    if use_customized_samples:
        raise NotImplementedError(
            "sampled_softmax_with_cross_entropy: use_customized_samples is "
            "not supported — only the uniform sampler is implemented")
    helper = LayerHelper("sampled_softmax_with_cross_entropy", name=name)
    sampled_logits = helper.create_variable_for_type_inference(logits.dtype)
    sampled_label = helper.create_variable_for_type_inference(
        "int64", stop_gradient=True)
    samples = helper.create_variable_for_type_inference(
        "int64", stop_gradient=True)
    probs = helper.create_variable_for_type_inference(
        logits.dtype, stop_gradient=True)
    helper.append_op(
        type="sample_logits",
        inputs={"Logits": [logits.name], "Labels": [label.name]},
        outputs={"SampledLogits": [sampled_logits.name],
                 "SampledLabels": [sampled_label.name],
                 "Samples": [samples.name],
                 "Probabilities": [probs.name]},
        attrs={"num_samples": num_samples, "seed": seed,
               "remove_accidental_hits": remove_accidental_hits})
    return softmax_with_cross_entropy(sampled_logits, sampled_label)
