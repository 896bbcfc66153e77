"""Auto-generated single-in/single-out layers.

Reference analog: ``python/paddle/fluid/layers/ops.py`` + the
layer_function_generator — thin wrappers emitting one op each.
"""
from __future__ import annotations

import sys

from ..layer_helper import LayerHelper


def _activation_layer(op_type, x, attrs, name=None):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    helper.append_op(type=op_type, inputs={"X": [x.name]},
                     outputs={"Out": [out.name]}, attrs=attrs or {})
    return out


_UNARY_OPS = [
    "sigmoid", "tanh", "softplus", "softsign", "logsigmoid",
    "exp", "log", "abs", "sqrt", "rsqrt", "square", "ceil", "floor", "round",
    "reciprocal", "sign", "sin", "cos", "tan", "asin", "acos", "atan",
    "sinh", "cosh", "erf", "tanh_shrink", "mish", "silu",
]

_mod = sys.modules[__name__]
for _op in _UNARY_OPS:
    def _make(op_type):
        def layer(x, name=None):
            return _activation_layer(op_type, x, {}, name)
        layer.__name__ = op_type
        layer.__doc__ = f"Emit a `{op_type}` op (reference activation_op.cc family)."
        return layer
    setattr(_mod, _op, _make(_op))


def leaky_relu(x, alpha=0.02, name=None):
    return _activation_layer("leaky_relu", x, {"alpha": alpha}, name)


def elu(x, alpha=1.0, name=None):
    return _activation_layer("elu", x, {"alpha": alpha}, name)


def gelu(x, approximate=False, name=None):
    return _activation_layer("gelu", x, {"approximate": approximate}, name)


def relu2(x, name=None):
    """Squared ReLU, max(x, 0)^2 (TPU extension; also `act="relu2"` of
    `fc`)."""
    return _activation_layer("relu2", x, {}, name)


def relu6(x, threshold=6.0, name=None):
    return _activation_layer("relu6", x, {"threshold": threshold}, name)


def swish(x, beta=1.0, name=None):
    return _activation_layer("swish", x, {"beta": beta}, name)


def hard_sigmoid(x, slope=0.2, offset=0.5, name=None):
    return _activation_layer("hard_sigmoid", x, {"slope": slope, "offset": offset}, name)


def hard_swish(x, threshold=6.0, scale=6.0, offset=3.0, name=None):
    return _activation_layer("hard_swish", x,
                             {"threshold": threshold, "scale": scale, "offset": offset}, name)


def log_softmax(x, axis=-1, name=None):
    return _activation_layer("log_softmax", x, {"axis": axis}, name)


def pow(x, factor=1.0, name=None):
    return _activation_layer("pow", x, {"factor": factor}, name)


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    helper.append_op(type="scale", inputs={"X": [x.name]}, outputs={"Out": [out.name]},
                     attrs={"scale": scale, "bias": bias, "bias_after_scale": bias_after_scale})
    return helper.append_activation(out, act)


def _elementwise_layer(op_type, x, y, axis=-1, act=None, name=None):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    helper.append_op(type=op_type, inputs={"X": [x.name], "Y": [y.name]},
                     outputs={"Out": [out.name]}, attrs={"axis": axis})
    return helper.append_activation(out, act)


for _op in ["elementwise_add", "elementwise_sub", "elementwise_mul",
            "elementwise_div", "elementwise_min", "elementwise_max",
            "elementwise_pow", "elementwise_mod", "elementwise_floordiv"]:
    def _make_ew(op_type):
        def layer(x, y, axis=-1, act=None, name=None):
            return _elementwise_layer(op_type, x, y, axis, act, name)
        layer.__name__ = op_type
        return layer
    setattr(_mod, _op, _make_ew(_op))


def _compare_layer(op_type, x, y, cond=None, name=None):
    helper = LayerHelper(op_type, name=name)
    # cond= writes into an existing bool var (the While-loop condition idiom:
    # layers.less_than(i, limit, cond=cond) re-binds cond each iteration).
    out = cond if cond is not None else helper.create_variable_for_type_inference(
        "bool", x.shape, stop_gradient=True)
    helper.append_op(type=op_type, inputs={"X": [x.name], "Y": [y.name]},
                     outputs={"Out": [out.name]}, attrs={})
    return out


for _op in ["equal", "not_equal", "less_than", "less_equal", "greater_than",
            "greater_equal", "logical_and", "logical_or", "logical_xor"]:
    def _make_cmp(op_type):
        def layer(x, y, cond=None, name=None):
            return _compare_layer(op_type, x, y, cond, name)
        layer.__name__ = op_type
        return layer
    setattr(_mod, _op, _make_cmp(_op))


def logical_not(x, name=None):
    helper = LayerHelper("logical_not", name=name)
    out = helper.create_variable_for_type_inference("bool", x.shape, stop_gradient=True)
    helper.append_op(type="logical_not", inputs={"X": [x.name]},
                     outputs={"Out": [out.name]}, attrs={})
    return out
