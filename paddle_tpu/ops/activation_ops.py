"""Activation ops.

Reference analog: ``paddle/fluid/operators/activation_op.cc`` (~30 activations
registered through a functor table). All map to VPU element-wise code via XLA;
grads come from jax.vjp instead of hand-written GradFunctors.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .common import one, relu2


def _act(name, fn):
    @register_op(name)
    def _impl(ctx, inputs, attrs, _fn=fn):
        (x,) = inputs["X"]
        return one(_fn(x, attrs))
    return _impl


_act("relu", lambda x, a: jax.nn.relu(x))
_act("relu2", lambda x, a: relu2(x))
_act("sigmoid", lambda x, a: jax.nn.sigmoid(x))
_act("tanh", lambda x, a: jnp.tanh(x))
_act("softplus", lambda x, a: jax.nn.softplus(x))
_act("softsign", lambda x, a: jax.nn.soft_sign(x))
_act("relu6", lambda x, a: jnp.clip(x, 0.0, a.get("threshold", 6.0)))
_act("leaky_relu", lambda x, a: jax.nn.leaky_relu(x, a.get("alpha", 0.02)))
_act("elu", lambda x, a: jax.nn.elu(x, a.get("alpha", 1.0)))
_act("gelu", lambda x, a: jax.nn.gelu(x, approximate=a.get("approximate", False)))
_act("swish", lambda x, a: x * jax.nn.sigmoid(a.get("beta", 1.0) * x))
_act("hard_swish", lambda x, a: x * jnp.clip(
    x + a.get("offset", 3.0), 0.0, a.get("threshold", 6.0)) / a.get("scale", 6.0))
_act("hard_sigmoid", lambda x, a: jnp.clip(a.get("slope", 0.2) * x + a.get("offset", 0.5), 0.0, 1.0))
_act("logsigmoid", lambda x, a: jax.nn.log_sigmoid(x))
_act("tanh_shrink", lambda x, a: x - jnp.tanh(x))
_act("softshrink", lambda x, a: jnp.where(
    x > a.get("lambda", 0.5), x - a.get("lambda", 0.5),
    jnp.where(x < -a.get("lambda", 0.5), x + a.get("lambda", 0.5), 0.0)))
_act("hard_shrink", lambda x, a: jnp.where(jnp.abs(x) > a.get("threshold", 0.5), x, 0.0))
_act("thresholded_relu", lambda x, a: jnp.where(x > a.get("threshold", 1.0), x, 0.0))
_act("stanh", lambda x, a: a.get("scale_b", 1.7159) * jnp.tanh(a.get("scale_a", 0.67) * x))
_act("mish", lambda x, a: x * jnp.tanh(jax.nn.softplus(x)))
_act("silu", lambda x, a: jax.nn.silu(x))
_act("exp_act", lambda x, a: jnp.exp(x))


@register_op("prelu")
def _prelu(ctx, inputs, attrs):
    (x,) = inputs["X"]
    (alpha,) = inputs["Alpha"]
    mode = attrs.get("mode", "all")
    if mode == "channel" and alpha.ndim == 1:
        alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    return one(jnp.where(x > 0, x, alpha * x))


@register_op("softmax")
def _softmax(ctx, inputs, attrs):
    (x,) = inputs["X"]
    return one(jax.nn.softmax(x, axis=attrs.get("axis", -1)))


@register_op("log_softmax")
def _log_softmax(ctx, inputs, attrs):
    (x,) = inputs["X"]
    return one(jax.nn.log_softmax(x, axis=attrs.get("axis", -1)))


@register_op("maxout")
def _maxout(ctx, inputs, attrs):
    (x,) = inputs["X"]
    groups = attrs["groups"]
    n, c = x.shape[0], x.shape[1]
    rest = x.shape[2:]
    return one(jnp.max(x.reshape((n, c // groups, groups) + rest), axis=2))
