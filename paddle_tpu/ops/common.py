"""Shared helpers for op implementations."""
from __future__ import annotations

import jax.numpy as jnp


def bcast_y(x, y, axis: int = -1):
    """Paddle elementwise broadcast rule (operators/elementwise/
    elementwise_op_function.h): `y`'s shape is aligned to `x` starting at
    `axis`; axis==-1 means align trailing dims (numpy rule)."""
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    if x.ndim == y.ndim or y.ndim == 0:
        return y
    if axis == -1:
        axis = x.ndim - y.ndim
    new_shape = (1,) * axis + y.shape + (1,) * (x.ndim - axis - y.ndim)
    return y.reshape(new_shape)


def one(outs):
    return {"Out": [outs]}


def opt_input(inputs, slot):
    """Optional input slot: missing key or empty list -> None."""
    vs = inputs.get(slot) or [None]
    return vs[0]


def length_mask(length, B, T, dtype):
    """Padded-sequence validity mask [B, T]: 1.0 where t < length[b].
    length=None means all positions valid (the padded+mask stand-in for the
    reference's LoD metadata)."""
    if length is None:
        return jnp.ones((B, T), dtype)
    return (jnp.arange(T)[None, :] < length.reshape(-1, 1)).astype(dtype)


# Shared activation-name → jax fn map (activation_op.cc functor registry).
# Used by fused ops, rnn cells, and fuse passes; "" / "identity" = no-op.
def _identity(x):
    return x


def relu2(x):
    """Squared ReLU (Primer, So et al. 2021; the expert activation of
    Nemotron-H): max(x, 0)^2."""
    import jax
    return jnp.square(jax.nn.relu(x))


def act_map():
    import jax
    return {
        "": _identity,
        "identity": _identity,
        "relu": jax.nn.relu,
        "tanh": jnp.tanh,
        "sigmoid": jax.nn.sigmoid,
        "gelu": jax.nn.gelu,
        "silu": jax.nn.silu,
        "relu2": relu2,
    }
