"""Control-flow ops.

Reference analog: ``paddle/fluid/operators/controlflow/`` (while_op.cc,
conditional_block_op.cc) and recurrent_op.cc — block-attribute ops interpreted
by the executor.

TPU-native redesign: data-dependent Python control flow cannot live inside a
traced program, so these lower to `lax.while_loop` / `lax.cond` / `lax.scan`
over sub-blocks lowered as pure functions. `static_rnn` (lax.scan) is the
differentiable path (reference StaticRNN); `while` is provided for parity and
is non-differentiable (as in most real uses: inference decoding loops).
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_op


def _lower_subblock(ctx, block, env_names: List[str]):
    """Build a pure fn: tuple(vals for env_names) -> same, by running block."""
    from ..core.executor import _run_block, ExecContext

    def fn(vals):
        env = dict(zip(env_names, vals))
        sub = ExecContext(None, is_test=ctx.is_test, mesh=ctx.mesh,
                          data_axis=ctx.data_axis)
        _run_block(block, env, sub)
        return tuple(env[n] for n in env_names)

    return fn


@register_op("while",
             differentiable=lambda attrs: attrs.get("max_iters") is not None)
def _while(ctx, inputs, attrs):
    """while_op.cc parity. Two lowerings:

    - unbounded (no `max_iters`): lax.while_loop — data-dependent trip
      count, non-differentiable (inference decoding loops);
    - bounded (`max_iters=N`): a fixed-length lax.scan of masked updates —
      the loop body runs N times and each carried value only advances while
      the condition still holds. Reverse-mode differentiable, which is what
      gives the reference's WhileGradOp (while_op.cc) capability a
      TPU-native answer: trained dynamic decoders with a known bound.
    """
    block = attrs["sub_block"]
    loop_vars: List[str] = attrs["loop_vars"]
    cond_name: str = attrs["cond_name"]
    max_iters = attrs.get("max_iters")
    xs = inputs["X"]
    body = _lower_subblock(ctx, block, loop_vars)
    cond_idx = loop_vars.index(cond_name)

    if max_iters is None:
        def cond_fn(vals):
            return vals[cond_idx].reshape(()).astype(bool)

        out = lax.while_loop(cond_fn, lambda v: body(v), tuple(xs))
        return {"Out": list(out)}

    def step(vals, _):
        alive = vals[cond_idx].reshape(()).astype(bool)
        new = body(vals)
        merged = tuple(
            jnp.where(alive, n.astype(v.dtype) if hasattr(n, "astype") else n, v)
            for n, v in zip(new, vals))
        return merged, None

    out, _ = lax.scan(step, tuple(xs), None, length=int(max_iters))
    return {"Out": list(out)}


@register_op("conditional_block", differentiable=False)
def _conditional_block(ctx, inputs, attrs):
    """conditional_block_op.cc parity via lax.cond; both branches must produce
    the declared outputs (false branch passes through defaults)."""
    block = attrs["sub_block"]
    var_names: List[str] = attrs["var_names"]
    (cond,) = inputs["Cond"]
    xs = inputs["X"]
    body = _lower_subblock(ctx, block, var_names)
    out = lax.cond(cond.reshape(()).astype(bool), body, lambda v: tuple(v), tuple(xs))
    return {"Out": list(out)}


@register_op("static_rnn")
def _static_rnn(ctx, inputs, attrs):
    """StaticRNN / recurrent_op.cc parity via lax.scan — differentiable.

    Sequence inputs are [B, T, ...] scanned over T; states carry across steps.
    attrs: sub_block, state_names (pre names), state_out_names (post names),
    seq_in_names, out_names (per-step outputs collected along T).
    """
    block = attrs["sub_block"]
    state_names = attrs["state_names"]
    state_out_names = attrs["state_out_names"]
    seq_in_names = attrs["seq_in_names"]
    out_names = attrs["out_names"]
    param_names = attrs.get("param_names", [])

    states = inputs["State"]
    seqs = inputs["Seq"]
    params = inputs.get("Param", [])

    from ..core.executor import _run_block, ExecContext

    def step(carry, xt):
        env = dict(zip(state_names, carry))
        env.update(zip(seq_in_names, xt))
        env.update(zip(param_names, params))
        sub = ExecContext(None, is_test=ctx.is_test, mesh=ctx.mesh,
                          data_axis=ctx.data_axis)
        _run_block(block, env, sub)
        new_carry = tuple(env[n] for n in state_out_names)
        ys = tuple(env[n] for n in out_names)
        return new_carry, ys

    seqs_tfirst = tuple(jnp.swapaxes(s, 0, 1) for s in seqs)
    final_states, ys = lax.scan(step, tuple(states), seqs_tfirst)
    outs = [jnp.swapaxes(y, 0, 1) for y in ys]
    return {"Out": outs, "FinalState": list(final_states)}


@register_op("cond", differentiable=False)
def _cond(ctx, inputs, attrs):
    """Two-branch functional cond (paddle 2.x layers.cond capability;
    reference expresses it as paired conditional_block ops). Each branch is a
    sub-block lowered to a pure fn over its own captured environment; both
    must produce the same number/shape of outputs (lax.cond contract)."""
    (pred,) = inputs["Pred"]
    t_in = inputs.get("TrueIn", [])
    f_in = inputs.get("FalseIn", [])
    tb, fb = attrs["true_block"], attrs["false_block"]
    t_env, f_env = attrs["true_env_names"], attrs["false_env_names"]
    t_out, f_out = attrs["true_out_names"], attrs["false_out_names"]

    from ..core.executor import _run_block, ExecContext

    def mk(block, env_names, out_names, vals):
        def fn(_):
            env = dict(zip(env_names, vals))
            sub = ExecContext(None, is_test=ctx.is_test, mesh=ctx.mesh,
                              data_axis=ctx.data_axis)
            _run_block(block, env, sub)
            return tuple(env[n] for n in out_names)
        return fn

    out = lax.cond(pred.reshape(()).astype(bool),
                   mk(tb, t_env, t_out, t_in), mk(fb, f_env, f_out, f_in),
                   operand=None)
    return {"Out": list(out)}


@register_op("switch", differentiable=False)
def _switch(ctx, inputs, attrs):
    """First-matching-case switch (layers/control_flow.py Switch parity —
    the lr-schedule workhorse). Cases + optional default are sub-blocks that
    write a shared carried var set; lowered to lax.switch on the index of the
    first true condition."""
    conds = inputs["Conds"]
    xs = inputs["X"]
    case_blocks = attrs["case_blocks"]
    default_block = attrs.get("default_block")
    var_names = attrs["var_names"]

    from ..core.executor import _run_block, ExecContext

    def mk(block):
        def fn(vals):
            if block is None:
                return tuple(vals)
            env = dict(zip(var_names, vals))
            sub = ExecContext(None, is_test=ctx.is_test, mesh=ctx.mesh,
                              data_axis=ctx.data_axis)
            _run_block(block, env, sub)
            return tuple(env[n] for n in var_names)
        return fn

    branches = [mk(b) for b in case_blocks] + [mk(default_block)]
    flags = jnp.stack([c.reshape(()).astype(bool) for c in conds])
    first = jnp.argmax(flags)                       # first True (or 0)
    idx = jnp.where(flags.any(), first, len(case_blocks))
    out = lax.switch(idx, branches, tuple(xs))
    return {"Out": list(out)}


@register_op("select")
def _select(ctx, inputs, attrs):
    """Rowwise/elementwise select (IfElse merge): Out = where(Cond, X, Y).
    Cond broadcasts from [B,1] over trailing dims."""
    (cond,) = inputs["Cond"]
    (x,) = inputs["X"]
    (y,) = inputs["Y"]
    c = cond.astype(bool)
    while c.ndim < x.ndim:
        c = c[..., None]
    # collapse trailing singleton mismatch ([B,1] vs [B,D])
    c = jnp.broadcast_to(c, x.shape)
    return {"Out": [jnp.where(c, x, y)]}


# ---- tensor-array ops (LoDTensorArray capability, dense redesign) --------
# Reference: lod_tensor_array ops (array_write/read, lod_array_length,
# controlflow/while users). XLA needs static shapes, so an "array" is a
# preallocated [max_len, ...] buffer var plus an int64 length scalar,
# updated via dynamic_update_slice — usable inside while loops.

@register_op("array_write", nondiff_inputs=["I", "Length"])
def _array_write(ctx, inputs, attrs):
    (arr,) = inputs["Array"]
    (i,) = inputs["I"]
    (x,) = inputs["X"]
    (n,) = inputs["Length"]
    idx = i.reshape(()).astype(jnp.int32)
    new = lax.dynamic_update_index_in_dim(arr, x.astype(arr.dtype), idx, 0)
    return {"Out": [new], "LengthOut": [jnp.maximum(n, (idx + 1).astype(n.dtype))]}


@register_op("array_read", nondiff_inputs=["I"])
def _array_read(ctx, inputs, attrs):
    (arr,) = inputs["Array"]
    (i,) = inputs["I"]
    idx = i.reshape(()).astype(jnp.int32)
    return {"Out": [lax.dynamic_index_in_dim(arr, idx, 0, keepdims=False)]}


@register_op("array_length", differentiable=False)
def _array_length(ctx, inputs, attrs):
    (n,) = inputs["Length"]
    return {"Out": [n.reshape((1,)).astype(jnp.int64)]}
