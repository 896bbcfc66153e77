"""Mixture-of-Experts framework op.

No reference analog (barrierye/Paddle predates MoE) — this exposes the
machinery of parallel/moe.py to static-graph programs as a single `moe_ffn`
op, the same way the reference exposes composite blocks as fused ops (e.g.
fused_embedding_seq_pool_op.cc): a float32 router over all the layer's
experts, dropless sort-and-segment dispatch, one grouped product over the
experts held (plain, or gated where the op has a `W3` input). Under a
compiled mesh with an `ep` axis the experts are sharded over it and the
tokens gathered and reduce-scattered; otherwise the op computes the part of
the result its `experts_held` give (under any other mesh, where the grouped
product is the Pallas kernels, each data shard's tokens inside a
shard_map). Differentiable through the executor's vjp tape (the grouped
product brings its own backward).

Gray under AMP (not listed in contrib/mixed_precision/fp16_lists.py): the
router runs in float32 whatever dtype the activations arrive in, the expert
products take the activations' dtype with float32 accumulation, and the
weight gradients are summed in float32 straight into the float32 masters.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..core.registry import register_op
from ..parallel import moe as _moe
from .common import act_map, opt_input


@register_op("moe_ffn", nondiff_inputs=["CorrectionBias"])
def _moe_ffn(ctx, inputs, attrs):
    (x,) = inputs["X"]                 # [B, T, D] or [N, D]
    (gate_w,) = inputs["GateW"]        # [D, E]
    (w1,) = inputs["W1"]               # [E_held, D, H]
    (w2,) = inputs["W2"]               # [E_held, H, D]
    b1 = opt_input(inputs, "B1")       # [E_held, H]
    b2 = opt_input(inputs, "B2")       # [E_held, D]
    w3 = opt_input(inputs, "W3")       # [E_held, D, H]: gated experts
    bias = opt_input(inputs, "CorrectionBias")     # [E]
    axis = attrs.get("ep_axis", "ep")
    act = act_map()[attrs.get("act", "gelu")]
    kw = dict(k=int(attrs.get("k", 2)), act=act,
              scoring=attrs.get("scoring", "softmax"), correction_bias=bias,
              norm_topk=bool(attrs.get("norm_topk", True)),
              routed_scaling=float(attrs.get("routed_scaling", 1.0)),
              w3=w3)
    e = gate_w.shape[1]
    first = int(attrs.get("experts_first", 0))

    shape = x.shape
    flat = x.reshape(-1, shape[-1])

    mesh = ctx.mesh
    if mesh is not None and axis in mesh.axis_names \
            and w1.shape[0] == e and e % mesh.shape[axis] == 0 \
            and flat.shape[0] % mesh.shape[axis] == 0:
        out = _moe.moe_ffn_expert_parallel(flat, gate_w, w1, b1, w2, b2,
                                           mesh, axis=axis, **kw)
    elif mesh is not None and mesh.size > 1 and _moe.grouped_path(
            flat.shape[1], w1.shape[2], w3 is not None, flat.dtype,
            _moe.TILE, flat.shape[0] * kw["k"]) == "pallas":
        # GSPMD cannot partition a Mosaic kernel: each data shard's tokens
        # (the path asked by all the tokens' pairs, a shard's upper bound)
        out = _moe.moe_ffn_data_parallel(
            flat, gate_w, w1, b1, w2, b2, mesh, ctx.data_axis,
            experts_held=(first, w1.shape[0]), **kw)
    else:
        out = _moe.moe_ffn(flat, gate_w, w1, b1, w2, b2,
                           experts_held=(first, w1.shape[0]), **kw)
    return {"Out": [out.y.reshape(shape)], "AuxLoss": [out.aux_loss],
            "TokensPerExpert": [out.tokens_per_expert],
            "PairsHeld": [out.pairs_held.astype(jnp.int32)]}
