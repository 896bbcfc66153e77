"""Fused ops produced by the ir fuse passes.

Reference analog: ``paddle/fluid/operators/fused/`` (fused_elemwise_activation
_op.cc, fc_op via fc_fuse_pass). On TPU these exist so the *traced graph* has
one op where the pattern had two/three — XLA then fuses the arithmetic into a
single kernel around the MXU gemm; autodiff sees one tape entry.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register_op
from .common import act_map, bcast_y, one, opt_input

_ACTS = act_map()


@register_op("fused_elemwise_activation")
def _fused_elemwise_activation(ctx, inputs, attrs):
    """act(add(x, y)) in one op (fused_elemwise_activation_op.cc)."""
    (x,) = inputs["X"]
    (y,) = inputs["Y"]
    binary, unary = attrs["functor_list"]
    y = bcast_y(x, y, attrs.get("axis", -1))
    binop = {"elementwise_add": jnp.add, "elementwise_mul": jnp.multiply}[binary]
    return one(_ACTS[unary](binop(x, y)))


@register_op("fused_fc")
def _fused_fc(ctx, inputs, attrs):
    """gemm + bias + activation as one MXU-shaped unit (fc_fuse_pass.cc)."""
    (x,) = inputs["Input"]
    (w,) = inputs["W"]
    b = opt_input(inputs, "Bias")
    ncol = attrs.get("in_num_col_dims", 1)
    lead = x.shape[:ncol]
    x2 = x.reshape((int(np.prod(lead)) if lead else 1, -1))
    out = jnp.matmul(x2, w)
    if b is not None:
        out = out + b.reshape((1, -1))
    out = _ACTS[attrs.get("activation_type", "")](out)
    return one(out.reshape(lead + (w.shape[-1],)))


@register_op("fused_conv_bn", nondiff_inputs=["Mean", "Variance"])
def _fused_conv_bn(ctx, inputs, attrs):
    """1×1-conv + batch_norm (+relu, +residual) as one op — the training
    analog of the inference conv_bn_fuse pass, for the resnet bottleneck
    tail. Pallas on TPU (or under FORCE_PALLAS_INTERPRET); otherwise an
    XLA composition with the exact math of the separate conv2d +
    batch_norm("xla1") (+elementwise_add+relu) lowerings, bitwise-equal
    end to end. ``PDTPU_CONV_BN_FUSION=xla`` forces the composition."""
    import os

    from jax import lax

    from .pallas_kernels import fused_bn

    (x,) = inputs["Input"]
    (w,) = inputs["Filter"]
    (scale,) = inputs["Scale"]
    (bias,) = inputs["Bias"]
    (mean,) = inputs["Mean"]
    (var,) = inputs["Variance"]
    residual = opt_input(inputs, "Residual")
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    act = attrs.get("act", "")
    stride = int(attrs.get("stride", 1))
    is_test = attrs.get("is_test", False) or ctx.is_test
    mode = os.environ.get("PDTPU_CONV_BN_FUSION", "pallas")
    if w.dtype != x.dtype:
        # AMP casts the activations at the op boundary but doesn't know
        # this op's Filter slot; round the f32 master weight to the
        # compute dtype here — same rounding the unfused conv2d path gets
        # from its inserted cast op (scale/bias/stats stay f32)
        w = w.astype(x.dtype)

    if is_test:
        y, _, _ = fused_bn.conv_bn_xla(x, w, scale, bias, eps, act, stride,
                                       residual, use_mean=mean, use_var=var)
        return {"Y": [y], "MeanOut": [mean], "VarianceOut": [var],
                "SavedMean": [mean], "SavedVariance": [var]}

    use_pallas = (mode != "xla"
                  and fused_bn.conv_bn_supports(x.shape, w.shape, stride)
                  and (fused_bn._on_tpu() or fused_bn.FORCE_PALLAS_INTERPRET))
    if use_pallas:
        y, bmean, bvar = fused_bn.fused_conv_bn_act(
            x, w, scale, bias, eps, act, stride, residual is not None,
            residual)
    else:
        y, bmean, bvar = fused_bn.conv_bn_xla(x, w, scale, bias, eps, act,
                                              stride, residual)
    mean_out = momentum * mean + (1.0 - momentum) * bmean
    var_out = momentum * var + (1.0 - momentum) * bvar
    return {
        "Y": [y],
        "MeanOut": [lax.stop_gradient(mean_out)],
        "VarianceOut": [lax.stop_gradient(var_out)],
        "SavedMean": [lax.stop_gradient(bmean)],
        "SavedVariance": [lax.stop_gradient(bvar)],
    }


def _per_data_shard(ctx, fn, arrays, key, replicated=()):
    """``fn(*arrays, *replicated, key)`` for what GSPMD cannot partition
    under a mesh: a Mosaic kernel (the lowering refuses it outright), a
    compaction of each shard's own rows. The call runs inside a shard_map
    over the whole mesh: dim 0 of every one of `arrays` and of every result
    (the batch) is split over the data axis when it divides, everything else
    — `replicated` whole: parameters, whose gradients the shard_map's
    transpose sums over the shards — is replicated, and each data shard
    folds its index into the dropout key so shards do not repeat each
    other's masks."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.collective import shard_map

    mesh, axis = ctx.mesh, ctx.data_axis
    if axis is not None and any(
            a.shape[0] % mesh.shape[axis] for a in arrays):
        axis = None
    spec = P(axis) if axis is not None else P()

    def body(key, *arrays):
        if key is not None and axis is not None:
            key = jax.random.fold_in(key, jax.lax.axis_index(axis))
        return fn(*arrays, key)

    return shard_map(
        body, mesh,
        in_specs=(P(),) + (spec,) * len(arrays) + (P(),) * len(replicated),
        out_specs=spec)(key, *arrays, *replicated)


def _under_mesh(ctx) -> bool:
    return ctx.mesh is not None and ctx.mesh.size > 1


@register_op("flash_attention", nondiff_inputs=["BiasQK"])
def _flash_attention(ctx, inputs, attrs):
    """Memory-efficient fused attention (Pallas on TPU, blockwise JAX
    elsewhere). Replaces the matmul→softmax→dropout→matmul chain; see
    ops/pallas_kernels/flash_attention.py."""
    import importlib
    # the package re-exports the flash_attention *function* under the same
    # name, shadowing the submodule — import the module explicitly
    _fa = importlib.import_module(
        "paddle_tpu.ops.pallas_kernels.flash_attention")

    (q,) = inputs["Q"]
    (k,) = inputs["K"]
    (v,) = inputs["V"]
    bias = opt_input(inputs, "BiasQK")
    rate = attrs.get("dropout_prob", 0.0)
    is_test = attrs.get("is_test", False) or ctx.is_test
    key = None
    if rate > 0.0 and not is_test:
        key = ctx.rng()
    causal = attrs.get("causal", False)
    window = attrs.get("window")
    rate = 0.0 if is_test else rate
    if q.ndim == 3:
        # packed [B, T, H] layout — adapted to the folded kernel layout
        # (see the layout note in pallas_kernels/flash_attention.py)
        if "num_heads" not in attrs:
            raise ValueError(
                "flash_attention: 3D (packed [B,T,H]) q/k/v requires the "
                "num_heads attr — pass num_heads= to layers.flash_attention")
        nh = attrs["num_heads"]
        nkv = attrs.get("num_kv_heads", nh)
        t, d, dv = q.shape[1], q.shape[2] // nh, v.shape[2] // nkv

        def attend(q, k, v, *rest):
            *bias, key = rest
            return _fa.flash_attention_packed(
                q, k, v, nh, bias=bias[0] if bias else None, causal=causal,
                dropout_rate=rate, dropout_key=key, num_kv_heads=nkv,
                window=window)
    else:
        t, d, dv = q.shape[2], q.shape[3], v.shape[3]

        def attend(q, k, v, *rest):
            *bias, key = rest
            return _fa.flash_attention(
                q, k, v, bias=bias[0] if bias else None, causal=causal,
                dropout_rate=rate, dropout_key=key, window=window)
    arrays = (q, k, v) if bias is None else (q, k, v, bias)
    if _under_mesh(ctx) and _fa._pallas_ok(t, d, dv):
        return one(_per_data_shard(ctx, attend, arrays, key))
    return one(attend(*arrays, key))


@register_op("flash_attention_sparse", nondiff_inputs=["QSeg", "KSeg"])
def _flash_attention_sparse(ctx, inputs, attrs):
    """Block-sparse packed-segment attention: visibility travels as the
    packed segment-id rows instead of a dense [B, 1, Tq, Tk] additive mask,
    and fully-masked K blocks are skipped in the fwd and bwd kernel grids.
    See the block-sparse section of ops/pallas_kernels/flash_attention.py."""
    import importlib
    _fa = importlib.import_module(
        "paddle_tpu.ops.pallas_kernels.flash_attention")

    (q,) = inputs["Q"]
    (k,) = inputs["K"]
    (v,) = inputs["V"]
    (q_seg,) = inputs["QSeg"]
    (k_seg,) = inputs["KSeg"]
    rate = attrs.get("dropout_prob", 0.0)
    is_test = attrs.get("is_test", False) or ctx.is_test
    key = None
    if rate > 0.0 and not is_test:
        key = ctx.rng()
    nh = attrs["num_heads"]

    def attend(q, k, v, q_seg, k_seg, key):
        return _fa.flash_attention_packed_sparse(
            q, k, v, nh, q_seg, k_seg, causal=attrs.get("causal", False),
            dropout_rate=0.0 if is_test else rate, dropout_key=key)

    if _under_mesh(ctx) and _fa._sparse_pallas_ok(
            q.shape[1], k.shape[1], q.shape[2] // nh):
        return one(_per_data_shard(ctx, attend, (q, k, v, q_seg, k_seg),
                                   key))
    return one(attend(q, k, v, q_seg, k_seg, key))
