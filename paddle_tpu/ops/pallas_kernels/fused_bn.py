"""Pallas fused training-mode batch norm for TPU.

Reference analog: batch_norm_op.cu:35 (cuDNN BatchNormalizationForwardTraining)
plus fused_bn_add_activation semantics — one statistics pass + one apply pass,
with relu (and the bottleneck residual add) foldable into the apply.

Why this kernel exists (round-3 xplane profiling on v5e): the ResNet-50 train
step is HBM-bound and XLA's per-channel BN reduction fusions sustain only
~140 GB/s (1.5 ms for a 205 MB activation) vs ~450 GB/s for its elementwise
fusions. The kernel streams the activation once for the statistics and once
for the apply.

Layout is the whole game here. XLA keeps conv activations physically
channel-minor on TPU (e.g. bf16[128,256,56,56]{1,0,3,2} — NHWC bytes under an
NCHW logical shape). A kernel that demands row-major NCHW forces a material
transpose around every call (measured: 116 ms vs 54 ms full step — 2× WORSE).
So these kernels operate on the (M, C) = (N·H·W, C) view with channel riding
the lane axis: the logical NCHW→NHWC transpose then lines up with the bytes
XLA already has, per-channel statistics become sublane-axis sums at streaming
bandwidth, and every broadcast is a natural row broadcast.

When C < 128 the (M, C) view would waste the lane axis (C=64 pads to 128 —
half the bandwidth on exactly the stage-1 tensors that dominate traffic), so
the view is folded to (M/k, k·C) with k = 128//C and the k per-channel
partials are combined outside the kernel.

Backward (custom_vjp): a reduction pass producing dbeta=Σg, dgamma=Σg·x̂
(g = dy masked through the fused relu), then a dx pass
`dx = inv·scale·(g − dbeta/m − x̂·dgamma/m)`, emitting dresidual=g for free
when the residual add was fused.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ._account import kernel_call


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# Tests set this to run the kernels on CPU through the interpreter; nothing
# else turns the interpreter on.
FORCE_PALLAS_INTERPRET = False

# Per-operand VMEM budget per grid step (bytes); the widest backward pass
# streams five (BM, C) operands (dy, x, y in; dx, dres out) plus double
# buffering inside ~16 MB.
_MAX_BLOCK_BYTES = 1024 * 1024


def supports(x_shape, dtype) -> bool:
    """Static gate for the pallas path: 4-D, lane-friendly C, and enough
    rows that kernel launch overhead amortizes."""
    if len(x_shape) != 4:
        return False
    n, c, h, w = x_shape
    if c < 8 or c > 8192 or (c < 128 and 128 % c != 0) or \
            (c >= 128 and c % 128 != 0):
        return False
    m = n * h * w
    k = 128 // c if c < 128 else 1
    mk = m // k
    return m % max(k, 1) == 0 and mk >= 1024 and mk % 8 == 0


def _fold(c):
    """Lane-fold factor k: view (M, C) as (M/k, kC) so the lane axis is
    full when C < 128."""
    return 128 // c if c < 128 else 1


def _pick_bm(mk, ck, itemsize):
    """Sublane block: largest power-of-two divisor of M/k within the
    per-operand byte budget (dtype-aware — f32 blocks are half the rows of
    bf16 ones)."""
    cap = max(8, _MAX_BLOCK_BYTES // (ck * itemsize))
    bm = 8
    while bm * 2 <= cap and mk % (bm * 2) == 0:
        bm *= 2
    return bm


def _nhwc_2d(x):
    """(N, C, H, W) → (M/k, k·C) channel-minor view (bitcast against XLA's
    preferred conv layout, not a material transpose)."""
    n, c, h, w = x.shape
    k = _fold(c)
    return jnp.transpose(x, (0, 2, 3, 1)).reshape(n * h * w // k, k * c)


def _un_nhwc(y2, shape):
    n, c, h, w = shape
    return jnp.transpose(y2.reshape(n, h, w, c), (0, 3, 1, 2))


# ---------------------------------------------------------------------------
# forward: statistics (one streaming pass)
# ---------------------------------------------------------------------------

def _stats_kernel(x_ref, sum_ref, ssq_ref):
    mb = pl.program_id(0)

    @pl.when(mb == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        ssq_ref[...] = jnp.zeros_like(ssq_ref)

    xf = x_ref[...].astype(jnp.float32)                    # [BM, kC]
    sum_ref[...] += jnp.sum(xf, axis=0, keepdims=True)
    ssq_ref[...] += jnp.sum(xf * xf, axis=0, keepdims=True)


def bn_stats(x, *, interpret=False):
    """Per-channel (mean, var) of NCHW x in one HBM pass. f32 outputs [C].

    One-pass E[x²]−E[x]² with f32 accumulators and a clamp at 0 — the same
    trade cuDNN's training path makes; exactness on adversarially large-mean
    inputs is traded for a single streaming read."""
    n, c, h, w = x.shape
    k = _fold(c)
    x2 = _nhwc_2d(x)
    mk, ck = x2.shape
    bm = _pick_bm(mk, ck, x.dtype.itemsize)
    s, ss = kernel_call(
        "bn_stats", _stats_kernel,
        grid=(mk // bm,),
        in_specs=[pl.BlockSpec((bm, ck), lambda mb: (mb, 0))],
        out_specs=[pl.BlockSpec((1, ck), lambda mb: (0, 0)),
                   pl.BlockSpec((1, ck), lambda mb: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, ck), jnp.float32),
                   jax.ShapeDtypeStruct((1, ck), jnp.float32)],
        interpret=interpret,
    )(x2)
    m = float(n * h * w)
    s = s.reshape(k, c).sum(axis=0)
    ss = ss.reshape(k, c).sum(axis=0)
    mean = s / m
    var = jnp.maximum(ss / m - mean * mean, 0.0)
    return mean, var


# ---------------------------------------------------------------------------
# forward: apply (+relu, +residual)
# ---------------------------------------------------------------------------

def _apply_kernel(x_ref, mean_ref, isc_ref, bias_ref, *rest, act, has_res):
    if has_res:
        res_ref, y_ref = rest
    else:
        (y_ref,) = rest
    xf = x_ref[...].astype(jnp.float32)
    y = (xf - mean_ref[...]) * isc_ref[...] + bias_ref[...]
    if has_res:
        y = y + res_ref[...].astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    y_ref[...] = y.astype(y_ref.dtype)


def bn_apply(x, mean, inv, scale, bias, *, act="", residual=None,
             interpret=False):
    n, c, h, w = x.shape
    k = _fold(c)
    x2 = _nhwc_2d(x)
    mk, ck = x2.shape
    bm = _pick_bm(mk, ck, x.dtype.itemsize)
    isc = (inv * scale.astype(jnp.float32))
    meanv = jnp.tile(mean.astype(jnp.float32), k).reshape(1, ck)
    iscv = jnp.tile(isc, k).reshape(1, ck)
    biasv = jnp.tile(bias.astype(jnp.float32), k).reshape(1, ck)
    vec = pl.BlockSpec((1, ck), lambda mb: (0, 0))
    big = pl.BlockSpec((bm, ck), lambda mb: (mb, 0))
    args = [x2, meanv, iscv, biasv]
    in_specs = [big, vec, vec, vec]
    if residual is not None:
        args.append(_nhwc_2d(residual))
        in_specs.append(big)
    y2 = kernel_call(
        "bn_apply",
        functools.partial(_apply_kernel, act=act,
                          has_res=residual is not None),
        grid=(mk // bm,),
        in_specs=in_specs,
        out_specs=big,
        out_shape=jax.ShapeDtypeStruct((mk, ck), x.dtype),
        interpret=interpret,
    )(*args)
    return _un_nhwc(y2, x.shape)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_reduce_kernel(dy_ref, x_ref, *rest, act):
    """dbeta = Σ g, dgamma = Σ g·x̂ in one streaming pass.
    g = dy·(y>0) when relu was fused (y passed in), else dy."""
    if act == "relu":
        y_ref, mean_ref, inv_ref, dbeta_ref, dgamma_ref = rest
    else:
        mean_ref, inv_ref, dbeta_ref, dgamma_ref = rest
    mb = pl.program_id(0)

    @pl.when(mb == 0)
    def _init():
        dbeta_ref[...] = jnp.zeros_like(dbeta_ref)
        dgamma_ref[...] = jnp.zeros_like(dgamma_ref)

    g = dy_ref[...].astype(jnp.float32)
    if act == "relu":
        g = jnp.where(y_ref[...].astype(jnp.float32) > 0, g, 0.0)
    xhat = (x_ref[...].astype(jnp.float32) - mean_ref[...]) * inv_ref[...]
    dbeta_ref[...] += jnp.sum(g, axis=0, keepdims=True)
    dgamma_ref[...] += jnp.sum(g * xhat, axis=0, keepdims=True)


def _bwd_dx_kernel(dy_ref, x_ref, *rest, act, has_res, m):
    if act == "relu":
        y_ref = rest[0]
        rest = rest[1:]
    mean_ref, inv_ref, isc_ref, dbeta_ref, dgamma_ref = rest[:5]
    outs = rest[5:]
    g = dy_ref[...].astype(jnp.float32)
    if act == "relu":
        g = jnp.where(y_ref[...].astype(jnp.float32) > 0, g, 0.0)
    xhat = (x_ref[...].astype(jnp.float32) - mean_ref[...]) * inv_ref[...]
    dx = isc_ref[...] * (
        g - dbeta_ref[...] * (1.0 / m) - xhat * (dgamma_ref[...] * (1.0 / m)))
    outs[0][...] = dx.astype(outs[0].dtype)
    if has_res:
        outs[1][...] = g.astype(outs[1].dtype)


# ---------------------------------------------------------------------------
# public fused op with custom_vjp
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_bn_act(x, scale, bias, eps, act, residual_tag, residual=None):
    """Training-mode fused BN: y = act(x̂·scale + bias [+ residual]).

    Returns (y, mean, var) with mean/var the f32 batch statistics (for the
    running-stat update). `residual_tag` statically records whether a
    residual is fused (custom_vjp needs it nondiff)."""
    y, mean, var, _ = _fwd(x, scale, bias, eps, act, residual)
    return y, mean, var


def _fwd(x, scale, bias, eps, act, residual):
    interpret = FORCE_PALLAS_INTERPRET
    mean, var = bn_stats(x, interpret=interpret)
    inv = lax.rsqrt(var + eps)
    y = bn_apply(x, mean, inv, scale, bias, act=act, residual=residual,
                 interpret=interpret)
    return y, mean, var, inv


def _fused_fwd(x, scale, bias, eps, act, residual_tag, residual=None):
    y, mean, var, inv = _fwd(x, scale, bias, eps, act, residual)
    saved_y = y if act == "relu" else None
    return (y, mean, var), (x, scale, mean, inv, saved_y)


def _bn_bwd_2d(dy2, x2, y2, mean, inv, scale, act, has_res, m, k, interpret):
    """2-D core of the fused-BN backward on the channel-minor (M/k, k·C)
    view: one reduction pass (dbeta, dgamma), one dx pass (+dresidual when
    the residual add was fused). mean/inv/scale are per-channel f32 [C];
    returns (dx2, dgamma, dbeta, dres2_or_None). Shared by the plain
    fused-BN vjp and the conv+BN vjp (where x2 is the conv output)."""
    mk, ck = x2.shape
    c = ck // k
    bm = _pick_bm(mk, ck, x2.dtype.itemsize)
    vec = pl.BlockSpec((1, ck), lambda mb: (0, 0))
    big = pl.BlockSpec((bm, ck), lambda mb: (mb, 0))

    meanv = jnp.tile(mean, k).reshape(1, ck)
    invv = jnp.tile(inv, k).reshape(1, ck)

    args = [dy2, x2]
    in_specs = [big, big]
    if act == "relu":
        args.append(y2)
        in_specs.append(big)
    args += [meanv, invv]
    in_specs += [vec, vec]

    dbeta2, dgamma2 = kernel_call(
        "bn_bwd_stats", functools.partial(_bwd_reduce_kernel, act=act),
        grid=(mk // bm,),
        in_specs=in_specs,
        out_specs=[vec, vec],
        out_shape=[jax.ShapeDtypeStruct((1, ck), jnp.float32),
                   jax.ShapeDtypeStruct((1, ck), jnp.float32)],
        interpret=interpret,
    )(*args)
    dbeta = dbeta2.reshape(k, c).sum(axis=0)
    dgamma = dgamma2.reshape(k, c).sum(axis=0)

    isc = inv * scale.astype(jnp.float32)
    args2 = args + [jnp.tile(isc, k).reshape(1, ck),
                    jnp.tile(dbeta, k).reshape(1, ck),
                    jnp.tile(dgamma, k).reshape(1, ck)]
    in_specs2 = in_specs + [vec, vec, vec]
    out_specs = [big]
    out_shape = [jax.ShapeDtypeStruct((mk, ck), x2.dtype)]
    if has_res:
        out_specs.append(big)
        out_shape.append(jax.ShapeDtypeStruct((mk, ck), x2.dtype))
    outs = kernel_call(
        "bn_bwd_apply",
        functools.partial(_bwd_dx_kernel, act=act, has_res=has_res, m=m),
        grid=(mk // bm,),
        in_specs=in_specs2,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*args2)
    return outs[0], dgamma, dbeta, (outs[1] if has_res else None)


def _fused_bwd(eps, act, residual_tag, saved, cots):
    x, scale, mean, inv, saved_y = saved
    dy, _dmean, _dvar = cots  # mean/var feed stop-gradient running stats
    interpret = FORCE_PALLAS_INTERPRET
    n, c, h, w = x.shape
    k = _fold(c)
    m = float(n * h * w)
    y2 = _nhwc_2d(saved_y) if act == "relu" else None
    dx2, dgamma, dbeta, dres2 = _bn_bwd_2d(
        _nhwc_2d(dy), _nhwc_2d(x), y2, mean, inv, scale, act,
        residual_tag, m, k, interpret)
    dx = _un_nhwc(dx2, x.shape)
    dscale = dgamma.astype(scale.dtype)
    dbias = dbeta.astype(scale.dtype)
    dres = _un_nhwc(dres2, x.shape) if residual_tag else None
    return dx, dscale, dbias, dres


fused_bn_act.defvjp(_fused_fwd, _fused_bwd)


# ---------------------------------------------------------------------------
# fused 1×1-conv + BN (+relu, +residual): the bottleneck epilogue kernels
# ---------------------------------------------------------------------------
# A 1×1 conv with stride s is subsample-then-matmul, so on the channel-minor
# (M, C) view the whole bottleneck tail `conv1x1 → BN → (+residual) → relu`
# is one MXU matmul whose statistics ride along in the same streaming pass.
# HBM sees x once and the conv output twice (stats-producing write + apply
# read) instead of the unfused 4–5 passes, and — unlike a standalone Pallas
# BN, which measured 2× WORSE from layout round-trips — the matmul itself
# lives in the kernel, so no transpose traffic is ever materialized.
#
# Layout/padding rules that make this fast (and which `conv_bn_supports`
# enforces): channels ride the lane axis as the full minor dimension of the
# block (always Mosaic-legal; C < 128 merely wastes lanes — only the first
# bottleneck's C=64 input hits this), rows are 8×-tiled on the sublane axis,
# and the weight matrix stays resident in VMEM across the whole grid.

# The (Ci, Co) weight panel must fit VMEM alongside the streaming blocks;
# resnet50's largest is 2048×512 (4 MB f32).
_MAX_W_BYTES = 8 * 1024 * 1024


def conv_bn_supports(x_shape, w_shape, stride) -> bool:
    """Static gate for the fused conv+BN pallas path: 1×1 kernel, stride
    1/2, lane-friendly channel counts, enough output rows to tile."""
    if len(x_shape) != 4 or len(w_shape) != 4:
        return False
    n, ci, h, w = x_shape
    co, wci, kh, kw = w_shape
    if (kh, kw) != (1, 1) or wci != ci or stride not in (1, 2):
        return False
    if ci < 8 or co < 8 or ci > 8192 or co > 8192 or ci % 8 or co % 8:
        return False
    if ci * co * 4 > _MAX_W_BYTES:
        return False
    m = n * -(-h // stride) * -(-w // stride)
    return m >= 1024 and m % 8 == 0


def _to2d(x):
    """(N, C, H, W) → (M, C) channel-minor view, no lane fold (the conv
    kernels need C intact as the contraction/output axis)."""
    n, c, h, w = x.shape
    return jnp.transpose(x, (0, 2, 3, 1)).reshape(n * h * w, c)


def _from2d(y2, shape):
    n, c, h, w = shape
    return jnp.transpose(y2.reshape(n, h, w, c), (0, 3, 1, 2))


def _conv_stats_kernel(x_ref, w_ref, y_ref, sum_ref, ssq_ref):
    """One grid step: yf = x·w on the MXU (f32 accumulation), stored in
    activation dtype, with the BN statistics accumulated from the *stored*
    values — matching an unfused conv→BN chain that reads the rounded
    activation back from HBM."""
    mb = pl.program_id(0)

    @pl.when(mb == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        ssq_ref[...] = jnp.zeros_like(ssq_ref)

    yf = lax.dot_general(x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)
    yc = yf.astype(y_ref.dtype)
    y_ref[...] = yc
    ys = yc.astype(jnp.float32)
    sum_ref[...] += jnp.sum(ys, axis=0, keepdims=True)
    ssq_ref[...] += jnp.sum(ys * ys, axis=0, keepdims=True)


def _conv_stats(x2, w2, out_dtype, interpret):
    """(M, Ci) @ (Ci, Co) with per-channel (mean, var) of the result in the
    same pass. Returns (y2, mean, var)."""
    mk, ci = x2.shape
    co = w2.shape[1]
    bm = _pick_bm(mk, max(ci, co), max(x2.dtype.itemsize, 2))
    y2, s, ss = kernel_call(
        "conv_bn_stats", _conv_stats_kernel,
        grid=(mk // bm,),
        in_specs=[pl.BlockSpec((bm, ci), lambda mb: (mb, 0)),
                  pl.BlockSpec((ci, co), lambda mb: (0, 0))],
        out_specs=[pl.BlockSpec((bm, co), lambda mb: (mb, 0)),
                   pl.BlockSpec((1, co), lambda mb: (0, 0)),
                   pl.BlockSpec((1, co), lambda mb: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((mk, co), out_dtype),
                   jax.ShapeDtypeStruct((1, co), jnp.float32),
                   jax.ShapeDtypeStruct((1, co), jnp.float32)],
        interpret=interpret,
    )(x2, w2)
    mean = s[0] / mk
    var = jnp.maximum(ss[0] / mk - mean * mean, 0.0)
    return y2, mean, var


def _apply2d(x2, mean, inv, scale, bias, act, res2, interpret):
    """BN apply (+act, +residual) on an (M, C) view — reuses the fused-BN
    apply kernel with no lane fold."""
    mk, c = x2.shape
    bm = _pick_bm(mk, c, x2.dtype.itemsize)
    vec = pl.BlockSpec((1, c), lambda mb: (0, 0))
    big = pl.BlockSpec((bm, c), lambda mb: (mb, 0))
    isc = inv * scale.astype(jnp.float32)
    args = [x2, mean.reshape(1, c), isc.reshape(1, c),
            bias.astype(jnp.float32).reshape(1, c)]
    in_specs = [big, vec, vec, vec]
    if res2 is not None:
        args.append(res2)
        in_specs.append(big)
    return kernel_call(
        "conv_bn_apply",
        functools.partial(_apply_kernel, act=act, has_res=res2 is not None),
        grid=(mk // bm,),
        in_specs=in_specs,
        out_specs=big,
        out_shape=jax.ShapeDtypeStruct((mk, c), x2.dtype),
        interpret=interpret,
    )(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def fused_conv_bn_act(x, w, scale, bias, eps, act, stride, residual_tag,
                      residual=None):
    """Fused 1×1-conv + training BN: y = act(BN(conv(x, w)) [+ residual]).

    x is NCHW, w is OIHW with a 1×1 kernel; returns (y, mean, var) with
    mean/var the f32 batch statistics of the conv output (for the
    running-stat update). `residual_tag` statically records whether a
    residual is fused."""
    y, mean, var, _ = _conv_bn_fwd_impl(x, w, scale, bias, eps, act, stride,
                                        residual)
    return y, mean, var


def _conv_bn_fwd_impl(x, w, scale, bias, eps, act, stride, residual):
    interpret = FORCE_PALLAS_INTERPRET
    co = w.shape[0]
    xs = x[:, :, ::stride, ::stride] if stride > 1 else x
    n, _, hs, ws = xs.shape
    x2 = _to2d(xs)
    w2 = jnp.transpose(w.reshape(co, w.shape[1]))
    yc2, mean, var = _conv_stats(x2, w2, x.dtype, interpret)
    inv = lax.rsqrt(var + eps)
    res2 = _to2d(residual) if residual is not None else None
    y2 = _apply2d(yc2, mean, inv, scale, bias, act, res2, interpret)
    y = _from2d(y2, (n, co, hs, ws))
    return y, mean, var, (x2, yc2, y2, inv)


def _conv_bn_fwd(x, w, scale, bias, eps, act, stride, residual_tag,
                 residual=None):
    y, mean, var, (x2, yc2, y2, inv) = _conv_bn_fwd_impl(
        x, w, scale, bias, eps, act, stride, residual)
    saved_y2 = y2 if act == "relu" else None
    return (y, mean, var), (x, w, scale, mean, inv, yc2, saved_y2)


def _conv_bn_bwd(eps, act, stride, residual_tag, saved, cots):
    x, w, scale, mean, inv, yc2, saved_y2 = saved
    dy, _dmean, _dvar = cots
    interpret = FORCE_PALLAS_INTERPRET
    co = w.shape[0]
    dy2 = _to2d(dy)
    m = float(yc2.shape[0])
    # BN half: grads w.r.t. the conv output (and the free residual grad)
    dyc2, dgamma, dbeta, dres2 = _bn_bwd_2d(
        dy2, yc2, saved_y2, mean, inv, scale, act, residual_tag, m, 1,
        interpret)
    # matmul half: XLA's dots are already MXU-shaped — the fusion win is
    # the BN/elementwise traffic, not the gemm, so these stay plain
    xs = x[:, :, ::stride, ::stride] if stride > 1 else x
    x2 = _to2d(xs)
    w2 = jnp.transpose(w.reshape(co, w.shape[1]))
    dx2 = lax.dot_general(dyc2, w2, (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32).astype(x.dtype)
    dw2 = lax.dot_general(x2, dyc2, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    dx_sub = _from2d(dx2, xs.shape)
    if stride > 1:
        dx = jnp.zeros(x.shape, x.dtype).at[:, :, ::stride, ::stride].set(
            dx_sub)
    else:
        dx = dx_sub
    dw = jnp.transpose(dw2).reshape(w.shape).astype(w.dtype)
    dres = _from2d(dres2, dy.shape) if residual_tag else None
    return (dx, dw, dgamma.astype(scale.dtype), dbeta.astype(scale.dtype),
            dres)


fused_conv_bn_act.defvjp(_conv_bn_fwd, _conv_bn_bwd)


def conv_bn_xla(x, w, scale, bias, eps, act, stride, residual=None,
                use_mean=None, use_var=None):
    """XLA fallback/reference composition with the exact math of the
    separate conv2d + batch_norm("xla1") (+ elementwise_add + relu)
    lowerings — bitwise-equal end to end, which is what makes the fused op
    safe to enable per-model. `use_mean`/`use_var` switch to frozen
    (inference) statistics. Returns (y, mean, var)."""
    y = lax.conv_general_dilated(
        x, w, (stride, stride), [(0, 0), (0, 0)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    xf = y.astype(jnp.float32)
    if use_mean is None:
        mean = jnp.mean(xf, axis=(0, 2, 3))
        var = jnp.maximum(jnp.mean(xf * xf, axis=(0, 2, 3)) - mean * mean,
                          0.0)
    else:
        mean = use_mean.astype(jnp.float32)
        var = use_var.astype(jnp.float32)
    shp = (1, -1, 1, 1)
    inv = lax.rsqrt(var.reshape(shp) + eps)
    out = ((xf - mean.reshape(shp)) * inv * scale.reshape(shp)
           + bias.reshape(shp)).astype(x.dtype)
    if residual is not None:
        out = out + residual
    if act == "relu":
        out = jax.nn.relu(out)
    return out, mean, var
