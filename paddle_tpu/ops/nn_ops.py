"""Neural-net ops: conv, pool, norms, dropout, embedding, losses.

Reference analog: ``paddle/fluid/operators/`` conv_op.cc (+conv_cudnn_op.cu),
pool_op.cc, batch_norm_op.cc, layer_norm_op.cc, group_norm_op.cc,
dropout_op.cc, lookup_table_op.cc, cross_entropy_op.cc,
softmax_with_cross_entropy_op.cc, sigmoid_cross_entropy_with_logits_op.cc.

TPU notes: convs lower to lax.conv_general_dilated → MXU; data layout is kept
NCHW at the API (Paddle convention) and XLA's layout assignment picks the
physical HBM layout. Embedding grads become XLA scatter-adds (dense), the
TPU-native replacement for SelectedRows sparse rows (selected_rows.h).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.registry import register_op
from .common import act_map, one, opt_input


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v),) * n


# ---------------------------------------------------------------------------
# convolution
# ---------------------------------------------------------------------------

@register_op("conv2d", nondiff_inputs=[])
def _conv2d(ctx, inputs, attrs):
    (x,) = inputs["Input"]
    (w,) = inputs["Filter"]
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dilations = _pair(attrs.get("dilations", [1, 1]))
    groups = int(attrs.get("groups", 1))
    pad_alg = attrs.get("padding_algorithm", "EXPLICIT")
    if pad_alg == "SAME":
        padding = "SAME"
    elif pad_alg == "VALID":
        padding = "VALID"
    else:
        padding = [(pads[0], pads[0]), (pads[1], pads[1])] if len(pads) == 2 else \
            [(pads[0], pads[1]), (pads[2], pads[3])]
    # no preferred_element_type=f32: the MXU accumulates bf16 convs in f32
    # regardless and only rounds the output, while jax 0.9's conv transpose
    # rule mishandles mixed (bf16, f32) operands it would create
    out = lax.conv_general_dilated(
        x, w, window_strides=strides, padding=padding,
        rhs_dilation=dilations, feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return one(out)


@register_op("depthwise_conv2d")
def _depthwise_conv2d(ctx, inputs, attrs):
    attrs = dict(attrs)
    (x,) = inputs["Input"]
    attrs["groups"] = x.shape[1]
    return _conv2d(ctx, inputs, attrs)


def conv_transpose_nd(x, w, strides, pads, dils, groups, out_pads=None):
    """Shared N-d transposed-conv core (conv_transpose_op.cc semantics:
    out = (i-1)*s - 2p + d*(k-1) + 1, plus per-dim output_padding on the
    trailing edge when `out_pads` is given — the output_size resolver).
    Expressed as a fractionally-strided conv (lhs_dilation) with the kernel
    spatially flipped — the gradient-of-conv formulation XLA lowers well.
    `w` is paddle layout [C_in, C_out/groups, *k]."""
    nd = len(strides)
    ks = w.shape[2:]
    wt = jnp.flip(w, axis=tuple(range(2, 2 + nd)))
    if groups > 1:
        cin, cog = w.shape[0], w.shape[1]
        wt = wt.reshape(groups, cin // groups, cog, *ks)
        wt = jnp.swapaxes(wt, 1, 2).reshape(groups * cog, cin // groups, *ks)
    else:
        wt = jnp.swapaxes(wt, 0, 1)
    out_pads = out_pads or [0] * nd
    pad = [(d * (k - 1) - p, d * (k - 1) - p + op)
           for k, p, d, op in zip(ks, pads, dils, out_pads)]
    dn = {1: ("NCH", "OIH", "NCH"), 2: ("NCHW", "OIHW", "NCHW"),
          3: ("NCDHW", "OIDHW", "NCDHW")}[nd]
    return lax.conv_general_dilated(
        x, wt, window_strides=(1,) * nd, padding=pad,
        lhs_dilation=tuple(strides), rhs_dilation=tuple(dils),
        feature_group_count=groups, dimension_numbers=dn)


def _out_pads_from_output_size(x, w, attrs, nd):
    """Resolve the reference's output_size attr into trailing output
    padding: output_size must lie in [default, default + stride)."""
    output_size = attrs.get("output_size")
    if not output_size:
        return None
    strides = _pair(attrs.get("strides", [1] * nd), nd)
    pads = _pair(attrs.get("paddings", [0] * nd), nd)
    dils = _pair(attrs.get("dilations", [1] * nd), nd)
    ks = w.shape[2:]
    out_pads = []
    for i, want in enumerate(_pair(output_size, nd)):
        default = ((x.shape[2 + i] - 1) * strides[i] - 2 * pads[i]
                   + dils[i] * (ks[i] - 1) + 1)
        extra = int(want) - default
        if not 0 <= extra < strides[i]:
            raise ValueError(
                f"conv_transpose output_size[{i}]={want} must be in "
                f"[{default}, {default + strides[i] - 1}]")
        out_pads.append(extra)
    return out_pads


@register_op("conv2d_transpose")
def _conv2d_transpose(ctx, inputs, attrs):
    (x,) = inputs["Input"]
    (w,) = inputs["Filter"]
    return one(conv_transpose_nd(
        x, w, _pair(attrs.get("strides", [1, 1])),
        _pair(attrs.get("paddings", [0, 0])),
        _pair(attrs.get("dilations", [1, 1])),
        int(attrs.get("groups", 1)),
        out_pads=_out_pads_from_output_size(x, w, attrs, 2)))


@register_op("conv3d")
def _conv3d(ctx, inputs, attrs):
    (x,) = inputs["Input"]
    (w,) = inputs["Filter"]
    strides = _pair(attrs.get("strides", [1, 1, 1]), 3)
    pads = _pair(attrs.get("paddings", [0, 0, 0]), 3)
    dilations = _pair(attrs.get("dilations", [1, 1, 1]), 3)
    groups = int(attrs.get("groups", 1))
    padding = [(p, p) for p in pads]
    out = lax.conv_general_dilated(
        x, w, window_strides=strides, padding=padding,
        rhs_dilation=dilations, feature_group_count=groups,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))
    return one(out)


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------

@register_op("pool2d")
def _pool2d(ctx, inputs, attrs):
    (x,) = inputs["X"]
    ptype = attrs.get("pooling_type", "max")
    ksize = _pair(attrs.get("ksize", [2, 2]))
    strides = _pair(attrs.get("strides", ksize))
    pads = _pair(attrs.get("paddings", [0, 0]))
    if attrs.get("global_pooling", False) or attrs.get("adaptive", False) and _pair(attrs.get("ksize")) == (1, 1):
        axis = (2, 3)
        out = jnp.max(x, axis=axis, keepdims=True) if ptype == "max" else jnp.mean(x, axis=axis, keepdims=True)
        return one(out)
    window = (1, 1) + ksize
    strides_full = (1, 1) + strides
    padding = ((0, 0), (0, 0), (pads[0], pads[0]), (pads[1], pads[1]))
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        out = lax.reduce_window(x, init, lax.max, window, strides_full, padding)
    else:
        summed = lax.reduce_window(x, 0.0, lax.add, window, strides_full, padding)
        if attrs.get("exclusive", True) and (pads[0] or pads[1]):
            ones = jnp.ones_like(x)
            counts = lax.reduce_window(ones, 0.0, lax.add, window, strides_full, padding)
            out = summed / counts
        else:
            out = summed / (ksize[0] * ksize[1])
    return one(out)


def _adaptive_bins(size, out):
    """(start, end) per output bin — torch/paddle adaptive pooling rule."""
    return [(i * size // out, -(-((i + 1) * size) // out))
            for i in range(out)]


@register_op("adaptive_pool2d")
def _adaptive_pool2d(ctx, inputs, attrs):
    (x,) = inputs["X"]
    oh, ow = _pair(attrs["pooling_size"] if "pooling_size" in attrs else attrs["ksize"])
    ptype = attrs.get("pooling_type", "avg")
    n, c, h, w = x.shape
    if h % oh == 0 and w % ow == 0:  # fast path: one reshape-reduce
        x5 = x.reshape(n, c, oh, h // oh, ow, w // ow)
        return one(jnp.mean(x5, axis=(3, 5)) if ptype == "avg"
                   else jnp.max(x5, axis=(3, 5)))
    red = jnp.mean if ptype == "avg" else jnp.max
    rows = []
    for hs, he in _adaptive_bins(h, oh):
        cols = [red(x[:, :, hs:he, ws:we], axis=(2, 3))
                for ws, we in _adaptive_bins(w, ow)]
        rows.append(jnp.stack(cols, axis=-1))
    return one(jnp.stack(rows, axis=-2))


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

@register_op("batch_norm", nondiff_inputs=["Mean", "Variance"])
def _batch_norm(ctx, inputs, attrs):
    """batch_norm_op.cc parity: running-stat update in train, frozen in test.
    When a mesh data axis is active (sync_batch_norm / sync_batch_norm_pass
    analog), XLA computes the batch stats over the *global* batch because the
    reduction is over the sharded batch dim — sync-BN falls out for free."""
    import os
    (x,) = inputs["X"]
    (scale,) = inputs["Scale"]
    (bias,) = inputs["Bias"]
    (mean,) = inputs["Mean"]
    (var,) = inputs["Variance"]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False) or ctx.is_test
    layout = attrs.get("data_layout", "NCHW")
    act = attrs.get("act", "")  # folded by layers.batch_norm (fused-BN path)
    bn_mode = os.environ.get("PDTPU_BN_MODE", "xla1")
    axes = tuple(i for i in range(x.ndim) if i != (1 if layout == "NCHW" else x.ndim - 1))
    ch_axis = 1 if layout == "NCHW" else x.ndim - 1
    shape = [1] * x.ndim
    shape[ch_axis] = x.shape[ch_axis]

    if is_test:
        use_mean, use_var = mean, var
        mean_out, var_out = mean, var
        saved_mean = mean
        saved_var = var
    else:
        from .pallas_kernels import fused_bn
        # Default lowering is the one-pass XLA stats below; the Pallas fused
        # kernel stays available for experimentation (PDTPU_BN_MODE=pallas)
        # but measured SLOWER end-to-end on v5e (116 ms vs 54 ms ResNet-50
        # step) — XLA's fused sibling-reduction read beats a hand-rolled
        # kernel that fights the conv layouts; see fused_bn.py.
        if (bn_mode.startswith("pallas") and layout == "NCHW"
                and act in ("", "relu")
                and fused_bn.supports(x.shape, x.dtype)
                and (fused_bn._on_tpu() or fused_bn.FORCE_PALLAS_INTERPRET)):
            if bn_mode == "pallas_stats":
                # perf probe only: frozen-stats gradient (no d/dx through
                # the batch statistics)
                bmean, bvar = fused_bn.bn_stats(
                    lax.stop_gradient(x),
                    interpret=fused_bn.FORCE_PALLAS_INTERPRET)
                inv = lax.rsqrt(bvar.reshape(shape) + eps)
                y = ((x.astype(jnp.float32) - bmean.reshape(shape)) * inv
                     * scale.reshape(shape) + bias.reshape(shape))
                if act == "relu":
                    y = jnp.maximum(y, 0.0)
                y = y.astype(x.dtype)
                mean_out = momentum * mean + (1.0 - momentum) * bmean
                var_out = momentum * var + (1.0 - momentum) * bvar
                return {
                    "Y": [y],
                    "MeanOut": [lax.stop_gradient(mean_out)],
                    "VarianceOut": [lax.stop_gradient(var_out)],
                    "SavedMean": [bmean],
                    "SavedVariance": [bvar],
                }
            # One-streaming-pass statistics + fused apply(+relu) Pallas kernel
            # (see fused_bn.py header for the roofline); XLA's lowering reads
            # the activation three times per training BN.
            y, bmean, bvar = fused_bn.fused_bn_act(x, scale, bias, eps, act,
                                                   False)
            mean_out = momentum * mean + (1.0 - momentum) * bmean
            var_out = momentum * var + (1.0 - momentum) * bvar
            return {
                "Y": [y],
                "MeanOut": [lax.stop_gradient(mean_out)],
                "VarianceOut": [lax.stop_gradient(var_out)],
                "SavedMean": [lax.stop_gradient(bmean)],
                "SavedVariance": [lax.stop_gradient(bvar)],
            }
    if not is_test:
        # statistics always in f32 (bf16 accumulation over N·H·W terms would
        # lose digits); x itself stays in its native dtype — the op is
        # AMP-"gray" so a bf16 conv trunk never round-trips through f32 HBM
        if bn_mode == "xla2":
            use_mean = jnp.mean(x, axis=axes, dtype=jnp.float32)
            # two-pass variance (E[(x−μ)²]): exact but costs a second read
            use_var = jnp.var(x.astype(jnp.float32), axis=axes)
        else:
            # one-pass stats: mean and E[x²] are sibling reductions XLA
            # fuses into a single read of x (9% faster ResNet-50 step,
            # measured). f32 accumulation + clamp guards the E[x²]−E[x]²
            # cancellation (cuDNN's training path makes the same trade —
            # batch_norm_op.cu:35).
            xf = x.astype(jnp.float32)
            use_mean = jnp.mean(xf, axis=axes)
            use_var = jnp.maximum(
                jnp.mean(xf * xf, axis=axes) - use_mean * use_mean, 0.0)
        mean_out = momentum * mean + (1.0 - momentum) * use_mean
        var_out = momentum * var + (1.0 - momentum) * use_var
        saved_mean = use_mean
        saved_var = use_var
    inv = lax.rsqrt(use_var.astype(jnp.float32).reshape(shape) + eps)
    y = ((x.astype(jnp.float32) - use_mean.astype(jnp.float32).reshape(shape))
         * inv * scale.reshape(shape) + bias.reshape(shape)).astype(x.dtype)
    if act:
        from .common import act_map
        y = act_map()[act](y)
    return {
        "Y": [y],
        "MeanOut": [lax.stop_gradient(mean_out)],
        "VarianceOut": [lax.stop_gradient(var_out)],
        "SavedMean": [lax.stop_gradient(saved_mean)],
        "SavedVariance": [lax.stop_gradient(saved_var)],
    }


@register_op("layer_norm")
def _layer_norm(ctx, inputs, attrs):
    """Gray-listed under AMP (like batch_norm): accepts bf16 activations and
    computes the statistics/normalization in f32 internally, returning the
    input dtype — black-listing it would bounce every residual-stream
    activation through f32 HBM twice per layer."""
    (x,) = inputs["X"]
    scale = inputs.get("Scale", [None])[0]
    bias = inputs.get("Bias", [None])[0]
    eps = attrs.get("epsilon", 1e-5)
    bna = attrs.get("begin_norm_axis", 1)
    axes = tuple(range(bna, x.ndim))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    norm_shape = (1,) * bna + x.shape[bna:]
    if scale is not None:
        y = y * scale.astype(jnp.float32).reshape(norm_shape)
    if bias is not None:
        y = y + bias.astype(jnp.float32).reshape(norm_shape)
    return {"Y": [y.astype(x.dtype)], "Mean": [mean.squeeze(axes)],
            "Variance": [var.squeeze(axes)]}


@register_op("rms_norm")
def _rms_norm(ctx, inputs, attrs):
    """Root-mean-square normalisation over the last axis (Zhang & Sennrich
    2019): y = x / sqrt(mean(x^2) + eps) * Scale. With a Gate input z (same
    shape as X) the input is x * silu(z) first, and with `group_size` the
    mean is taken over consecutive groups of that many channels, each
    normalised by itself (Mamba-2's gated norm). With `gate_after` (an
    activation's name) the gate multiplies the normalised, scaled result
    instead, as `act(z)` (Kimi Delta Attention's output gate: "sigmoid").
    Gray under AMP, like
    layer_norm: activations in whatever dtype, statistics in float32, the
    input dtype back."""
    (x,) = inputs["X"]
    scale = inputs.get("Scale", [None])[0]
    gate = inputs.get("Gate", [None])[0]
    eps = attrs.get("epsilon", 1e-5)
    group = int(attrs.get("group_size", 0)) or x.shape[-1]
    after = attrs.get("gate_after", "")
    xf = x.astype(jnp.float32)
    if gate is not None and not after:
        xf = xf * jax.nn.silu(gate.astype(jnp.float32))
    grouped = xf.reshape(x.shape[:-1] + (x.shape[-1] // group, group))
    ms = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
    y = (grouped * lax.rsqrt(ms + eps)).reshape(x.shape)
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    if after:       # the normalised, scaled result times act(gate)
        y = y * act_map()[after](gate.astype(jnp.float32))
    return one(y.astype(x.dtype))


def yarn_inv_freq(theta: float, dim: int, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies (Peng et al. 2023, arXiv:2309.00071, as
    `transformers`' `_compute_yarn_parameters` has them) for the `dim // 2`
    pairs of a rotated part of `dim` channels, float64: pair i turns at
    `f_i = theta^(-2i/dim)` where it makes more than `beta_fast` turns over
    the `original_max` positions the model was trained at, at `f_i / factor`
    where it makes fewer than `beta_slow`, and at a blend between, linear in
    the pair's index from `low = floor(c(beta_fast))` to `high =
    ceil(c(beta_slow))`, `c(n) = dim ln(original_max / (2 pi n)) /
    (2 ln theta)`."""
    i = np.arange(dim // 2, dtype=np.float64)
    f = float(theta) ** (-2.0 * i / dim)

    def c(turns):
        return (dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(c(beta_fast)), 0)
    high = min(math.ceil(c(beta_slow)), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - ramp) + f / factor * ramp


def _rope_tables_rule(d: int, t: int, theta: float, sign: float,
                      interleaved: bool, rule):
    """(C, S [T, 1, D] float32, the partner matrix [D, D] bool) of a rotation
    that `rule = (rotary_dim, yarn, factor)` changes. Per lane, as constants:
    its inverse frequency (0 on the channels past `rotary_dim`, which pass:
    cos 0 = 1, sin 0 = 0), its factor on both tables (1 on the passing
    ones), its sine's sign and its partner (itself where it passes)."""
    rotary_dim, yarn, factor = rule
    d_r = d if rotary_dim is None else int(rotary_dim)
    half = d_r // 2
    lane = np.arange(d)
    turns = lane < d_r
    pair = np.where(turns, lane // 2 if interleaved else lane % half, 0)
    freqs = (yarn_inv_freq(theta, d_r, *yarn) if yarn is not None
             else float(theta) ** (-2.0 * np.arange(half) / d_r))
    inv_freq = np.where(turns, freqs[pair], 0.0).astype(np.float32)
    scale = np.where(turns, 1.0 if factor is None else factor,
                     1.0).astype(np.float32)
    minus = (lane % 2 == 0) if interleaved else (lane < half)
    mate = np.where(turns, lane ^ 1 if interleaved else (lane + half) % d_r,
                    lane)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * (scale * np.where(minus, -sign, sign)).astype(
        np.float32))[:, None, :]
    return cos, sin, jnp.asarray(lane[:, None] == mate[None, :])


def _rope_pass(x, heads: int, theta: float, sign: float,
               interleaved: bool = False, rule=None):
    """`x · C + partner(x) · S` on packed heads X [B, T, H·D], float32
    multiply-adds, x's dtype back. C = cos ‖ cos and S = ∓sin ‖ ±sin of the
    angles `t * theta^(-2j/D)` are [T, D] float32 tables made from iotas
    (`sign` +1: the rotation; -1: the rotation by the negative angle, its
    transpose). `partner(x)` holds channel j + D/2 of its head at j and
    channel j - D/2 at j + D/2: the product of the head with a constant
    [D, D] matrix of 0 and 1, exact (each output is one input times 1,
    float32 accumulation; float32 inputs at full precision), so nothing here
    has a minor dimension of D/2 and nothing is concatenated. XLA hangs the
    multiply-add on the product's output and the layout the product wants
    on the relayouts its neighbours make anyway (PERF.md section 6,
    PR 38). `interleaved`: pair j is channels (2j, 2j + 1) and not
    (j, j + D/2), so channel c turns by pair c // 2's angle, takes channel
    c ^ 1 as its partner, and the even channels carry the -sin: another
    partner matrix and other tables, the same pass. `rule = (rotary_dim,
    yarn, factor)`: the first `rotary_dim` channels of each head turn (pairs
    within them) and the rest pass, which is C = 1, S = 0 on their lanes;
    `yarn` = (factor, original positions, beta_fast, beta_slow) takes the
    inverse frequencies from `yarn_inv_freq`; `factor` multiplies both
    tables on the turning lanes: other tables still, the same pass."""
    b, t, hd = x.shape
    d = hd // heads
    if rule is not None:
        cos, sin, swap = _rope_tables_rule(d, t, theta, sign, interleaved,
                                           rule)
        return _rope_apply(x, heads, cos, sin, lambda: swap.astype(x.dtype))
    half = d // 2
    lane = jnp.arange(d, dtype=jnp.int32)
    # each in the place the rotate-half form has had it: that form's jaxpr
    # is the one it was before there were two
    pair = lane // 2 if interleaved else lane % half
    inv_freq = jnp.asarray(theta, jnp.float32) ** (
        -pair.astype(jnp.float32) * 2.0 / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(ang)[:, None, :]                               # [T, 1, D]
    sin = (jnp.sin(ang) * jnp.where(
        lane % 2 == 0 if interleaved else lane < half,
        -sign, sign))[:, None, :]
    return _rope_apply(x, heads, cos, sin, lambda: (
        lane[:, None] == (lane[None, :] ^ 1 if interleaved
                          else (lane[None, :] + half) % d)).astype(x.dtype))


def _rope_apply(x, heads: int, cos, sin, swap_of):
    """`x · C + partner(x) · S` from the [T, 1, D] tables; `swap_of()` makes
    the partner matrix (after the view, where it has always been made)."""
    b, t, hd = x.shape
    xh = x.reshape(b, t, heads, hd // heads)
    swap = swap_of()
    partner = lax.dot_general(
        xh, swap, (((3,), (0,)), ((), ())),
        precision=(lax.Precision.HIGHEST if x.dtype.itemsize > 2
                   else lax.Precision.DEFAULT),
        preferred_element_type=jnp.float32)
    out = xh.astype(jnp.float32) * cos + partner * sin
    return out.astype(x.dtype).reshape(x.shape)


@functools.lru_cache(maxsize=None)
def _rope_jaxpr(shape, dtype, heads: int, theta: float, sign: float,
                interleaved: bool = False, rule=None):
    return jax.make_jaxpr(
        lambda x: _rope_pass(x, heads, theta, sign, interleaved, rule))(
            jax.ShapeDtypeStruct(shape, dtype))


def _rope_turn(x, heads: int, theta: float, sign: float,
               interleaved: bool = False, rule=None):
    """`_rope_pass` traced once a shape and a sign, its operations bound in
    place at the call: no call's edge in the step. The backward rule needs
    that: behind a `jax.jit` XLA concatenates dq‖dk in a pass of its own
    before the product (32 passes, 5 ms a step in the Ouro cell; PERF.md
    section 6, PR 38)."""
    closed = _rope_jaxpr(x.shape, x.dtype, heads, theta, sign, interleaved,
                         rule)
    (out,) = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, x)
    return out


# The forward pass is asked for three times an application (the primal, the
# forward rule, and the forward rule again behind the remat block) at 32
# applications of a looped decoder: a call, so that each costs one bind and
# not the pass's 36. Compiled, the step is the inline one's, operation for
# operation (both cells, compiled for the v5e), and the Ouro cell's set-up is
# 2 s shorter on the chip's host (PERF.md section 6, PR 38).
_rope_forward = jax.jit(_rope_pass, static_argnums=(1, 2, 3, 4, 5))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _rope(x, heads: int, theta: float, interleaved: bool = False, rule=None):
    return _rope_forward(x, heads, theta, 1.0, interleaved, rule)


def _rope_fwd(x, heads, theta, interleaved, rule):
    return _rope_forward(x, heads, theta, 1.0, interleaved, rule), None


def _rope_bwd(heads, theta, interleaved, rule, _, g):
    """A rotation's transpose is the rotation by the negative angle,
    `dX = g · C - partner(g) · S`: the same pass with the sines' sign
    turned, over the cotangent alone. No residual: the tables are remade
    from iotas, so a remat block keeps nothing for it."""
    return (_rope_turn(g, heads, theta, -1.0, interleaved, rule),)


_rope.defvjp(_rope_fwd, _rope_bwd)


@register_op("rotary_embedding")
def _rotary_embedding(ctx, inputs, attrs):
    """Rotary position embedding (Su et al. 2021) on packed heads: X
    [B, T, H*D], every head rotated alike, position t by the angles
    `t * theta^(-2j/D)`, j < D/2. The rotate-half convention:
    channel j pairs with channel j + D/2 of its head,
    out[j] = x[j] cos - x[j + D/2] sin, out[j + D/2] = x[j + D/2] cos +
    x[j] sin; under the attribute `interleaved` pair j is channels 2j and
    2j + 1 instead, out[2j] = x[2j] cos - x[2j + 1] sin, out[2j + 1] =
    x[2j + 1] cos + x[2j] sin. Both computed as `x · C + partner(x) · S` on
    whole heads (`_rope_turn`). Gray under AMP: the angles, their sines and the
    multiply-adds in float32, one rounding to the input dtype. The backward
    rule is the op's own (`_rope_bwd`; a `jax.custom_vjp` and not the
    registry's `grad_fn`, which would keep the op out of a remat block's one
    differentiated function): the same pass at the negative angle, no
    residual.

    Three attributes, each absent where unused, change the tables and
    nothing else: `rotary_dim` (the first that many channels of each head
    turn, pairs within them, and the rest pass), `yarn` = [factor, original
    positions, beta_fast, beta_slow] (YaRN's blended inverse frequencies:
    `yarn_inv_freq`) and `attention_factor` (on cosines and sines alike, so
    on the turning channels and not on the passing ones)."""
    (x,) = inputs["X"]
    rule = None
    if any(a in attrs for a in ("rotary_dim", "yarn", "attention_factor")):
        yarn = attrs.get("yarn")
        rule = (attrs.get("rotary_dim"),
                None if yarn is None else tuple(float(y) for y in yarn),
                attrs.get("attention_factor"))
    return one(_rope(x, int(attrs["num_heads"]),
                     float(attrs.get("theta", 10000.0)),
                     bool(attrs.get("interleaved", False)), rule))


@register_op("swiglu")
def _swiglu(ctx, inputs, attrs):
    """The gated MLP's activation (Shazeer 2020): silu(X) * Y, elementwise.
    Gray under AMP: the product in float32 (one rounding, not one after the
    silu and one after the product), the input dtype back."""
    (gate,) = inputs["X"]
    (up,) = inputs["Y"]
    out = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
    return one(out.astype(gate.dtype))


@register_op("loop_exit_gate")
def _loop_exit_gate(ctx, inputs, attrs):
    """The exit distribution of a looped model (Ouro, "Scaling Latent
    Reasoning via Looped Language Models", 2025). X [P, ..., D] holds the
    state after each of P passes; the gate of pass t < P is
    lambda_t = sigmoid(x_t . W + Bias) a position, and a position leaves at
    pass t with probability p_t = lambda_t prod_{j<t} (1 - lambda_j); what
    is left goes to the last pass. Out [P, ...] float32, summing to 1 over
    P. Gray under AMP: whatever arrives, the gate is computed in float32 at
    full matmul precision (the loss's weights are learnt through it)."""
    (x,) = inputs["X"]
    (w,) = inputs["W"]
    (b,) = inputs["Bias"]
    logits = jnp.einsum("p...d,d->p...", x[:-1].astype(jnp.float32),
                        w.astype(jnp.float32).reshape(-1),
                        precision=lax.Precision.HIGHEST)
    logits = logits + b.astype(jnp.float32).reshape(())
    # log-space: p_t = exp(log lambda_t + sum_{j<t} log(1 - lambda_j))
    log_stay = jax.nn.log_sigmoid(-logits)
    before = jnp.cumsum(log_stay, axis=0)
    zero = jnp.zeros_like(before[:1])
    log_p = jnp.concatenate(
        [jax.nn.log_sigmoid(logits) + jnp.concatenate([zero, before[:-1]]),
         before[-1:]])
    return one(jnp.exp(log_p))


@register_op("loop_exit_loss")
def _loop_exit_loss(ctx, inputs, attrs):
    """The exit-weighted objective of a looped model (Ouro's first stage):
    the mean over the positions of sum_t p_t CE_t - beta H(p), H(p) =
    -sum_t p_t log p_t. P [P, ...] (`loop_exit_gate`), CE [P, ...(, 1)] the
    per-position loss of each pass's exit. Also ExitShare [P], the mean p_t,
    and ExitEntropy, the mean H(p), for the counters. Float32 throughout."""
    (p,) = inputs["P"]
    (ce,) = inputs["CE"]
    p = p.astype(jnp.float32)
    ce = ce.astype(jnp.float32).reshape(p.shape)
    # 0 log 0 = 0, with a finite gradient there
    entropy = -jnp.sum(p * jnp.log(jnp.maximum(
        p, jnp.finfo(jnp.float32).tiny)), axis=0)
    per_pos = jnp.sum(p * ce, axis=0) - attrs.get("beta", 0.0) * entropy
    share = jnp.mean(p.reshape(p.shape[0], -1), axis=1)
    return {"Loss": [jnp.mean(per_pos)],
            "ExitShare": [lax.stop_gradient(share)],
            "ExitEntropy": [lax.stop_gradient(jnp.mean(entropy))]}


@register_op("group_norm")
def _group_norm(ctx, inputs, attrs):
    (x,) = inputs["X"]
    scale = inputs.get("Scale", [None])[0]
    bias = inputs.get("Bias", [None])[0]
    eps = attrs.get("epsilon", 1e-5)
    groups = attrs["groups"]
    nhwc = attrs.get("data_layout", "NCHW") == "NHWC"
    if nhwc:  # normalize in channels-first, restore on the way out
        x = jnp.moveaxis(x, -1, 1)
    n, c = x.shape[0], x.shape[1]
    rest = x.shape[2:]
    xg = x.reshape((n, groups, c // groups) + rest)
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    y = ((xg - mean) * lax.rsqrt(var + eps)).reshape(x.shape)
    cshape = (1, c) + (1,) * len(rest)
    if scale is not None:
        y = y * scale.reshape(cshape)
    if bias is not None:
        y = y + bias.reshape(cshape)
    if nhwc:
        y = jnp.moveaxis(y, 1, -1)
    return {"Y": [y], "Mean": [mean.reshape(n, groups)], "Variance": [var.reshape(n, groups)]}


@register_op("instance_norm")
def _instance_norm(ctx, inputs, attrs):
    (x,) = inputs["X"]
    scale = inputs.get("Scale", [None])[0]
    bias = inputs.get("Bias", [None])[0]
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps)
    cshape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(cshape)
    if bias is not None:
        y = y + bias.reshape(cshape)
    return {"Y": [y]}


@register_op("l2_normalize")
def _l2_normalize(ctx, inputs, attrs):
    (x,) = inputs["X"]
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-12)
    norm = jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=True))
    return one(x / jnp.maximum(norm, eps))


# ---------------------------------------------------------------------------
# dropout / embedding
# ---------------------------------------------------------------------------

@register_op("dropout")
def _dropout(ctx, inputs, attrs):
    (x,) = inputs["X"]
    p = attrs.get("dropout_prob", 0.5)
    is_test = attrs.get("is_test", False) or ctx.is_test
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if is_test or p == 0.0:
        # reference dropout_op.cc: at inference, downgrade_in_infer scales by
        # (1-p); upscale_in_train is identity (scaling happened in training).
        y = x * (1.0 - p) if (impl == "downgrade_in_infer" and is_test and p > 0.0) else x
        return {"Out": [y], "Mask": [jnp.ones_like(x)]}
    keep = jax.random.bernoulli(ctx.rng(), 1.0 - p, x.shape)
    mask = keep.astype(x.dtype)
    if impl == "upscale_in_train":
        y = x * mask / (1.0 - p)
    else:
        y = x * mask
    return {"Out": [y], "Mask": [lax.stop_gradient(mask)]}


def _lookup_sparse_grad(attrs):
    """lookup_table_op.cc is_sparse=True GradOpMaker analog: the table's
    cotangent is SelectedRows (ids, dOut rows) — a [vocab, dim] dense
    gradient is never materialized (SURVEY §7 DeepFM-scale hard part)."""
    if not attrs.get("is_sparse"):
        return None  # dense path: generic jax.vjp scatter-add

    def grad(ctx, inputs, attrs2, outputs, out_cots):
        from ..core.selected_rows import SelectedRows

        (w,) = inputs["W"]
        (ids,) = inputs["Ids"]
        (g,) = out_cots["Out"]
        squeeze_last = ids.ndim >= 2 and ids.shape[-1] == 1
        idx = ids[..., 0] if squeeze_last else ids
        flat_ids = idx.reshape(-1).astype(jnp.int32)
        rows = g.reshape(-1, g.shape[-1])
        if not attrs2.get("row_pack_dt"):  # packed tables keep f32 grads
            rows = rows.astype(w.dtype)
        padding_idx = attrs2.get("padding_idx", -1)
        if padding_idx is not None and padding_idx >= 0:
            rows = jnp.where((flat_ids == padding_idx)[:, None], 0.0, rows)
        out = {"W": [SelectedRows(flat_ids, rows, w.shape[0])],
               "Ids": [None]}
        # pending deferred-update state is opt state, not a diff input
        for slot in ("PendingPos", "PendingCum"):
            if slot in inputs:
                out[slot] = [None]
        return out

    return grad


@register_op("lookup_table", nondiff_inputs=["Ids", "PendingPos", "PendingCum"],
             grad_fn=_lookup_sparse_grad)
def _lookup_table(ctx, inputs, attrs):
    """lookup_table_op.cc: W[ids]; padding_idx rows produce zeros. Grad is an
    XLA scatter-add (dense) by default; with is_sparse=True the grad is a
    SelectedRows rows bundle consumed row-wise by sgd/adam/adagrad.

    With PendingPos/PendingCum inputs (wired by a deferred-row optimizer,
    ops/deferred_rows.py), the read adds the postab-indexed pending
    cumulative delta to the base gather, so lookups always see the exact
    serial-update value regardless of fold cadence — the TPU-native analog
    of the reference's distributed_lookup_table prefetch rewrite
    (parameter_prefetch.cc). The extra CumOut output feeds the deferred
    optimizer op, which reuses these gathers instead of issuing its own."""
    (w,) = inputs["W"]
    (ids,) = inputs["Ids"]
    squeeze_last = ids.ndim >= 2 and ids.shape[-1] == 1
    idx = ids[..., 0] if squeeze_last else ids
    rp_dt = attrs.get("row_pack_dt")
    if rp_dt:
        # packed row-major table (ops/deferred_rows.py): [V, 128] uint16
        # holding dt bit-split f32 values per row — full-row gather, then
        # bit-exact unpack
        from .deferred_rows import unpack_rows
        q = idx.reshape(-1).astype(jnp.int32)
        out = unpack_rows(jnp.take(w, q, axis=0), int(rp_dt))
        out = out.reshape(idx.shape + (int(rp_dt),))
    else:
        out = jnp.take(w, idx, axis=0)
    padding_idx = attrs.get("padding_idx", -1)
    if "PendingPos" in inputs:
        from .deferred_rows import lookup_join
        (postab,) = inputs["PendingPos"]
        (log_cum,) = inputs["PendingCum"]
        q = idx.reshape(-1).astype(jnp.int32)
        cur, cum = lookup_join(postab, log_cum, out.reshape(q.shape[0], -1), q)
        shp = idx.shape + (w.shape[-1],)
        out = lax.stop_gradient(cur.reshape(shp) - out) + out
        if padding_idx is not None and padding_idx >= 0:
            out = jnp.where((idx == padding_idx)[..., None], 0.0, out)
        return {"Out": [out],
                "CumOut": [lax.stop_gradient(cum.reshape(shp))]}
    if padding_idx is not None and padding_idx >= 0:
        out = jnp.where((idx == padding_idx)[..., None], 0.0, out)
    return one(out)


@register_op("lookup_table_v2", nondiff_inputs=["Ids"],
             grad_fn=_lookup_sparse_grad)
def _lookup_table_v2(ctx, inputs, attrs):
    return _lookup_table_impl(ctx, inputs, attrs)


def _lookup_table_impl(ctx, inputs, attrs):
    (w,) = inputs["W"]
    (ids,) = inputs["Ids"]
    out = jnp.take(w, ids, axis=0)
    padding_idx = attrs.get("padding_idx", -1)
    if padding_idx is not None and padding_idx >= 0:
        out = jnp.where((ids == padding_idx)[..., None], 0.0, out)
    return one(out)


@register_op("one_hot", differentiable=False)
def _one_hot(ctx, inputs, attrs):
    (x,) = inputs["X"]
    depth = attrs["depth"]
    idx = x[..., 0] if x.ndim >= 2 and x.shape[-1] == 1 else x
    return one(jax.nn.one_hot(idx, depth, dtype=jnp.float32))


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

@register_op("cross_entropy", nondiff_inputs=["Label"])
def _cross_entropy(ctx, inputs, attrs):
    """cross_entropy_op.cc: input is a probability distribution (post-softmax).
    Hard labels (int) index; soft labels dot."""
    (x,) = inputs["X"]
    (label,) = inputs["Label"]
    eps = 1e-8
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        loss = _pick_hard_label(jnp.log(x + eps), label, -1,
                                attrs.get("ignore_index", -100))
    return one(loss)


def _pick_hard_label(logp, label, axis, ignore):
    """Index log-probs by integer labels along `axis` (any position).
    label may carry a singleton at the class axis or omit it."""
    ax = axis % logp.ndim
    idx = label
    if idx.ndim == logp.ndim and idx.shape[ax] == 1:
        idx = jnp.squeeze(idx, ax)
    picked = jnp.take_along_axis(logp, jnp.expand_dims(idx.astype(jnp.int32), ax), axis=ax)
    loss = -picked
    if ignore is not None:
        loss = jnp.where(jnp.expand_dims(idx == ignore, ax), 0.0, loss)
    return loss


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _hard_label_ce(logits, idx, axis, ignore):
    """Memory-lean hard-label CE: works on low-precision logits directly
    (f32 reductions in-register), saves only (logits, idx, lse) for the
    backward — never materializes a full-vocab f32 softmax. At BERT's MLM
    head ([B·T, 30k] logits) this halves the HBM traffic of the loss."""
    loss, _ = _hard_label_ce_fwd(logits, idx, axis, ignore)
    return loss


def _hard_label_ce_fwd(logits, idx, axis, ignore):
    ax = axis % logits.ndim
    # max over the native dtype is exact (max of bf16 values IS a bf16), and
    # each .astype(f32) below has exactly one consumer chain so XLA fuses the
    # cast into the reduce — a shared `lf = logits.astype(f32)` would
    # materialize a full-vocab f32 copy (4 GB on the BERT-base MLM head)
    m = jnp.max(logits, axis=ax, keepdims=True)
    sumexp = jnp.sum(jnp.exp(logits.astype(jnp.float32)
                             - m.astype(jnp.float32)),
                     axis=ax, keepdims=True)
    lse = m.astype(jnp.float32) + jnp.log(sumexp)
    picked = jnp.take_along_axis(
        logits, jnp.expand_dims(idx.astype(jnp.int32), ax),
        axis=ax).astype(jnp.float32)
    loss = lse - picked
    if ignore is not None:
        loss = jnp.where(jnp.expand_dims(idx == ignore, ax), 0.0, loss)
    return loss, (logits, idx, lse)


def _hard_label_ce_bwd(axis, ignore, res, g):
    logits, idx, lse = res
    ax = axis % logits.ndim
    p = jnp.exp(logits.astype(jnp.float32) - lse)
    iota = lax.broadcasted_iota(jnp.int32, logits.shape, ax)
    onehot = iota == jnp.expand_dims(idx.astype(jnp.int32), ax)
    gv = g
    if ignore is not None:
        gv = jnp.where(jnp.expand_dims(idx == ignore, ax), 0.0, gv)
    dlogits = ((p - onehot) * gv).astype(logits.dtype)
    return dlogits, None


_hard_label_ce.defvjp(_hard_label_ce_fwd, _hard_label_ce_bwd)


@register_op("softmax_with_cross_entropy", nondiff_inputs=["Label"])
def _softmax_with_cross_entropy(ctx, inputs, attrs):
    (logits,) = inputs["Logits"]
    (label,) = inputs["Label"]
    axis = attrs.get("axis", -1)
    if not attrs.get("soft_label", False):
        ax = axis % logits.ndim
        idx = label
        if idx.ndim == logits.ndim and idx.shape[ax] == 1:
            idx = jnp.squeeze(idx, ax)
        loss = _hard_label_ce(logits, idx, axis,
                              attrs.get("ignore_index", -100))
        # recomputed independently of the loss path → DCE'd when unused
        softmax = jax.nn.softmax(logits.astype(jnp.float32), axis=axis)
        return {"Loss": [loss], "Softmax": [softmax]}
    # soft-label path: the op is AMP-white-listed (inputs may arrive bf16),
    # so upcast — a vocab-length bf16 accumulation would lose ~3 digits
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=axis)
    loss = -jnp.sum(label.astype(jnp.float32) * logp, axis=axis,
                    keepdims=True)
    return {"Loss": [loss], "Softmax": [jnp.exp(logp)]}


# What a loop iteration hands from one fusion to the next stays under this
# many bytes, which the compiler then keeps on the chip (memory space 1 in
# the compiled text; a v5e places 100 MB there and not 200): the forward
# loop's float32 [rows, vocab] logits, between the product with its row
# maximum and the exponentials, and the backward loop's [rows, vocab] softmax
# gradient, which the MXU reads in two bytes an element
_CE_CHUNK_BYTES = 128 << 20
# Rows from which an iteration of the backward loop is no longer bound by its
# accumulator: it adds a [hidden, rows] x [rows, vocab] product into the
# float32 [hidden, vocab] sum of dW, 2 * rows * hidden * vocab operations for
# the 8 * hidden * vocab bytes the sum is read and written, rows / 4 a byte
# whatever the hidden width; a v5e's MXU and HBM take equally long at 240
# (197 TFLOP/s over 819 GB/s), 960 rows: the next power of two
_CE_RIDGE_ROWS = 1024


def _cdiv(a, b):
    return (a + b - 1) // b


def linear_ce_chunk_rows(n_pos: int, vocab: int):
    """(forward rows, backward rows): the rows one iteration of each of the
    labelled-rows loops projects, from the shapes alone.

    Forward: the largest power of two whose float32 [rows, vocab] logits
    stay under _CE_CHUNK_BYTES (1,024 rows at BERT's 30,522, 2,048 at
    16,384, 512 at 49,152). Its product runs at the MXU's pace from 512 rows
    on; more rows only push the logits out to HBM.

    Backward: that loop holds no float32 logits (they are fused into the
    softmax gradient) but reads and writes the float32 [hidden, vocab]
    accumulator of dW every iteration, whatever the rows. So it takes whole
    forward chunks up to _CE_RIDGE_ROWS, as far as the softmax gradient
    stays under _CE_CHUNK_BYTES. Where the forward's rows are at the ridge
    already (BERT's, Nemotron's) both loops take the same.

    Never more than the positions there are, rounded up to a sublane
    multiple."""
    def under_cap(itemsize):
        rows = max(8, _CE_CHUNK_BYTES // (itemsize * vocab))
        return 1 << (rows.bit_length() - 1)

    n_rows = _cdiv(n_pos, 8) * 8
    fwd = min(under_cap(4), n_rows)
    bwd = min(max(fwd, min(_CE_RIDGE_ROWS, under_cap(2))), n_rows)
    return fwd, fwd * (bwd // fwd)


def _labelled_first(valid, chunk):
    """Order the positions with the labelled ones first, both kept in their
    own order. Returns `rank` [n_pos] (position -> slot), `order` (slot ->
    position, padded to whole chunks with 0) and the labelled count."""
    n_pos = valid.shape[0]
    seen = jnp.cumsum(valid.astype(jnp.int32))
    n = seen[-1]
    pos = jnp.arange(n_pos, dtype=jnp.int32)
    rank = jnp.where(valid, seen - 1, n + pos - seen)
    order = jnp.zeros((_cdiv(n_pos, chunk) * chunk,), jnp.int32)
    order = order.at[rank].set(pos, unique_indices=True,
                               mode="promise_in_bounds")
    return rank, order, n


def _rows(a, idx):
    return a.at[idx].get(mode="promise_in_bounds")


def _chunk_logits(x, w, b, order, lbl, at, chunk):
    """The `chunk` ordered positions from slot `at`: which they are, their
    rows of x, the float32 logits of those rows and a one-hot mask of their
    labels."""
    slots = lax.dynamic_slice(order, (at,), (chunk,))
    xc = _rows(x, slots)
    logits = jnp.dot(xc, w, preferred_element_type=jnp.float32)
    if b is not None:
        logits = logits + b
    hot = (lax.broadcasted_iota(jnp.int32, logits.shape, 1)
           == _rows(lbl, slots)[:, None])
    return slots, xc, logits, hot


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _linear_ce(x, w, b, lbl, ignore, chunks):
    """Per-position softmax cross-entropy of `x @ w + b` against hard labels
    `lbl`, 0 where the label is `ignore` — computed on the labelled rows
    only, a chunk of them at a time, so that [positions, vocab] logits never
    exist. x [N, H]; w [H, V] and b [V] (or None: no bias) are rounded to
    x's dtype for the products, which accumulate in float32 like everything
    after them; lbl [N] int; `chunks` the (forward, backward) rows an
    iteration, the second a multiple of the first
    (`linear_ce_chunk_rows`). Exact for any number of labelled rows: the
    loops run ceil(labelled / rows) times (jax does not reverse a loop of
    dynamic length by itself, hence the custom_vjp). The gradient rule keeps
    the inputs and the rows' log-sum-exp and makes the logits again, a chunk
    at a time: the op is its own rematerialisation (`own_remat` where it is
    registered)."""
    loss, _ = _linear_ce_fwd(x, w, b, lbl, ignore, chunks)
    return loss


def _linear_ce_fwd(x, w, b, lbl, ignore, chunks):
    chunk, bwd_chunk = chunks
    valid = lbl != ignore
    rank, order, n = _labelled_first(valid, bwd_chunk)
    w_lo = w.astype(x.dtype)
    bf = None if b is None else b.astype(jnp.float32)

    def body(i, carry):
        loss_c, lse_c = carry
        at = i * chunk
        _, _, logits, hot = _chunk_logits(x, w_lo, bf, order, lbl, at, chunk)
        m = jnp.max(logits, axis=1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=1))
        picked = jnp.sum(jnp.where(hot, logits, 0.0), axis=1)
        return (lax.dynamic_update_slice(loss_c, lse - picked, (at,)),
                lax.dynamic_update_slice(lse_c, lse, (at,)))

    zeros = jnp.zeros(order.shape, jnp.float32)
    loss_c, lse_c = lax.fori_loop(0, _cdiv(n, chunk), body, (zeros, zeros))
    loss = jnp.where(valid, _rows(loss_c, rank), 0.0)
    return loss, (x, w, b, lbl, rank, order, n, lse_c)


def _linear_ce_bwd(ignore, chunks, res, g):
    x, w, b, lbl, rank, order, n, lse_c = res
    fwd_chunk, chunk = chunks
    valid = lbl != ignore
    w_lo = w.astype(x.dtype)
    bf = None if b is None else b.astype(jnp.float32)
    g = g.astype(jnp.float32)
    row = jnp.arange(chunk, dtype=jnp.int32)

    def body(i, carry):
        dx_c, dw, db = carry
        at = i * chunk
        slots, xc, logits, hot = _chunk_logits(x, w_lo, bf, order, lbl, at,
                                               chunk)
        # slots from the labelled count on hold ignored positions
        live = at + row < n
        gc = jnp.where(live, _rows(g, slots), 0.0)
        lse = lax.dynamic_slice(lse_c, (at,), (chunk,))
        if chunk != fwd_chunk:
            # and past the forward loop's last chunk no log-sum-exp
            lse = jnp.where(live, lse, jnp.inf)
        dl = (jnp.exp(logits - lse[:, None]) - hot) * gc[:, None]
        dl_lo = dl.astype(x.dtype)
        dxc = lax.dot_general(dl_lo, w_lo, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
        dw = dw + lax.dot_general(xc, dl_lo, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dx_c = lax.dynamic_update_slice(dx_c, dxc.astype(x.dtype), (at, 0))
        return dx_c, dw, None if db is None else db + jnp.sum(dl, axis=0)

    dx_c, dw, db = lax.fori_loop(
        0, _cdiv(n, chunk), body,
        (jnp.zeros((order.shape[0], x.shape[1]), x.dtype),
         jnp.zeros(w.shape, jnp.float32),
         None if b is None else jnp.zeros(b.shape, jnp.float32)))
    dx = jnp.where(valid[:, None], _rows(dx_c, rank), 0)
    return (dx, dw.astype(w.dtype),
            None if b is None else db.astype(b.dtype), None)


_linear_ce.defvjp(_linear_ce_fwd, _linear_ce_bwd)


@register_op("linear_softmax_with_cross_entropy", nondiff_inputs=["Label"],
             own_remat=True)
def _linear_softmax_with_cross_entropy(ctx, inputs, attrs):
    """softmax_with_cross_entropy(X @ W + Bias, Label, ignore_index) in one
    op that projects the labelled positions only (`_linear_ce`): the loss
    and every gradient are those of the pair, the positions whose label is
    ignore_index (loss 0, gradient exactly 0 there too) are never
    multiplied. Under a mesh each data shard compacts its own positions (a
    global compaction would make GSPMD gather the batch) and the gradients
    of W and Bias are summed over the shards once, by the shard_map's own
    transpose. With the attribute `transpose_w` W is [size, hidden], an
    embedding's table serving as the head's matrix. Also returns the rows it
    projected (whole chunks) and the labelled count, summed over the
    shards."""
    from ..observability import get_registry
    from .fused_ops import _per_data_shard, _under_mesh

    (x,) = inputs["X"]
    (w,) = inputs["W"]
    (label,) = inputs["Label"]
    b = opt_input(inputs, "Bias")      # None: a head without a bias
    if attrs.get("transpose_w", False):
        # a tied head: W is an embedding's [size, hidden] table, and its
        # gradient comes back in the table's layout to meet the lookup's
        w = jnp.swapaxes(w, 0, 1)
    ignore = attrs.get("ignore_index", -100)
    path = "per_data_shard" if _under_mesh(ctx) else "whole"

    def head(x, idx, w, b, key=None):
        flat = idx.reshape(-1)
        chunks = linear_ce_chunk_rows(flat.shape[0], w.shape[1])
        obs = get_registry()
        obs.counter("ops/linear_ce_lowered", path=path).inc()
        for loop, chunk in zip(("forward", "backward"), chunks):
            obs.gauge("ops/linear_ce_chunk_rows", loop=loop).set(chunk)
        loss = _linear_ce(x.reshape(flat.shape[0], -1), w, b, flat, ignore,
                          chunks)
        n = jnp.sum(flat != ignore, dtype=jnp.int32)
        rows = _cdiv(n, chunks[0]) * chunks[0]
        return loss.reshape(idx.shape), jnp.stack([rows, n])[None]

    idx = label.reshape(x.shape[:-1] + (1,))
    if path == "whole":
        loss, counts = head(x, idx, w, b)
    else:
        loss, counts = _per_data_shard(ctx, head, (x, idx), None,
                                       replicated=(w, b))
    rows, n = jnp.sum(counts, axis=0)
    return {"Loss": [loss], "RowsComputed": [rows], "Labelled": [n]}


@register_op("sigmoid_cross_entropy_with_logits", nondiff_inputs=["Label"])
def _sigmoid_ce(ctx, inputs, attrs):
    (x,) = inputs["X"]
    (label,) = inputs["Label"]
    loss = jnp.maximum(x, 0.0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore = attrs.get("ignore_index", -100)
    loss = jnp.where(label == ignore, 0.0, loss)
    if attrs.get("normalize", False):
        n = jnp.maximum(jnp.sum(jnp.where(label != ignore, 1.0, 0.0)), 1.0)
        loss = loss / n
    return one(loss)


@register_op("square_error_cost", nondiff_inputs=["Label"])
def _square_error_cost(ctx, inputs, attrs):
    (x,) = inputs["X"]
    (label,) = inputs["Label"]
    return one(jnp.square(x - label))


@register_op("smooth_l1_loss", nondiff_inputs=["Y"])
def _smooth_l1(ctx, inputs, attrs):
    (x,) = inputs["X"]
    (y,) = inputs["Y"]
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    diff = jnp.abs(x - y)
    loss = jnp.where(diff < 1.0 / s2, 0.5 * s2 * diff * diff, diff - 0.5 / s2)
    loss = jnp.sum(loss.reshape(x.shape[0], -1), axis=1, keepdims=True)
    return {"Out": [loss], "Diff": [x - y]}


@register_op("huber_loss", nondiff_inputs=["Y"])
def _huber(ctx, inputs, attrs):
    (x,) = inputs["X"]
    (y,) = inputs["Y"]
    delta = attrs.get("delta", 1.0)
    diff = y - x
    ad = jnp.abs(diff)
    loss = jnp.where(ad <= delta, 0.5 * diff * diff, delta * (ad - 0.5 * delta))
    return {"Out": [loss], "Residual": [diff]}


@register_op("kldiv_loss", nondiff_inputs=["Target"])
def _kldiv(ctx, inputs, attrs):
    (x,) = inputs["X"]
    (t,) = inputs["Target"]
    loss = jnp.where(t > 0, t * (jnp.log(t) - x), 0.0)
    red = attrs.get("reduction", "mean")
    if red == "mean":
        loss = jnp.mean(loss)
    elif red == "sum":
        loss = jnp.sum(loss)
    elif red == "batchmean":
        loss = jnp.sum(loss) / x.shape[0]
    return one(loss)


@register_op("log_loss", nondiff_inputs=["Labels"])
def _log_loss(ctx, inputs, attrs):
    (p,) = inputs["Predicted"]
    (y,) = inputs["Labels"]
    eps = attrs.get("epsilon", 1e-4)
    return {"Loss": [-y * jnp.log(p + eps) - (1.0 - y) * jnp.log(1.0 - p + eps)]}


@register_op("margin_rank_loss", nondiff_inputs=["Label"])
def _margin_rank_loss(ctx, inputs, attrs):
    (x1,) = inputs["X1"]
    (x2,) = inputs["X2"]
    (label,) = inputs["Label"]
    margin = attrs.get("margin", 0.0)
    out = jnp.maximum(0.0, -label * (x1 - x2) + margin)
    return {"Out": [out], "Activated": [lax.stop_gradient((out > 0).astype(x1.dtype))]}


@register_op("cos_sim", nondiff_inputs=[])
def _cos_sim(ctx, inputs, attrs):
    (x,) = inputs["X"]
    (y,) = inputs["Y"]
    xn = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(y * y, axis=-1, keepdims=True))
    out = jnp.sum(x * y, axis=-1, keepdims=True) / (xn * yn + 1e-12)
    return {"Out": [out], "XNorm": [xn], "YNorm": [yn]}
