"""Pallas fused batch-norm kernel (interpret mode) vs the jnp reference, and
the batch_norm layer's act-folding contract.

The kernel is opt-in on TPU (PDTPU_BN_MODE=pallas; measured slower than the
default one-pass XLA lowering on v5e, kept for other-chip experiments), but
its numerics must stay correct either way.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas_kernels import fused_bn


def _ref_bn(x, scale, bias, eps, act, residual=None):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=(0, 2, 3))
    var = jnp.var(xf, axis=(0, 2, 3))
    sh = (1, x.shape[1], 1, 1)
    y = ((xf - mean.reshape(sh)) * jax.lax.rsqrt(var.reshape(sh) + eps)
         * scale.reshape(sh) + bias.reshape(sh))
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype), mean, var


@pytest.fixture(autouse=True)
def _interpret():
    fused_bn.FORCE_PALLAS_INTERPRET = True
    yield
    fused_bn.FORCE_PALLAS_INTERPRET = False


@pytest.mark.parametrize("shape,act", [
    ((4, 16, 8, 32), "relu"),
    ((4, 16, 8, 32), ""),
    ((2, 32, 16, 16), "relu"),
])
def test_fused_bn_forward_and_grads(shape, act):
    rng = np.random.RandomState(0)
    n, c, h, w = shape
    x = jnp.asarray(rng.randn(*shape).astype("float32") * 1.5 + 0.3)
    scale = jnp.asarray(rng.rand(c).astype("float32") + 0.5)
    bias = jnp.asarray(rng.randn(c).astype("float32") * 0.2)
    dy = jnp.asarray(rng.randn(*shape).astype("float32"))

    def loss_p(x, s, b):
        y, m, v = fused_bn.fused_bn_act(x, s, b, 1e-5, act, False)
        return jnp.sum(y * dy), (y, m, v)

    def loss_r(x, s, b):
        y, m, v = _ref_bn(x, s, b, 1e-5, act)
        return jnp.sum(y * dy), (y, m, v)

    (lp, (yp, mp, vp)), gp = jax.value_and_grad(
        loss_p, argnums=(0, 1, 2), has_aux=True)(x, scale, bias)
    (lr, (yr, mr, vr)), gr = jax.value_and_grad(
        loss_r, argnums=(0, 1, 2), has_aux=True)(x, scale, bias)

    np.testing.assert_allclose(np.asarray(yp), np.asarray(yr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(mp), np.asarray(mr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(vp), np.asarray(vr), atol=1e-4,
                               rtol=1e-5)
    for a, b, nm in zip(gp, gr, ("dx", "dscale", "dbias")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=1e-4, err_msg=nm)


def test_fused_bn_residual_grad():
    rng = np.random.RandomState(1)
    shape = (2, 16, 8, 16)
    x = jnp.asarray(rng.randn(*shape).astype("float32"))
    res = jnp.asarray(rng.randn(*shape).astype("float32"))
    scale = jnp.asarray(rng.rand(16).astype("float32") + 0.5)
    bias = jnp.zeros((16,), jnp.float32)
    dy = jnp.asarray(rng.randn(*shape).astype("float32"))

    def loss_p(x, s, b, r):
        y, _, _ = fused_bn.fused_bn_act(x, s, b, 1e-5, "relu", True, r)
        return jnp.sum(y * dy)

    def loss_r(x, s, b, r):
        y, _, _ = _ref_bn(x, s, b, 1e-5, "relu", residual=r)
        return jnp.sum(y * dy)

    gp = jax.grad(loss_p, argnums=(0, 1, 2, 3))(x, scale, bias, res)
    gr = jax.grad(loss_r, argnums=(0, 1, 2, 3))(x, scale, bias, res)
    for a, b, nm in zip(gp, gr, ("dx", "dscale", "dbias", "dres")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=1e-4, err_msg=nm)


def test_batch_norm_layer_act_folding():
    """batch_norm(act='relu') folds the relu into the op (no separate relu
    op in the program) and still produces relu'd output on the default
    lowering."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = layers.data("x", [8, 6, 6])
        out = layers.batch_norm(xv, act="relu")
        loss = layers.mean(out)
    assert not any(op.type == "relu" for op in main.global_block().ops)
    bn_ops = [op for op in main.global_block().ops if op.type == "batch_norm"]
    assert bn_ops and bn_ops[0].attrs.get("act") == "relu"

    exe = fluid.Executor(fluid.TPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        x = np.random.RandomState(0).randn(4, 8, 6, 6).astype("float32")
        got = exe.run(main, feed={"x": x}, fetch_list=[out])[0]
    assert (got >= 0).all()
    ref = x - x.mean(axis=(0, 2, 3), keepdims=True)
    ref = ref / np.sqrt(x.var(axis=(0, 2, 3), keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, np.maximum(ref, 0), atol=1e-4)


# ---------------------------------------------------------------------------
# fused 1x1-conv + BN (+residual +relu) epilogue kernels (ISSUE 19)
# ---------------------------------------------------------------------------

def _ref_conv_bn(x, w, scale, bias, eps, act, stride, residual=None):
    return fused_bn.conv_bn_xla(x, w, scale, bias, eps, act, stride,
                                residual=residual)


@pytest.mark.parametrize("stride,act,with_res", [
    (1, "relu", True),
    (1, "", False),
    (2, "relu", False),
    (2, "", True),
])
def test_fused_conv_bn_interpret_parity(stride, act, with_res):
    """Pallas conv+BN kernel (interpret mode) vs the exact XLA composition:
    forward outputs, batch stats, and all five grads."""
    rng = np.random.RandomState(0)
    n, ci, co, hw = 4, 16, 32, 16
    x = jnp.asarray(rng.randn(n, ci, hw, hw).astype("float32"))
    w = jnp.asarray((rng.randn(co, ci, 1, 1) * 0.1).astype("float32"))
    scale = jnp.asarray(rng.rand(co).astype("float32") + 0.5)
    bias = jnp.asarray(rng.randn(co).astype("float32") * 0.2)
    hs = -(-hw // stride)
    res = (jnp.asarray(rng.randn(n, co, hs, hs).astype("float32"))
           if with_res else None)
    dy = jnp.asarray(rng.randn(n, co, hs, hs).astype("float32"))

    def loss_p(x, w, s, b, r):
        y, m, v = fused_bn.fused_conv_bn_act(x, w, s, b, 1e-5, act, stride,
                                             with_res, r)
        return jnp.sum(y * dy), (y, m, v)

    def loss_r(x, w, s, b, r):
        y, m, v = _ref_conv_bn(x, w, s, b, 1e-5, act, stride, residual=r)
        return jnp.sum(y * dy), (y, m, v)

    argnums = (0, 1, 2, 3, 4) if with_res else (0, 1, 2, 3)
    (_, (yp, mp, vp)), gp = jax.value_and_grad(
        loss_p, argnums=argnums, has_aux=True)(x, w, scale, bias, res)
    (_, (yr, mr, vr)), gr = jax.value_and_grad(
        loss_r, argnums=argnums, has_aux=True)(x, w, scale, bias, res)

    np.testing.assert_allclose(np.asarray(yp), np.asarray(yr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(mp), np.asarray(mr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(vp), np.asarray(vr), atol=1e-5,
                               rtol=1e-5)
    names = ("dx", "dw", "dscale", "dbias", "dres")
    for a, b, nm in zip(gp, gr, names):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=1e-4, err_msg=nm)


def test_conv_bn_supports_gate():
    """Static support gate: 1x1 only, stride 1/2, lane-aligned channels,
    enough output rows to tile."""
    assert fused_bn.conv_bn_supports((8, 64, 16, 16), (128, 64, 1, 1), 1)
    assert not fused_bn.conv_bn_supports((8, 64, 16, 16), (128, 64, 3, 3), 1)
    assert not fused_bn.conv_bn_supports((8, 64, 16, 16), (128, 64, 1, 1), 4)
    assert not fused_bn.conv_bn_supports((8, 60, 16, 16), (128, 60, 1, 1), 1)
    assert not fused_bn.conv_bn_supports((1, 64, 8, 8), (128, 64, 1, 1), 1)


def _bottleneck_prog(fusion_mode, ci, filters):
    """Build x -> bottleneck(x) under PDTPU_CONV_BN_FUSION=fusion_mode
    (None = unfused seed graph). Same param names either way."""
    import os

    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    prev = os.environ.get("PDTPU_CONV_BN_FUSION")
    if fusion_mode is None:
        os.environ.pop("PDTPU_CONV_BN_FUSION", None)
    else:
        os.environ["PDTPU_CONV_BN_FUSION"] = fusion_mode
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            from paddle_tpu import layers
            x = layers.data("x", [ci, 8, 8])
            y = resnet.bottleneck(x, filters, 1, "blk")
        return main, startup, y
    finally:
        if prev is None:
            os.environ.pop("PDTPU_CONV_BN_FUSION", None)
        else:
            os.environ["PDTPU_CONV_BN_FUSION"] = prev


def test_fused_conv_bn_e2e_bitwise_at_model_widths():
    """End-to-end contract that makes per-model enablement safe: a resnet
    bottleneck at model widths (256->64->256) built with the fused op
    (XLA lowering) is BITWISE-identical to the unfused seed graph — the
    two programs share one scope and one startup (same param names), so
    the only variable is the lowering."""
    import paddle_tpu as fluid

    fused_main, fused_st, fy = _bottleneck_prog("xla", 256, 64)
    unf_main, _unf_st, uy = _bottleneck_prog(None, 256, 64)
    # the fused graph really did fuse: one op for the .c tail, no separate
    # add/relu on the residual path
    types_f = [op.type for op in fused_main.global_block().ops]
    types_u = [op.type for op in unf_main.global_block().ops]
    assert "fused_conv_bn" in types_f
    assert "fused_conv_bn" not in types_u

    exe = fluid.Executor(fluid.TPUPlace())
    rng = np.random.RandomState(3)
    x = rng.randn(2, 256, 8, 8).astype("float32")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(fused_st)                      # ONE init for both arms
        got_f = exe.run(fused_main, feed={"x": x}, fetch_list=[fy])[0]
        got_u = exe.run(unf_main, feed={"x": x}, fetch_list=[uy])[0]
    assert got_f.shape == (2, 256, 8, 8)
    np.testing.assert_array_max_ulp(got_f, got_u, maxulp=1)
    np.testing.assert_array_equal(got_f, got_u)
