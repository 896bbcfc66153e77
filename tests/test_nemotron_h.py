"""Nemotron-H (Mamba-2 + sparse experts + GQA) at a tiny size on the CPU, in
float32, against the plain reference the benchmark keeps
(benchmark/configs/nemotron3_nano_reference.py, which imports nothing of the
program): the scan against the literal recurrence, each kind of block and the
9-block model's loss, gradients and Adam steps, grouped-query flash attention
against plain attention, and the share test that ties a chip's share of the
experts to the uncut layer."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from benchmark.configs import nemotron3_nano_reference as ref
from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.ops import ssm_ops
from paddle_tpu.ops.activation_ops import relu2
from paddle_tpu.parallel import moe

fa = importlib.import_module("paddle_tpu.ops.pallas_kernels.flash_attention")
ssd_kernels = importlib.import_module(
    "paddle_tpu.ops.pallas_kernels.ssd_scan")


def _cfg(pattern="MEMEM*EME", experts=8, held=(2, 4), **over):
    cfg = {
        "hidden_size": 32, "hybrid_override_pattern": pattern,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
        "mamba_num_heads": 4, "mamba_head_dim": 8, "ssm_state_size": 16,
        "n_groups": 2, "conv_kernel": 4, "chunk_size": 16,
        "n_routed_experts": held[1], "n_routed_experts_published": experts,
        "experts_held": list(held), "num_experts_per_tok": 2,
        "moe_intermediate_size": 24,
        "moe_shared_expert_intermediate_size": 48,
        "routed_scaling_factor": 2.5, "norm_topk_prob": True,
        "norm_eps": 1e-5, "vocab_size": 64, "initializer_range": 0.2,
        "time_step_min": 0.001, "time_step_max": 0.1,
        "time_step_floor": 1e-4,
        "optimizer": {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.999,
                      "epsilon": 1e-8},
        "reference": {"follow_steps": 3, "head_rows": 16}}
    cfg.update(over)
    return cfg


def _model_cfg(cfg):
    return nh.NemotronHConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        pattern=cfg["hybrid_override_pattern"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mamba_num_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"],
        ssm_state_size=cfg["ssm_state_size"], n_groups=cfg["n_groups"],
        chunk_size=cfg["chunk_size"],
        n_routed_experts=cfg["n_routed_experts_published"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_intermediate_size=cfg["moe_shared_expert_intermediate_size"],
        experts_held=tuple(cfg["experts_held"]),
        initializer_range=cfg["initializer_range"])


def _batches(cfg, n, b=2, t=64, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        ids = rng.randint(0, cfg["vocab_size"], (b, t + 1)).astype("int32")
        out.append({"ids": ids[:, :-1].copy(),
                    "labels": ids[:, 1:, None].copy()})
    return out


# ---------------------------------------------------------------------------
# the scan
# ---------------------------------------------------------------------------

def _scan_inputs(t, h, p, g, n, seed=0, dtype=jnp.float32):
    """x, dt, A, B, C, D and a cotangent."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(ks[0], (2, t, h, p)).astype(dtype),
            jax.nn.softplus(jax.random.normal(ks[1], (2, t, h))),
            -jnp.exp(0.5 * jax.random.normal(ks[2], (h,))),
            jax.random.normal(ks[3], (2, t, g, n)).astype(dtype),
            jax.random.normal(ks[4], (2, t, g, n)).astype(dtype),
            jax.random.normal(ks[6], (h,)),
            jax.random.normal(ks[5], (2, t, h, p)))


def _literal(x, dt, a, b, c, d):
    """The recurrence position by position, in float32, with the skip."""
    x, b, c = (z.astype(jnp.float32) for z in (x, b, c))
    scan = jax.vmap(ref.ssd_recurrence, in_axes=(0, 0, None, 0, 0))
    return scan(x, dt, a, b, c) + d[:, None] * x


def _grads(f, args, w):
    return jax.grad(lambda *z: jnp.sum(f(*z).astype(jnp.float32) * w),
                    tuple(range(6)))(*args)


@pytest.fixture
def kernels_on(monkeypatch):
    """The scan's Pallas kernels, through the interpreter."""
    monkeypatch.setattr(ssd_kernels, "FORCE_PALLAS_INTERPRET", True)


# the einsum form's three small shapes (the rule does not take them), then
# the smallest the kernels do take: chunk 128, N 128, T of three chunks,
# R·P 128 and 512, one and two groups, float32 and bf16 inputs
_EINSUM_SHAPES = [(64, 4, 8, 2, 16, 16), (48, 6, 4, 3, 8, 8),
                  (32, 2, 8, 1, 4, 32)]
_KERNEL_SHAPES = [(384, 2, 64, 1, 128, 128), (384, 16, 64, 2, 128, 128),
                  (384, 4, 64, 2, 128, 128), (384, 8, 64, 1, 128, 128)]


@pytest.mark.parametrize("path,dtype,shape", [
    *(("einsum", "float32", s) for s in _EINSUM_SHAPES),
    *(("pallas", "float32", s) for s in _KERNEL_SHAPES),
    *(("pallas", "bfloat16", s) for s in _KERNEL_SHAPES)],
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else v)
def test_ssd_scan_matches_the_literal_recurrence(kernels_on, path, dtype,
                                                 shape):
    t, h, p, g, n, chunk = shape
    *args, w = _scan_inputs(t, h, p, g, n, dtype=jnp.dtype(dtype))
    assert ssm_ops.scan_path(t, h, p, g, n, chunk) == path
    got = ssm_ops.ssd_scan(*args, chunk)
    want = _literal(*args)
    assert got.dtype == args[0].dtype
    # float32: PR 26's tolerances at its small shapes; at the kernels'
    # (sums over a state of 128 and 384 positions) the literal recurrence's
    # own round-off is larger, and the einsum form reads the same gaps there
    # (values 2.3e-6 to 3.3e-6, dA 0.7e-5 to 3.5e-5). bf16: what rounding the
    # weights, the state's update and the output to 8 bits allows
    tol, gtol = {("einsum", "float32"): (2e-6, 5e-5),
                 ("pallas", "float32"): (1e-5, 2e-4),
                 ("pallas", "bfloat16"): (1e-2, 2e-2)}[path, dtype]
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < tol * scale
    g_got = _grads(lambda *z: ssm_ops.ssd_scan(*z, chunk), args, w)
    g_want = _grads(_literal, args, w)
    for u, v in zip(g_got, g_want):
        assert u.dtype == v.dtype and u.shape == v.shape
        gap = jnp.abs(u.astype(jnp.float32) - v.astype(jnp.float32)).max()
        assert float(gap) < gtol * float(jnp.abs(v).max())


@pytest.mark.parametrize("shape", [
    (384, 16, 64, 2, 128), (256, 1, 128, 1, 128), (256, 8, 32, 2, 256)],
    ids=lambda s: "x".join(map(str, s)))
def test_the_kernels_and_the_einsum_form_agree_to_round_off(kernels_on,
                                                            shape):
    """Float32 inputs: the two forms differ by the order of their sums
    alone. The one that shows is the log-decays' running sum along a chunk
    (the kernels add by doubling strides, `cumsum` in order): the sums reach
    -100 here, a last-place difference there is 1e-5 in the exponent, and
    the decays carry it. Heads of 64 (two to a 128-lane tile), 128 (one) and
    32 (four, state 256)."""
    t, h, p, g, n = shape
    *args, w = _scan_inputs(t, h, p, g, n, seed=1)
    assert ssm_ops.scan_path(t, h, p, g, n, 128) == "pallas"
    got = ssd_kernels.ssd_scan(*args, 128)
    want = ssm_ops.ssd_scan_einsum(*args, 128)
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(
        jnp.abs(want).max())
    g_got = _grads(lambda *z: ssd_kernels.ssd_scan(*z, 128), args, w)
    g_want = _grads(lambda *z: ssm_ops.ssd_scan_einsum(*z, 128), args, w)
    for u, v in zip(g_got, g_want):       # dA and dD sum B·T·P terms
        assert float(jnp.abs(u - v).max()) < 1e-4 * float(jnp.abs(v).max())


def _lowered(path):
    from paddle_tpu.observability import get_registry
    return sum(s["value"] for s in get_registry().series()
               if s["name"] == "ops/ssd_scan_lowered"
               and s["labels"].get("path") == path)


def _run_scan_op(t, h, p, g, n, chunk=128):
    from paddle_tpu.ops import eager
    eager._jit_cache.clear()      # lower the op anew: the counter counts that
    x, dt, a, b, c, d, _ = _scan_inputs(t, h, p, g, n)
    return _eager("ssd_scan", {
        "X": [x.reshape(2, t, h * p)], "Dt": [dt], "ALog": [jnp.log(-a)],
        "B": [b.reshape(2, t, g * n)], "C": [c.reshape(2, t, g * n)],
        "D": [d], "DtBias": [jnp.zeros((h,))]},
        {"num_heads": h, "n_groups": g, "chunk": chunk})["Out"][0]


def test_the_shapes_and_the_backend_choose_the_scan_s_form(monkeypatch):
    """Off the TPU: the einsum form, whatever the shapes. Where the kernels
    may run (a TPU; here the interpreter): the kernels for the shapes they
    take, the einsum form for the rest; the counter says which."""
    taken, not_taken = (256, 2, 64, 1, 128), (256, 2, 64, 1, 64)
    assert not ssd_kernels._on_tpu()
    for shape in (taken, not_taken):
        before = _lowered("einsum"), _lowered("pallas")
        _run_scan_op(*shape)
        assert (_lowered("einsum"), _lowered("pallas")) == (
            before[0] + 1, before[1])
    monkeypatch.setattr(ssd_kernels, "FORCE_PALLAS_INTERPRET", True)
    before = _lowered("einsum"), _lowered("pallas")
    got = _run_scan_op(*taken)
    assert (_lowered("einsum"), _lowered("pallas")) == (
        before[0], before[1] + 1)
    _run_scan_op(*not_taken)                  # N 64: not a vreg's width
    assert (_lowered("einsum"), _lowered("pallas")) == (
        before[0] + 1, before[1] + 1)
    for bad in [(250, 2, 64, 1, 128, 125), (256, 3, 64, 1, 128, 128),
                (256, 2, 96, 1, 128, 128), (256, 2, 64, 1, 128, 64)]:
        assert not ssd_kernels.supports(*bad), bad
    # the op gives the same numbers by either form
    monkeypatch.setattr(ssd_kernels, "FORCE_PALLAS_INTERPRET", False)
    want = _run_scan_op(*taken)
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(
        jnp.abs(want).max())


def test_the_reference_s_chunked_scan_is_the_literal_recurrence():
    x, dt, a, b, c, _, w = (z[0] if z.ndim > 1 else z
                            for z in _scan_inputs(64, 4, 8, 2, 16, seed=3))
    want = ref.ssd_recurrence(x, dt, a, b, c)
    got = ref.ssd_chunked(x, dt, a, b, c, 16)
    assert float(jnp.abs(got - want).max()) < 2e-6 * float(
        jnp.abs(want).max())
    g_got = jax.grad(lambda *z: jnp.sum(ref.ssd_chunked(*z, 16) * w),
                     (0, 1, 2, 3, 4))(x, dt, a, b, c)
    g_want = jax.grad(lambda *z: jnp.sum(ref.ssd_recurrence(*z) * w),
                      (0, 1, 2, 3, 4))(x, dt, a, b, c)
    for u, v in zip(g_got, g_want):
        assert float(jnp.abs(u - v).max()) < 5e-5 * float(jnp.abs(v).max())


def test_the_scan_never_forms_a_sequence_by_sequence_array():
    """T = 512 in chunks of 16: the largest intermediate is [chunk, chunk] a
    chunk and head, never [T, T]."""
    *args, _ = _scan_inputs(512, 2, 4, 1, 4)
    jaxpr = jax.make_jaxpr(lambda *z: jax.grad(
        lambda *y: jnp.sum(ssm_ops.ssd_scan(*y, 16)))(*z))(*args)
    sizes = [int(np.prod(v.aval.shape)) for eqn in jaxpr.eqns
             for v in eqn.outvars if hasattr(v.aval, "shape")]
    assert max(sizes) < 2 * 512 * 512      # batch 2: far under [T, T] a head


# ---------------------------------------------------------------------------
# the small ops against the reference's functions
# ---------------------------------------------------------------------------

def _eager(op_type, inputs, attrs):
    import paddle_tpu.ops as ops
    return ops.eager_call(op_type, {k: [jnp.asarray(v) for v in vs]
                                    for k, vs in inputs.items()}, attrs)


def test_rms_norm_plain_and_gated_by_group():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (5, 32))
    z = jax.random.normal(ks[1], (5, 32))
    w = 1.0 + 0.1 * jax.random.normal(ks[2], (32,))
    out = _eager("rms_norm", {"X": [x], "Scale": [w]}, {"epsilon": 1e-5})
    np.testing.assert_allclose(out["Out"][0], ref.rms_norm(x, w, 1e-5),
                               rtol=1e-6, atol=1e-6)
    out = _eager("rms_norm", {"X": [x], "Scale": [w], "Gate": [z]},
                 {"epsilon": 1e-5, "group_size": 8})
    y = x * jax.nn.silu(z)
    want = ref.rms_norm(y.reshape(5, 4, 8), 1.0, 1e-5).reshape(5, 32) * w
    np.testing.assert_allclose(out["Out"][0], want, rtol=1e-6, atol=1e-6)
    # bf16 in, bf16 out, float32 inside
    lo = _eager("rms_norm", {"X": [x.astype(jnp.bfloat16)], "Scale": [w]},
                {"epsilon": 1e-5})["Out"][0]
    assert lo.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(lo, np.float32),
                               ref.rms_norm(x, w, 1e-5), atol=3e-2)


def test_causal_conv1d_and_relu2():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (2, 12, 6))
    w = jax.random.normal(ks[1], (6, 4))
    b = jax.random.normal(ks[2], (6,))
    out = _eager("causal_conv1d", {"X": [x], "Filter": [w], "Bias": [b]},
                 {"activation": "silu"})["Out"][0]
    want = jnp.stack([jax.nn.silu(ref.causal_conv1d(row, w, b)) for row in x])
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)
    # causal: a later input moves no earlier output
    x2 = x.at[:, 7:].add(1.0)
    out2 = _eager("causal_conv1d", {"X": [x2], "Filter": [w], "Bias": [b]},
                  {"activation": "silu"})["Out"][0]
    assert np.array_equal(np.asarray(out[:, :7]), np.asarray(out2[:, :7]))
    v = jnp.asarray([-2.0, -0.0, 0.5, 3.0])
    assert np.array_equal(np.asarray(_eager("relu2", {"X": [v]}, {})
                                     ["Out"][0]), [0.0, 0.0, 0.25, 9.0])
    assert np.array_equal(np.asarray(relu2(v)), np.asarray(ref.relu2(v)))


# ---------------------------------------------------------------------------
# grouped-query flash attention
# ---------------------------------------------------------------------------

def _plain_attention(q, k, v, causal):
    h, t, d = q.shape[1], q.shape[2], q.shape[3]
    g = h // k.shape[1]
    k, v = jnp.repeat(k, g, 1), jnp.repeat(v, g, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["blockwise-jax", "pallas-interpret"])
@pytest.mark.parametrize("hq,hkv,causal", [(4, 2, True), (8, 1, True),
                                           (4, 4, False), (6, 2, False)])
def test_gqa_flash_attention_matches_plain_attention(monkeypatch, interpret,
                                                     hq, hkv, causal):
    monkeypatch.setattr(fa, "FORCE_PALLAS_INTERPRET", interpret)
    ks = jax.random.split(jax.random.PRNGKey(hq * 10 + hkv), 4)
    q = jax.random.normal(ks[0], (2, hq, 256, 64))
    k = jax.random.normal(ks[1], (2, hkv, 256, 64))
    v = jax.random.normal(ks[2], (2, hkv, 256, 64))
    w = jax.random.normal(ks[3], (2, hq, 256, 64))
    got = fa.flash_attention(q, k, v, causal=causal)
    want = _plain_attention(q, k, v, causal)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    g_got = jax.grad(lambda *z: jnp.sum(fa.flash_attention(
        *z, causal=causal) * w), (0, 1, 2))(q, k, v)
    g_want = jax.grad(lambda *z: jnp.sum(_plain_attention(
        *z, causal) * w), (0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["blockwise-jax", "pallas-interpret"])
def test_grouped_heads_compute_what_copied_heads_do(monkeypatch, interpret):
    """Sharing a key/value head through the index maps gives, bit for bit,
    what the ungrouped kernels give on explicit copies of it; and with one
    key/value head a query head (`kv_group` 1) the index map is the identity
    the kernels always had."""
    monkeypatch.setattr(fa, "FORCE_PALLAS_INTERPRET", interpret)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 4, 1024, 64))
    k = jax.random.normal(ks[1], (1, 2, 1024, 64))
    v = jax.random.normal(ks[2], (1, 2, 1024, 64))
    grouped = fa.flash_attention(q, k, v, causal=True)
    copied = fa.flash_attention(q, jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1),
                                causal=True)
    assert np.array_equal(np.asarray(grouped), np.asarray(copied))
    assert fa._kv_row(1)(7) == 7 and fa._kv_row(16)(35) == 2
    with pytest.raises(ValueError, match="must divide"):
        fa.flash_attention(q, k[:, :1].repeat(3, 1), v[:, :1].repeat(3, 1))


def test_packed_layer_takes_fewer_key_value_heads():
    from paddle_tpu import layers
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q = layers.data("q", [64, 4 * 16])
        k = layers.data("k", [64, 2 * 16])
        v = layers.data("v", [64, 2 * 16])
        out = layers.flash_attention(q, k, v, causal=True, num_heads=4,
                                     num_kv_heads=2)
    rng = np.random.RandomState(0)
    feed = {n: rng.randn(2, 64, w).astype("float32")
            for n, w in (("q", 64), ("k", 32), ("v", 32))}
    exe = fluid.Executor(fluid.TPUPlace())
    (got,) = exe.run(main, feed=feed, fetch_list=[out])

    def heads(a, n):
        return jnp.asarray(a).reshape(2, 64, n, 16).transpose(0, 2, 1, 3)

    want = _plain_attention(heads(feed["q"], 4), heads(feed["k"], 2),
                            heads(feed["v"], 2), True)
    np.testing.assert_allclose(
        got, want.transpose(0, 2, 1, 3).reshape(2, 64, 64),
        rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# blocks and the model against the reference
# ---------------------------------------------------------------------------

def _program(cfg, b=2, t=64, lr=None):
    opt = (lambda: fluid.optimizer.Adam(lr)) if lr else None
    with fluid.unique_name.guard():
        main, startup, _, loss, counters = nh.build_pretrain_program(
            _model_cfg(cfg), b, t, opt)
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    return main, loss, counters, exe, scope


def _reference_loss_and_grads(cfg, weights, batch):
    def total(p):
        return sum(ref.sum_loss(p, jnp.asarray(batch["ids"][r]),
                                jnp.asarray(batch["labels"][r, :, 0]), cfg)
                   for r in range(batch["ids"].shape[0])) / batch["ids"].size
    return jax.value_and_grad(total)(weights)


# the smallest mixer the scan's kernels take: 4 heads of 64 in 2 groups
# (R·P 128), state 128, chunks of 128
_KERNEL_MIXER = dict(mamba_num_heads=4, mamba_head_dim=64, n_groups=2,
                     ssm_state_size=128, chunk_size=128)


@pytest.mark.parametrize("pattern,kernels", [
    ("M", False), ("E", False), ("*", False), ("M", True)],
    ids=["M", "E", "*", "M-kernels"])
def test_one_block_of_each_kind_against_the_reference(monkeypatch, pattern,
                                                      kernels):
    cfg, t = _cfg(pattern), 64
    if kernels:
        monkeypatch.setattr(ssd_kernels, "FORCE_PALLAS_INTERPRET", True)
        cfg, t = _cfg(pattern, **_KERNEL_MIXER), 256
    lowered = _lowered("pallas")
    main, loss, _, exe, scope = _program(cfg, t=t)
    weights = ref.make_weights(cfg, 5)
    params = main.global_block().all_parameters()
    assert sorted(p.name for p in params) == sorted(weights)
    assert ([p.name for p in params if not p.trainable]
            == [k for k in weights if k.endswith(ref.FROZEN)])
    for k, v in weights.items():
        scope.set_var(k, jnp.copy(v))
    (batch,) = _batches(cfg, 1, t=t)
    want_loss, want_grads = _reference_loss_and_grads(cfg, weights, batch)
    # the block's gradients: the program's backward, fetched by name
    main_b, loss_b, _, exe_b, scope_b = _program(cfg, t=t, lr=1e-3)
    for k, v in weights.items():
        scope_b.set_var(k, jnp.copy(v))
    (got_loss,) = exe.run(main, feed=batch, fetch_list=[loss], scope=scope)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=2e-6)
    exe_b.run(main_b, feed=batch, fetch_list=[loss_b], scope=scope_b)
    for k in weights:
        if k.endswith(ref.FROZEN):
            continue
        got = scope_b.find_var(f"{k}_AdamOptimizer_moment1") / 0.1
        want = want_grads[k]
        scale = max(float(jnp.abs(want).max()), 1e-6)
        assert float(jnp.abs(got - want).max()) < 2e-4 * scale, k
    assert (_lowered("pallas") > lowered) == kernels


def test_the_nine_block_model_follows_the_reference():
    cfg = _cfg("MEMEM*EME")
    main, loss, counters, exe, scope = _program(cfg, lr=1e-3)
    batches = _batches(cfg, 3, seed=4)
    # with the routers' correction biases away from the zero they start at:
    # the program has to choose by score + bias, and leave the bias alone
    weights = ref.make_weights(cfg, 11)
    rng = np.random.default_rng(5)
    biases = {k: rng.normal(0, 0.05, v.shape).astype("float32")
              for k, v in weights.items() if k.endswith(ref.FROZEN)}
    assert len(biases) == 4
    weights.update({k: jnp.asarray(b) for k, b in biases.items()})
    for k, v in weights.items():
        scope.set_var(k, jnp.copy(v))
    want = ref.follow(cfg, weights, batches)
    trained = [k for k in weights if not k.endswith(ref.FROZEN)]
    assert sorted(want["grad_norms"]) == sorted(trained)
    fetch = [loss] + [v for _, t, p in counters for v in (t, p)]
    losses = []
    for i, batch in enumerate(batches):
        out = exe.run(main, feed=batch, fetch_list=fetch, scope=scope)
        losses.append(float(out[0]))
        if i == 0:
            grad_norms = {k: float(jnp.linalg.norm(scope.find_var(
                f"{k}_AdamOptimizer_moment1"))) / 0.1 for k in trained}
    np.testing.assert_allclose(losses, want["losses"], rtol=2e-6)
    for k, b in biases.items():       # no optimizer touched them
        assert np.array_equal(np.asarray(scope.find_var(k)), b)
    for k in trained:
        assert grad_norms[k] == pytest.approx(want["grad_norms"][k],
                                              rel=1e-4, abs=1e-7), k
        moved = float(jnp.linalg.norm(scope.find_var(k) - weights[k]))
        assert moved == pytest.approx(want["update_norms"][k], rel=2e-3), k
    # four expert blocks, each with its counters: nothing dropped
    assert len(counters) == 4
    for tokens, pairs in zip(out[1::2], out[2::2]):
        assert tokens.shape == (4,) and int(pairs) == tokens.sum()
    nh.record_moe_counters(counters, out[1:], 2 * 64, 2)
    from paddle_tpu.observability import get_registry
    series = {(s["name"], s["labels"].get("block")): s["value"]
              for s in get_registry().series() if s["name"].startswith("moe/")
              and "expert" not in s["labels"]}
    assert series[("moe/dropped", "blk1")] == 0
    assert series[("moe/pairs_routed", "blk1")] == 2 * 64 * 2
    assert series[("moe/pairs_held", "blk1")] == int(out[2])


def test_the_builder_reads_the_pattern_and_counts_its_parameters():
    cfg = _cfg("MEMEM*EME")
    mcfg = _model_cfg(cfg)
    n = sum(int(np.prod(shape)) for _, shape, _ in ref.weight_specs(cfg))
    assert nh.param_count(mcfg) == n
    main, *_ = nh.build_pretrain_program(mcfg, 2, 64)
    units = {op.attrs.get("__unit__") for op in main.global_block().ops}
    assert {"blk0.M/mamba/ssd", "blk1.E/moe", "blk5.A/attn",
            "blk1.E/moe/shared", "lm_head", "loss"} <= units
    # every block is made again in the backward pass, all but what its mixer
    # keeps: the in-projection's result; the shared expert's first product,
    # the routing and its plan; the q/k/v product and the kernel's outputs
    assert main.remat_policy == "full"
    keep = main.remat_keep
    assert sorted(keep) == sorted(
        nh.unit_name(i, c) for i, c in enumerate(mcfg.pattern))
    produced_in = {n: op.attrs["__unit__"] for op in main.global_block().ops
                   for n in op.output_names()}
    for block, names in keep.items():
        ops = [produced_in[n] for n in names if n in produced_in]
        own = [n for n in names if n not in produced_in]
        if block.endswith(".M"):
            assert (ops, own) == ([f"{block}/mamba/in_proj"], [])
        elif block.endswith(".E"):
            assert (ops, own) == ([f"{block}/moe/shared"], list(moe.KEPT))
        else:
            assert (ops, own) == ([f"{block}/attn"], list(fa.KEPT))
    # the published model: 52 blocks, 31.6B parameters
    full = nh.NemotronHConfig()
    assert (full.pattern.count("M"), full.pattern.count("E"),
            full.pattern.count("*")) == (23, 23, 6)
    assert nh.param_count(full) == pytest.approx(31.58e9, rel=1e-3)
    with pytest.raises(ValueError, match="unknown block kind"):
        nh.build_pretrain_program(
            nh.NemotronHConfig(pattern="MX", vocab_size=8), 1, 128)


@pytest.mark.parametrize("policy", ["kept", "full"])
@pytest.mark.parametrize("kernels", [False, True], ids=["einsum", "kernels"])
def test_remat_blocks_give_the_same_step(monkeypatch, kernels, policy):
    """The blocks are recomputed in the backward pass by the builder's own
    request, all but the values it keeps ("kept") or all of them ("full":
    nothing kept); without the request the step computes the same numbers
    (with the scan's kernels too, whose forward then runs twice)."""
    cfg, t = _cfg("ME*"), 64
    if kernels:
        monkeypatch.setattr(ssd_kernels, "FORCE_PALLAS_INTERPRET", True)
        cfg, t = _cfg("ME*", **_KERNEL_MIXER), 256
    lowered = _lowered("pallas")
    (batch,) = _batches(cfg, 1, t=t)
    weights = ref.make_weights(cfg, 2)
    results = []
    for remat in (True, False):
        main, loss, _, exe, scope = _program(cfg, t=t, lr=1e-3)
        assert main.remat_policy == "full" and len(main.remat_keep) == 3
        if not remat:
            main.remat_policy = None
        elif policy == "full":
            main.remat_keep = {}
        for k, v in weights.items():
            scope.set_var(k, jnp.copy(v))
        (got,) = exe.run(main, feed=batch, fetch_list=[loss], scope=scope)
        results.append((float(got), {k: np.asarray(scope.find_var(k))
                                     for k in weights}))
    assert results[0][0] == pytest.approx(results[1][0], rel=1e-6)
    for k in weights:
        np.testing.assert_allclose(results[0][1][k], results[1][1][k],
                                   rtol=1e-4, atol=1e-6)
    assert (_lowered("pallas") > lowered) == kernels


# ---------------------------------------------------------------------------
# what a block keeps
# ---------------------------------------------------------------------------

def _count_primitives(jaxpr, counts):
    """Every equation of `jaxpr` and of the jaxprs inside it, by primitive; a
    product also by its result's shape, a kernel call also by its kernel."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        counts[name] += 1
        if name == "dot_general":
            counts[name, tuple(eqn.outvars[0].aval.shape)] += 1
        elif name == "pallas_call":
            counts[name, eqn.params.get("name")
                   or eqn.params["jaxpr"].debug_info.func_name] += 1
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _count_primitives(sub, counts)
    return counts


def _step_primitives(cfg, t, edit=None):
    """The primitives of the whole training step (forward, backward and
    SGD) of `cfg`'s program, its `remat_keep` first changed by `edit`."""
    import collections
    from paddle_tpu.core.executor import convert_feed_value
    with fluid.unique_name.guard():
        main, startup, _, loss, _ = nh.build_pretrain_program(
            _model_cfg(cfg), 2, t, lambda: fluid.optimizer.SGD(0.1))
    if edit:
        edit(main.remat_keep)
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    ids = np.zeros((2, t), "int32")
    feed = {k: convert_feed_value(main.global_block(), k, v)
            for k, v in {"ids": ids, "labels": ids[:, :, None]}.items()}
    names = sorted(v.name for v in main.list_vars()
                   if v.persistable and scope.has_var(v.name))
    step = exe._build(main, sorted(feed), [loss.name], names, names)
    jaxpr = jax.make_jaxpr(step._step)(
        {n: scope.find_var(n) for n in names}, feed, jax.random.key(0))
    return _count_primitives(jaxpr.jaxpr, collections.Counter())


@pytest.fixture
def all_kernels_on(monkeypatch):
    """The scan's and attention's Pallas kernels, through the interpreter."""
    monkeypatch.setattr(ssd_kernels, "FORCE_PALLAS_INTERPRET", True)
    monkeypatch.setattr(fa, "FORCE_PALLAS_INTERPRET", True)


# a mixer the scan's kernels take and heads the attention kernels take
_KERNEL_BLOCKS = dict(_KERNEL_MIXER, head_dim=128)


def test_a_block_s_backward_does_not_remake_what_it_keeps(all_kernels_on):
    """`ME*`, the step's jaxpr with the builder's kept set against the one
    that keeps nothing: the backward pass holds no sort, no second forward
    call of the attention kernel, and one product fewer of each kept shape
    (in-projection, q/k/v, the shared expert's first, the router's). The
    scan's forward kernel is still called twice: nothing of it is kept."""
    cfg, t = _cfg("ME*", **_KERNEL_BLOCKS), 256
    full = _step_primitives(cfg, t, edit=dict.clear)
    kept = _step_primitives(cfg, t)
    mcfg, n = _model_cfg(cfg), 2 * t
    assert (full["sort"], kept["sort"]) == (2, 1)
    assert (full["top_k"], kept["top_k"]) == (2, 1)
    # attention: forward, remade forward, one backward -> forward, backward
    assert (full["pallas_call", "kernel"],
            kept["pallas_call", "kernel"]) == (2, 1)
    for call in ("dkv_kernel", "ssd_scan_bwd"):
        assert full["pallas_call", call] == kept["pallas_call", call] == 1
    assert full["pallas_call", "dq_kernel"] == 0   # dq comes with dk and dv
    assert (full["pallas_call", "ssd_scan_fwd"]
            == kept["pallas_call", "ssd_scan_fwd"] == 2)
    for width in (mcfg.d_inner + mcfg.conv_dim + mcfg.mamba_num_heads,
                  (mcfg.num_heads + 2 * mcfg.num_kv_heads) * mcfg.head_dim,
                  mcfg.shared_intermediate_size, mcfg.n_routed_experts):
        product = "dot_general", (n, width)
        assert kept[product] == full[product] - 1 >= 1, width
    # and no other product went: those four, and QK^T and PV inside the
    # remade attention forward's own jaxpr
    assert kept["dot_general"] == full["dot_general"] - 4 - 2


def test_the_kernel_s_out_without_its_lse_keeps_nothing(all_kernels_on):
    """The backward kernels read `out` and `lse`, the values the forward
    rule made: with one of the two names missing the forward call is made
    again whole, whatever else is kept."""
    cfg, t = _cfg("*", **_KERNEL_BLOCKS), 256

    def drop(name):
        return lambda keep: keep["blk0.A"].remove(name)

    assert _step_primitives(cfg, t)["pallas_call", "kernel"] == 1
    for name in fa.KEPT:
        assert _step_primitives(cfg, t, drop(name))[
            "pallas_call", "kernel"] == 2, name


def _kept_gauges(what, units=("blk0.M", "blk1.E", "blk2.A")):
    """As the last step traced left them (the registry is the process's)."""
    from paddle_tpu.observability import get_registry
    return {s["labels"]["unit"]: int(s["value"])
            for s in get_registry().series()
            if s["name"] == f"remat/kept_{what}"
            and s["labels"]["unit"] in units}


def test_the_gauges_say_what_each_block_keeps():
    """`remat/kept_bytes{unit}` and `remat/kept_values{unit}`, written when
    the step is traced, from the shapes: float32 here. 0 where the policy
    is "full" and nothing is named."""
    cfg, t = _cfg("ME*"), 64
    mcfg, n, f32 = _model_cfg(cfg), 2 * 64, 4
    (batch,) = _batches(cfg, 1, t=t)
    main, loss, _, exe, scope = _program(cfg, t=t, lr=1e-3)
    exe.run(main, feed=batch, fetch_list=[loss], scope=scope)
    k, held = mcfg.num_experts_per_tok, mcfg.held()[1]
    tiles = -(-n * k // moe.TILE) + held
    want = {
        "blk0.M": n * (mcfg.d_inner + mcfg.conv_dim
                       + mcfg.mamba_num_heads) * f32,
        "blk1.E": (n * mcfg.shared_intermediate_size * f32     # shared up
                   + n * mcfg.n_routed_experts * f32           # logits
                   + 2 * n * k * 4                             # idx, weight
                   + n * k * 4 + 3 * tiles * 4 + 4),           # the plan
        "blk2.A": (n * (mcfg.num_heads + 2 * mcfg.num_kv_heads)
                   * mcfg.head_dim * f32                       # qkv
                   + n * mcfg.num_heads * mcfg.head_dim * f32  # out
                   + n * mcfg.num_heads * 4)}                  # lse
    assert _kept_gauges("bytes") == want
    assert _kept_gauges("values") == {"blk0.M": 1, "blk1.E": 9, "blk2.A": 3}
    main, loss, _, exe, scope = _program(cfg, t=t, lr=1e-3)
    main.remat_keep = {}
    exe.run(main, feed=batch, fetch_list=[loss], scope=scope)
    assert _kept_gauges("bytes") == dict.fromkeys(want, 0)
    assert _kept_gauges("values") == dict.fromkeys(want, 0)


def test_what_is_weighed_is_what_the_policy_saves():
    """`core.remat.weighing` counts the named values while a block is
    traced; jax's own list of saved residuals says what the policy of those
    names really keeps: the same values, the same bytes."""
    from paddle_tpu.core import remat
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (64, 16))
    wq, gate = (jax.random.normal(k, (16, w)) for k, w in zip(ks[1:], (48, 8)))
    w1 = jax.random.normal(ks[3], (4, 16, 8))
    w2 = jax.random.normal(ks[4], (4, 8, 16))
    names = fa.KEPT + moe.KEPT + ("qkv",)

    def block(x, wq, gate, w1, w2):
        qkv = remat.kept(x @ wq, "qkv")
        q, k, v = (z.reshape(1, 64, 2, 8).transpose(0, 2, 1, 3)
                   for z in jnp.split(qkv, 3, axis=1))
        a = fa.flash_attention(q, k, v, causal=True)
        y = moe.moe_ffn(x, gate, w1, None, w2, None, k=2, act=relu2,
                        experts_held=(2, 4), scoring="sigmoid").y
        return jnp.sum(a) + jnp.sum(y)

    args = (x, wq, gate, w1, w2)
    wrapped = jax.checkpoint(
        block, policy=jax.checkpoint_policies.save_only_these_names(*names))
    with remat.weighing(names) as weighed:
        jax.vjp(wrapped, *args)
    # what `jax.ad_checkpoint.print_saved_residuals` prints, as a list: the
    # arguments, the named values (a float one as the `reduce_precision`
    # that jax puts behind it) and one [N, k] index array that a jitted
    # `take_along_axis` derives from the kept `idx`
    from jax._src.ad_checkpoint import saved_residuals
    saved = [aval for aval, why in saved_residuals(wrapped, *args)
             if not why.startswith(("from the argument",
                                    "output of jitted function"))]
    assert weighed.values == len(saved) == len(names)
    assert weighed.bytes == sum(
        int(np.prod(a.shape)) * a.dtype.itemsize for a in saved)
    with remat.weighing(()) as nothing:
        jax.vjp(jax.checkpoint(block), *args)
    assert (nothing.values, nothing.bytes) == (0, 0)


@pytest.mark.parametrize("given", ["names", "policy", "none"])
def test_the_caller_s_remat_strategy_overrides_the_program_s(given):
    """What `BuildStrategy` says wins over what the builder declared: its
    names replace the kept set under the program's own policy, its "full"
    with no names keeps nothing (the way out for a batch that needs every
    byte), its "none" leaves no remat block."""
    cfg = _cfg("ME")
    main, loss, _, exe, scope = _program(cfg, lr=1e-3)
    (zxbcdt,) = main.remat_keep["blk0.M"]
    bs = fluid.BuildStrategy()
    if given == "names":
        bs.remat_saveable_names = [zxbcdt]
    else:
        bs.remat_policy = {"policy": "full", "none": "none"}[given]
    cp = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, build_strategy=bs, places=jax.devices()[:1])
    spec = cp._remat_spec()
    own = fluid.CompiledProgram(main)._remat_spec()
    assert own.names_for("blk1.E") == tuple(main.remat_keep["blk1.E"])
    assert (spec.unit_policy is None) == (given == "none")
    kept = {"names": (zxbcdt,), "policy": (), "none": ()}[given]
    assert spec.names_for("blk0.M") == spec.names_for("blk1.E") == kept
    assert spec.token != own.token
    (batch,) = _batches(cfg, 1)
    exe.run(cp, feed=batch, fetch_list=[loss], scope=scope)
    if given != "none":
        assert _kept_gauges("values")["blk0.M"] == len(kept)
        assert _kept_gauges("bytes")["blk1.E"] == 0


# ---------------------------------------------------------------------------
# a chip's share of the experts
# ---------------------------------------------------------------------------

def test_sixteen_shares_and_the_shared_expert_once_give_the_uncut_layer():
    """32 experts over 16 chips, 2 a chip: the routed parts that the 16
    shares give, through the program's layer, plus the shared expert counted
    once, equal the reference's uncut layer; so do the reference's own
    shares."""
    cfg = _cfg("E", experts=32, held=(0, 32), num_experts_per_tok=6)
    weights = ref.make_weights(cfg, 9)
    x = jax.random.normal(jax.random.PRNGKey(1), (96, cfg["hidden_size"]))
    uncut = ref.moe_mixer(x, weights, "blk0", cfg)
    gate, w1, w2 = (weights[f"blk0.moe.{n}"] for n in ("gate", "w1", "w2"))
    shared = ref.shared_expert(x, weights, "blk0")
    program_sum, reference_sum, pairs = shared, shared, 0
    for chip in range(16):
        held = (2 * chip, 2)
        sl = slice(held[0], held[0] + 2)
        out = moe.moe_ffn(x, gate, w1[sl], None, w2[sl], None, k=6,
                          act=relu2, experts_held=held, scoring="sigmoid",
                          correction_bias=jnp.zeros((32,)),
                          routed_scaling=cfg["routed_scaling_factor"])
        program_sum = program_sum + out.y
        pairs += int(out.pairs_held)
        share = dict(weights, **{"blk0.moe.w1": w1[sl], "blk0.moe.w2": w2[sl]})
        reference_sum = reference_sum + ref.routed_experts(
            x, share, "blk0", cfg, held=held)
    assert pairs == 96 * 6                     # every pair on exactly one chip
    scale = float(jnp.abs(uncut).max())
    assert float(jnp.abs(program_sum - uncut).max()) < 1e-5 * scale
    assert float(jnp.abs(reference_sum - uncut).max()) < 1e-5 * scale


def test_all_tokens_on_one_held_expert_are_all_computed():
    cfg = _cfg("E")
    main, loss, counters, exe, scope = _program(cfg, t=128)
    # a router that sends every token to experts 2 and 3, both held
    gate = np.zeros((cfg["hidden_size"], 8), "float32")
    scope.set_var("blk0.norm.w", jnp.ones((cfg["hidden_size"],)))
    gate[:, 2], gate[:, 3] = 4.0, 3.0
    scope.set_var("blk0.moe.gate", jnp.asarray(gate))
    scope.set_var("embed.w", jnp.abs(scope.find_var("embed.w")) + 0.5)
    (batch,) = _batches(cfg, 1, t=128)
    (_, tokens, pairs) = counters[0]
    got = exe.run(main, feed=batch, fetch_list=[loss, tokens, pairs],
                  scope=scope)
    assert np.array_equal(got[1], [256, 256, 0, 0])
    assert int(got[2]) == 2 * 256 and np.isfinite(got[0])
