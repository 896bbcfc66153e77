"""Ouro (a looped decoder: one stack of layers run several times over the
same weights, a learned exit gate) at a tiny size on the CPU, in float32,
against the plain reference the benchmark keeps
(benchmark/configs/ouro_2_6b_reference.py, which imports nothing of the
program): the program's loss, every gradient and three Adam steps; the loop
test (the looped model is an untied model whose weights are copies, and a
shared weight's gradient is the sum of the copies'); the rotary embedding and
the gated activation against their written-out formulas; the exit
distribution; a head whose rows are weighted by a learnt weight; and what
sharing a parameter by its name means."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.ops as ops
from benchmark.configs import ouro_2_6b_reference as ref
from benchmark.configs.ouro_2_6b import model_config
from paddle_tpu import layers
from paddle_tpu.models import ouro
from paddle_tpu.ops import nn_ops

B, T = 2, 32


def _cfg(**over):
    cfg = {
        "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 2,
        "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 96,
        "vocab_size": 128, "total_ut_steps": 3, "rms_norm_eps": 1e-6,
        "rope_theta": 1e6, "entropy_beta": 0.05, "initializer_range": 0.2,
        "optimizer": {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.999,
                      "epsilon": 1e-8},
        "reference": {"follow_steps": 3, "head_rows": 8},
    }
    cfg.update(over)
    return cfg


def _batches(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        ids = rng.integers(0, cfg["vocab_size"], (B, T + 1)).astype(np.int32)
        out.append({"ids": np.ascontiguousarray(ids[:, :-1]),
                    "labels": np.ascontiguousarray(ids[:, 1:, None])})
    return out


def _weights(cfg, seed=5):
    """Seeded weights with a gate that is away from the zero it starts at."""
    weights = ref.make_weights(cfg, seed)
    rng = np.random.default_rng(seed)
    weights["exit_gate.w"] = jnp.asarray(
        rng.normal(0, 0.3, (cfg["hidden_size"], 1)), jnp.float32)
    weights["exit_gate.b"] = jnp.asarray([0.3], jnp.float32)
    return weights


def _program(cfg, lr=None, layer_prefix=ouro.shared):
    mcfg = model_config(cfg)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        ids = layers.data("ids", [T], dtype="int64")
        labels = layers.data("labels", [T, 1], dtype="int64")
        loss, share, entropy = ouro.objective(
            mcfg, ouro.decoder(mcfg, ids, layer_prefix), labels, T)
        if lr:
            fluid.optimizer.Adam(lr).minimize(loss)
    main.remat_policy = "full"
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    return main, startup, (loss, share, entropy), exe, scope


def _first_gradients(cfg, weights, batch, layer_prefix=ouro.shared):
    """(loss, {leaf: gradient}) of the program: Adam's first moment after
    one step is (1 - beta1) x the gradient the optimizer was given."""
    main, _, (loss, _, _), exe, scope = _program(cfg, 1e-3, layer_prefix)
    for k, v in weights.items():
        scope.set_var(k, jnp.copy(v))
    (got,) = exe.run(main, feed=batch, fetch_list=[loss], scope=scope)
    return float(got), {k: scope.find_var(f"{k}_AdamOptimizer_moment1") / 0.1
                        for k in weights}


def _reference_loss_and_grads(cfg, weights, batch, layer_params=None):
    def total(p):
        return sum(ref.sum_loss(p, jnp.asarray(batch["ids"][r]),
                                jnp.asarray(batch["labels"][r, :, 0]), cfg,
                                layer_params=layer_params and layer_params(p)
                                )[0]
                   for r in range(B)) / batch["ids"].size
    return jax.value_and_grad(total)(weights)


def _close(got, want, rel, what):
    scale = max(float(jnp.abs(want).max()), 1e-6)
    assert float(jnp.abs(got - want).max()) < rel * scale, what


# ---------------------------------------------------------------------------
# the program against the plain reference
# ---------------------------------------------------------------------------

def test_the_builder_shares_a_layer_s_weights_over_the_passes():
    cfg = _cfg()
    main, startup, _, _, _ = _program(cfg, 1e-3)
    specs = ref.weight_specs(cfg)
    params = main.global_block().all_parameters()
    assert sorted(p.name for p in params) == sorted(n for n, _, _ in specs)
    assert {p.name: list(p.shape) for p in params} == {
        n: list(s) for n, s, _ in specs}
    n = sum(int(np.prod(s)) for _, s, _ in specs)
    assert ouro.param_count(model_config(cfg)) == n
    # one initialiser and one Adam slot a weight, whatever the passes
    made = [o for op in startup.global_block().ops for o in op.output_names()]
    for name, _, _ in specs:
        assert made.count(name) == 1, name
        assert made.count(f"{name}_AdamOptimizer_moment1") == 1, name
    assert sum(op.type == "adam" for op in main.global_block().ops) == len(
        specs)
    # every application is a remat block of its own over those names
    units = {op.attrs.get("__unit__", "").split("/")[0]
             for op in main.global_block().ops}
    assert {f"blk{i}.u{t}" for i in range(2) for t in (1, 2, 3)} <= units
    assert {"final_norm.u1", "final_norm.u3", "exit_gate", "lm_head",
            "loss", "embed"} <= units
    assert main.remat_policy == "full"
    assert set(main.remat_keep) == {f"blk{i}.u{t}" for i in range(2)
                                    for t in (1, 2, 3)}


def test_the_published_cut_counts_612_million_parameters():
    n = ouro.param_count(ouro.OuroConfig(num_layers=8))
    assert n == 8 * 51_388_416 + 2 * 100_663_296 + 4_097 == 612_438_017
    assert ouro.param_count(ouro.OuroConfig()) == pytest.approx(2.668e9,
                                                               rel=1e-3)


def test_loss_and_every_gradient_against_the_reference():
    cfg = _cfg()
    weights = _weights(cfg)
    (batch,) = _batches(cfg, 1)
    want_loss, want_grads = _reference_loss_and_grads(cfg, weights, batch)
    got_loss, got_grads = _first_gradients(cfg, weights, batch)
    assert got_loss == pytest.approx(float(want_loss), rel=2e-6)
    assert sorted(got_grads) == sorted(want_grads)
    for k in weights:
        _close(got_grads[k], want_grads[k], 2e-4, k)
    # the gate learns: its gradient is the exits' losses, not zero
    assert float(jnp.abs(want_grads["exit_gate.w"]).max()) > 1e-4


def test_three_adam_steps_follow_the_reference():
    from paddle_tpu.observability import get_registry
    cfg = _cfg()
    main, _, fetch, exe, scope = _program(cfg, 1e-3)
    weights = _weights(cfg, seed=11)
    batches = _batches(cfg, 3, seed=4)
    for k, v in weights.items():
        scope.set_var(k, jnp.copy(v))
    want = ref.follow(cfg, weights, batches)
    losses = []
    for i, batch in enumerate(batches):
        loss, share, entropy = exe.run(main, feed=batch,
                                       fetch_list=list(fetch), scope=scope)
        losses.append(float(loss))
        if i == 0:
            grad_norms = {k: float(jnp.linalg.norm(scope.find_var(
                f"{k}_AdamOptimizer_moment1"))) / 0.1 for k in weights}
            np.testing.assert_allclose(share, want["exit_share"], rtol=1e-5)
            assert float(entropy) == pytest.approx(want["exit_entropy"],
                                                   rel=1e-5)
    np.testing.assert_allclose(losses, want["losses"], rtol=2e-6)
    for k in weights:
        assert grad_norms[k] == pytest.approx(want["grad_norms"][k],
                                              rel=1e-4, abs=1e-7), k
        moved = float(jnp.linalg.norm(scope.find_var(k) - weights[k]))
        assert moved == pytest.approx(want["update_norms"][k], rel=2e-3), k
    assert float(np.sum(share)) == pytest.approx(1.0, abs=1e-6)
    assert 0.0 < float(entropy) <= np.log(3) + 1e-6
    ouro.record_loop_counters(share, entropy)
    series = {(s["name"], s["labels"].get("pass")): s["value"]
              for s in get_registry().series()
              if s["name"].startswith("loop/")}
    assert series[("loop/passes", None)] == 3
    assert series[("loop/exit_entropy", None)] == pytest.approx(
        float(entropy))
    assert sum(series[("loop/exit_share", str(t))] for t in (1, 2, 3)) == \
        pytest.approx(1.0, abs=1e-6)


# ---------------------------------------------------------------------------
# the loop test
# ---------------------------------------------------------------------------

LAYER_LEAVES = ("norm1.w", "qkv.w", "o.w", "norm2.w", "norm3.w", "gate_up.w",
                "down.w", "norm4.w")


def _untied(weights, cfg):
    """Weights of the untied model of passes x layers layers: application
    (pass t, layer i) reads its own copy `u<t>.blk<i>.*` of `blk<i>.*`."""
    out = {k: v for k, v in weights.items() if not k.startswith("blk")}
    for t in range(1, cfg["total_ut_steps"] + 1):
        for i in range(cfg["num_hidden_layers"]):
            for leaf in LAYER_LEAVES:
                out[f"u{t}.blk{i}.{leaf}"] = weights[f"blk{i}.{leaf}"]
    return out


def test_the_loop_is_an_untied_model_whose_weights_are_copies():
    """The looped program equals the untied program of passes x layers
    layers whose weights are copies, and each shared weight's gradient is the
    sum of its copies' gradients; what is not looped (embedding, final norm,
    gate, head) has the same gradient in both."""
    cfg = _cfg()
    weights = _weights(cfg)
    (batch,) = _batches(cfg, 1, seed=2)
    loop_loss, loop_grads = _first_gradients(cfg, weights, batch)
    flat_loss, flat_grads = _first_gradients(
        cfg, _untied(weights, cfg), batch,
        layer_prefix=lambda t, i: f"u{t}.blk{i}")
    assert len(flat_grads) == len(weights) + 2 * 2 * len(LAYER_LEAVES)
    assert loop_loss == pytest.approx(flat_loss, rel=1e-6)
    for k in weights:
        if k.startswith("blk"):
            copies = [flat_grads[f"u{t}.{k}"] for t in (1, 2, 3)]
            # the passes differ: no copy's gradient is the sum's third
            assert float(jnp.abs(copies[0] - copies[2]).max()) > 0
            _close(loop_grads[k], sum(copies), 2e-5, k)
        else:
            _close(loop_grads[k], flat_grads[k], 2e-5, k)


def test_the_reference_s_loop_is_its_untied_model_too():
    cfg = _cfg()
    weights = _weights(cfg)
    (batch,) = _batches(cfg, 1, seed=2)
    want_loss, want = _reference_loss_and_grads(cfg, weights, batch)

    def per_application(p):        # pass t (from 0), layer i -> dict, index
        return lambda t, i: (
            {f"blk{i}.{leaf}": p[f"u{t + 1}.blk{i}.{leaf}"]
             for leaf in LAYER_LEAVES}, i)

    flat_loss, flat = _reference_loss_and_grads(
        cfg, _untied(weights, cfg), batch, layer_params=per_application)
    assert float(flat_loss) == pytest.approx(float(want_loss), rel=1e-6)
    for i in range(2):
        for leaf in LAYER_LEAVES:
            k = f"blk{i}.{leaf}"
            _close(want[k], sum(flat[f"u{t}.{k}"] for t in (1, 2, 3)),
                   2e-5, k)


# ---------------------------------------------------------------------------
# the new ops against their written-out formulas
# ---------------------------------------------------------------------------

def _eager(op_type, inputs, attrs):
    return ops.eager_call(op_type, {k: [jnp.asarray(v) for v in vs]
                                    for k, vs in inputs.items()}, attrs)


def _rope_by_complex_numbers(x, heads, theta):
    """Channel pair (j, j + D/2) of a head as the complex number
    x_j + i x_{j + D/2}, turned by exp(i pos theta^(-2j/D))."""
    b, t, hd = x.shape
    d = hd // heads
    xh = x.reshape(b, t, heads, d)
    z = xh[..., :d // 2] + 1j * xh[..., d // 2:]
    j = jnp.arange(d // 2)
    ang = jnp.arange(t)[:, None] * theta ** (-2.0 * j / d)[None]
    z = z * jnp.exp(1j * ang)[None, :, None, :]
    return jnp.concatenate([z.real, z.imag], -1).reshape(b, t, hd)


@pytest.mark.parametrize("heads,d,theta", [
    (2, 32, 1e6), (4, 16, 1e4), (1, 8, 1e6),
    # the chip's head sizes and counts around a 128-lane tile: a head a
    # tile, two heads a tile, half a tile left over, a head of two tiles
    (2, 128, 1e6), (4, 64, 1e6), (3, 64, 1e4), (1, 256, 1e6)])
def test_rotary_embedding_values_and_gradients(heads, d, theta):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 12, heads * d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    attrs = {"num_heads": heads, "theta": theta}

    def op(x):
        return _eager("rotary_embedding", {"X": [x]}, attrs)["Out"][0]

    want = _rope_by_complex_numbers(x, heads, theta)
    np.testing.assert_allclose(op(x), want, atol=2e-5)
    # the literal rotate-half pairing of the issue: (j, j + D/2)
    t, j = 5, 3
    angle = t * theta ** (-2 * j / d)
    lo, hi = x[0, t, j], x[0, t, j + d // 2]
    assert float(op(x)[0, t, j]) == pytest.approx(
        float(lo * np.cos(angle) - hi * np.sin(angle)), abs=2e-5)
    assert float(op(x)[0, t, j + d // 2]) == pytest.approx(
        float(hi * np.cos(angle) + lo * np.sin(angle)), abs=2e-5)
    got = jax.grad(lambda x: jnp.sum(op(x) * w))(x)
    want = jax.grad(lambda x: jnp.sum(
        _rope_by_complex_numbers(x, heads, theta) * w))(x)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # a rotation keeps every pair's length, and position 0 is left alone
    np.testing.assert_allclose(jnp.linalg.norm(op(x), axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    np.testing.assert_allclose(op(x)[:, 0], x[:, 0], atol=1e-6)


@pytest.mark.parametrize("heads,d", [(2, 128), (4, 64), (3, 64)])
def test_rotary_embedding_gradient_of_a_bf16_cotangent(heads, d):
    """Under AMP the cotangent arrives in bf16: the backward rule turns it
    in float32 and rounds once, as the forward does."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 12, heads * d)), jnp.bfloat16)
    g = jnp.asarray(rng.normal(size=x.shape), jnp.bfloat16)
    _, pull = jax.vjp(lambda x: nn_ops._rope(x, heads, 1e6), x)
    (got,) = pull(g)
    assert got.dtype == jnp.bfloat16
    _, pull = jax.vjp(lambda x: _rope_by_complex_numbers(x, heads, 1e6),
                      x.astype(jnp.float32))
    (want,) = pull(g.astype(jnp.float32))
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=2e-2)


@pytest.mark.parametrize("heads,d", [(2, 128), (4, 64)])
def test_the_rotation_is_lowered_without_half_heads(heads, d):
    """The form is kept: neither the op nor its backward rule concatenates,
    and no array in them has a minor dimension of D/2 (a half-head slice)."""
    x = jax.ShapeDtypeStruct((2, 24, heads * d), jnp.bfloat16)
    texts = [
        jax.jit(lambda x: nn_ops._rope(x, heads, 1e6)).lower(x).as_text(),
        jax.jit(lambda g: nn_ops._rope_bwd(heads, 1e6, False, None, None,
                                           g)[0]).lower(x).as_text()]
    half_minor = re.compile(rf"tensor<(\d+x)*{d // 2}x[a-z]")
    for text in texts:
        assert "stablehlo.multiply" in text
        assert "concatenate" not in text
        assert not half_minor.search(text)
    # what the test would catch: the half-head form reads so
    old = jax.jit(lambda x: _rope_by_complex_numbers(
        x.astype(jnp.float32), heads, 1e6)).lower(x).as_text()
    assert "concatenate" in old and half_minor.search(old)


def test_the_rotation_s_backward_is_the_rotation_by_the_negative_angle():
    """`vjp(g)` is the same pass over g with the sines' sign turned; it
    undoes the forward (a rotation's transpose is its inverse); and it keeps
    nothing of x's size for the backward pass."""
    heads, d, theta = 4, 16, 1e4
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 12, heads * d)), jnp.float32)
    g = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    out, pull = jax.vjp(lambda x: nn_ops._rope(x, heads, theta), x)
    np.testing.assert_allclose(
        pull(g)[0], nn_ops._rope_turn(g, heads, theta, -1.0), atol=1e-6)
    np.testing.assert_allclose(nn_ops._rope_turn(out, heads, theta, -1.0), x,
                               atol=1e-5)
    assert nn_ops._rope_fwd(x, heads, theta, False, None)[1] is None
    assert all(np.size(leaf) < x.size
               for leaf in jax.tree_util.tree_leaves(pull))
    # where a vjp does keep its input, the same count sees it
    _, keeps = jax.vjp(lambda x: x * x, x)
    assert any(np.size(leaf) == x.size
               for leaf in jax.tree_util.tree_leaves(keeps))


def test_rotary_embedding_keeps_bf16_and_rotates_in_float32():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(1, 8, 64)), jnp.bfloat16)
    attrs = {"num_heads": 2, "theta": 1e6}
    got = _eager("rotary_embedding", {"X": [x]}, attrs)["Out"][0]
    assert got.dtype == jnp.bfloat16
    want = _rope_by_complex_numbers(x.astype(jnp.float32), 2, 1e6)
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=2e-2)


def test_the_layer_refuses_heads_of_an_odd_size():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = layers.data("x", [8, 30], dtype="float32")
        with pytest.raises(ValueError, match="heads of an even size"):
            layers.rotary_embedding(x, num_heads=2)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_swiglu_values_and_gradients(dtype):
    rng = np.random.default_rng(0)
    gate = jnp.asarray(rng.normal(size=(2, 6, 24)) * 2, dtype)
    up = jnp.asarray(rng.normal(size=(2, 6, 24)), dtype)
    w = jnp.asarray(rng.normal(size=gate.shape), jnp.float32)

    def op(g, u):
        return _eager("swiglu", {"X": [g], "Y": [u]}, {})["Out"][0]

    def formula(g, u):
        g, u = g.astype(jnp.float32), u.astype(jnp.float32)
        return g / (1.0 + jnp.exp(-g)) * u

    tol = 1e-6 if dtype == "float32" else 2e-2
    assert op(gate, up).dtype == gate.dtype
    np.testing.assert_allclose(op(gate, up).astype(jnp.float32),
                               formula(gate, up), atol=tol, rtol=tol)
    got = jax.grad(lambda g, u: jnp.sum(op(g, u).astype(jnp.float32) * w),
                   (0, 1))(gate, up)
    want = jax.grad(lambda g, u: jnp.sum(formula(g, u) * w), (0, 1))(gate, up)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a.astype(jnp.float32),
                                   b.astype(jnp.float32), atol=10 * tol,
                                   rtol=10 * tol)


def test_the_gated_mlp_of_the_program_is_the_written_out_formula():
    """(silu(x W_gate) * x W_up) W_down through fc, split, swiglu, fc."""
    rng = np.random.default_rng(3)
    d, f = 16, 24
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", [4, d], dtype="float32")
        gu = layers.fc(x, 2 * f, num_flatten_dims=2, bias_attr=False,
                       param_attr=fluid.ParamAttr(name="gate_up"))
        act = layers.swiglu(*layers.split(gu, 2, dim=2))
        out = layers.fc(act, d, num_flatten_dims=2, bias_attr=False,
                        param_attr=fluid.ParamAttr(name="down"))
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    w_gu = rng.normal(size=(d, 2 * f)).astype("float32")
    w_down = rng.normal(size=(f, d)).astype("float32")
    xv = rng.normal(size=(2, 4, d)).astype("float32")
    with fluid.scope_guard(scope):
        exe.run(startup)
        scope.set_var("gate_up", w_gu)
        scope.set_var("down", w_down)
        (got,) = exe.run(main, feed={"x": xv}, fetch_list=[out])
    g, u = xv @ w_gu[:, :f], xv @ w_gu[:, f:]
    np.testing.assert_allclose(got, (g / (1 + np.exp(-g)) * u) @ w_down,
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the exits
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("passes", [2, 3, 4])
def test_the_exit_distribution_sums_to_one(passes):
    rng = np.random.default_rng(passes)
    states = jnp.asarray(rng.normal(size=(passes, 2, 5, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 1)), jnp.float32)
    b = jnp.asarray([0.2], jnp.float32)
    p = _eager("loop_exit_gate", {"X": [states], "W": [w], "Bias": [b]},
               {})["Out"][0]
    assert p.shape == (passes, 2, 5) and p.dtype == jnp.float32
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, atol=1e-6)
    # written out: p_t = lambda_t prod_{j<t} (1 - lambda_j), the rest last
    lam = 1.0 / (1.0 + np.exp(-(np.asarray(states[:-1]) @ np.asarray(w))[
        ..., 0] - 0.2))
    left = np.ones_like(lam[0])
    for t in range(passes - 1):
        np.testing.assert_allclose(p[t], lam[t] * left, rtol=1e-5, atol=1e-7)
        left = left * (1 - lam[t])
    np.testing.assert_allclose(p[-1], left, rtol=1e-5, atol=1e-7)
    want = ref.exit_distribution(states.reshape(passes, 10, 16),
                                 {"exit_gate.w": w, "exit_gate.b": b})
    np.testing.assert_allclose(p.reshape(passes, 10), want, rtol=1e-5,
                               atol=1e-7)


def test_the_exit_loss_against_its_formula_and_at_a_closed_exit():
    rng = np.random.default_rng(0)
    p = jnp.asarray(rng.dirichlet(np.ones(4), size=(2, 6)).transpose(2, 0, 1),
                    jnp.float32)
    ce = jnp.asarray(rng.uniform(1, 5, size=(4, 2, 6, 1)), jnp.float32)
    out = _eager("loop_exit_loss", {"P": [p], "CE": [ce]}, {"beta": 0.05})
    h = -jnp.sum(p * jnp.log(p), axis=0)
    want = jnp.mean(jnp.sum(p * ce[..., 0], axis=0) - 0.05 * h)
    assert float(out["Loss"][0]) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(out["ExitShare"][0],
                               jnp.mean(p, axis=(1, 2)), rtol=1e-6)
    assert float(out["ExitEntropy"][0]) == pytest.approx(float(jnp.mean(h)),
                                                         rel=1e-6)
    # an exit nobody takes (p exactly 0) adds 0 log 0 = 0 and a finite
    # gradient; the gradient reaching p elsewhere is CE - beta dH/dp
    shut = p.at[0].set(0.0)
    g = jax.grad(lambda q: _eager("loop_exit_loss", {"P": [q], "CE": [ce]},
                                  {"beta": 0.05})["Loss"][0])(shut)
    assert bool(jnp.all(jnp.isfinite(g)))
    np.testing.assert_allclose(
        g[1], (ce[1, ..., 0] + 0.05 * (jnp.log(shut[1]) + 1.0)) / 12,
        rtol=1e-5)


def test_with_the_gates_forced_shut_the_loss_is_the_last_exit_s():
    cfg = _cfg()
    main, _, (loss, share, entropy), exe, scope = _program(cfg)
    weights = ref.make_weights(cfg, 3)          # gate weight 0
    weights["exit_gate.b"] = jnp.asarray([-40.0], jnp.float32)
    for k, v in weights.items():
        scope.set_var(k, jnp.copy(v))
    (batch,) = _batches(cfg, 1)
    got, p, h = exe.run(main, feed=batch, fetch_list=[loss, share, entropy],
                        scope=scope)
    last = 0.0
    for r in range(B):
        states = jnp.stack(ref.exit_states(weights, jnp.asarray(
            batch["ids"][r]), cfg))
        last += float(jnp.sum(ref.cross_entropies(
            states, jnp.asarray(batch["labels"][r, :, 0]), weights, cfg)[-1]))
    assert float(got) == pytest.approx(last / (B * T), rel=1e-5)
    np.testing.assert_allclose(p, [0.0, 0.0, 1.0], atol=1e-6)
    assert abs(float(h)) < 1e-6


# ---------------------------------------------------------------------------
# the head with learnt row weights
# ---------------------------------------------------------------------------

def test_linear_ce_rows_weighted_by_a_learnt_weight_against_the_dense_pair(
        monkeypatch):
    """sum_r weight_r(x) CE_r with weight = sigmoid(x . g): the loss and the
    gradients of x, W and g through the chunked op are those of the dense
    logits and log-softmax, and the gradient reaching the weight is the
    row's loss."""
    rng = np.random.default_rng(0)
    n, h, v = 48, 16, 40
    x = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(h, v)) * 0.3, jnp.float32)
    g = jnp.asarray(rng.normal(size=(h,)) * 0.3, jnp.float32)
    b = jnp.zeros((v,), jnp.float32)
    lbl = jnp.asarray(rng.integers(0, v, n), jnp.int32)
    chunks = (16, 32)               # three chunks of rows, backward two

    def fused_rows(x, w):
        return nn_ops._linear_ce(x, w, b, lbl, -100, chunks)

    def dense_rows(x, w):
        logp = jax.nn.log_softmax(jnp.matmul(
            x, w, precision=jax.lax.Precision.HIGHEST), axis=-1)
        return -jnp.take_along_axis(logp, lbl[:, None], axis=-1)[:, 0]

    def total(rows):
        return lambda x, w, g: jnp.sum(jax.nn.sigmoid(x @ g) * rows(x, w))

    got = jax.value_and_grad(total(fused_rows), (0, 1, 2))(x, w, g)
    want = jax.value_and_grad(total(dense_rows), (0, 1, 2))(x, w, g)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for a, e, name in zip(got[1], want[1], ("x", "W", "gate")):
        _close(a, e, 1e-4, name)
    weight = jax.nn.sigmoid(x @ g)
    to_weight = jax.grad(lambda r: jnp.sum(r * fused_rows(x, w)))(weight)
    np.testing.assert_allclose(to_weight, dense_rows(x, w), rtol=1e-5)


# ---------------------------------------------------------------------------
# a parameter with several consumers
# ---------------------------------------------------------------------------

def test_create_parameter_twice_under_one_name_is_one_parameter():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", [8], dtype="float32")
        first = fluid.ParamAttr(name="shared.w", learning_rate=0.5)
        again = fluid.ParamAttr(
            name="shared.w", learning_rate=2.0,
            initializer=fluid.initializer.ConstantInitializer(7.0))
        h = layers.fc(x, 8, param_attr=first, bias_attr=False)
        y = layers.fc(h, 8, param_attr=again, bias_attr=False)
        loss = layers.reduce_mean(y)
        fluid.optimizer.Adam(1e-3).minimize(loss)
    params = main.global_block().all_parameters()
    assert [p.name for p in params] == ["shared.w"]
    ops_in = [op for op in main.global_block().ops if op.type == "mul"]
    assert [op.inputs["Y"] for op in ops_in] == [["shared.w"], ["shared.w"]]
    # the first definition stands: its learning rate, its initialiser
    assert params[0].optimize_attr == {"learning_rate": 0.5}
    made = [o for op in startup.global_block().ops for o in op.output_names()]
    assert made.count("shared.w") == 1
    assert made.count("shared.w_AdamOptimizer_moment1") == 1
    assert sum(op.type == "adam" for op in main.global_block().ops) == 1
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        w0 = np.asarray(scope.find_var("shared.w")).copy()
        assert not np.allclose(w0, 7.0)
        xv = np.random.default_rng(0).normal(size=(4, 8)).astype("float32")
        exe.run(main, feed={"x": xv}, fetch_list=[loss])
        # d mean(x W W) / dW has both uses' parts
        want = jax.grad(lambda w: jnp.mean(jnp.matmul(jnp.matmul(
            xv, w, precision="highest"), w, precision="highest")))(
                jnp.asarray(w0))
        got = scope.find_var("shared.w_AdamOptimizer_moment1") / 0.1
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("what,kwargs", [
    ("shape", {"size": 4}), ("dtype", {"size": 8, "dtype": "bfloat16"})])
def test_a_second_definition_of_another_shape_or_dtype_raises(what, kwargs):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", [8], dtype="float32")
        layers.fc(x, 8, param_attr=fluid.ParamAttr(name="w"), bias_attr=False)
        x2 = layers.cast(x, kwargs.get("dtype", "float32"))
        with pytest.raises(ValueError) as e:
            layers.fc(x2, kwargs["size"], param_attr=fluid.ParamAttr(name="w"),
                      bias_attr=False)
    assert "'w' exists with shape [8, 8]" in str(e.value)
    assert f"[8, {kwargs['size']}]" in str(e.value)
    assert len(main.global_block().all_parameters()) == 1


def test_a_name_that_is_no_parameter_cannot_be_shared():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", [8], dtype="float32")
        with pytest.raises(ValueError, match="is no parameter"):
            layers.fc(x, 8, param_attr=fluid.ParamAttr(name="x"),
                      bias_attr=False)


def test_two_programs_on_one_startup_initialise_a_parameter_once():
    startup = fluid.Program()
    mains = [fluid.Program(), fluid.Program()]
    for main in mains:
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            x = layers.data("x", [8], dtype="float32")
            layers.fc(x, 8, param_attr=fluid.ParamAttr(name="w"),
                      bias_attr=fluid.ParamAttr(name="b"))
    made = [o for op in startup.global_block().ops for o in op.output_names()]
    assert sorted(made) == ["b", "w"]
    assert all(len(m.global_block().all_parameters()) == 2 for m in mains)


# ---------------------------------------------------------------------------
# the backward walk over a shared weight, and what a remat block hands out
# ---------------------------------------------------------------------------

def _lowered_barriers(layer_prefix):
    cfg = _cfg()
    main, _, (loss, _, _), exe, scope = _program(cfg, 1e-3, layer_prefix)
    names = sorted(v.name for v in main.list_vars()
                   if v.persistable and scope.has_var(v.name))
    state = {n: scope.find_var(n) for n in names}
    (batch,) = _batches(cfg, 1)
    feed = {k: jnp.asarray(v, jnp.int32) for k, v in batch.items()}
    step = exe._build(main, sorted(feed), [loss.name], names, names)
    text = jax.jit(step._step).lower(state, feed, scope.find_var(
        "@RNG_STATE@")).as_text()
    return text.count("optimization_barrier")


def test_a_shared_weight_s_partial_sums_are_handed_on_in_order(monkeypatch):
    """On one device every tape entry that reads a matrix, or a parameter
    other entries read too, ends its backward behind one barrier that holds
    its cotangents with the sums so far: an application of a layer (a remat
    block's entry holds all its weights), a pass's final norm (a vector, but
    every pass reads it), the table's lookup, the head and the exit gate. So
    the looped model and the untied one, whose every matrix has one reader,
    lower the same barriers; the rest of the text's are jax.checkpoint's."""
    cfg = _cfg()
    passes = cfg["total_ut_steps"]
    applications = passes * cfg["num_hidden_layers"]
    looped = _lowered_barriers(ouro.shared)
    untied = _lowered_barriers(lambda t, i: f"u{t}.blk{i}")
    assert looped == untied
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    assert looped - _lowered_barriers(ouro.shared) == applications + passes + 3


def _mesh_barriers(layer_prefix, monkeypatch):
    """Entries a backward walk hands on behind a barrier when the step is
    lowered for a data-parallel mesh of two devices."""
    from test_grad_barriers import barriers_a_walk

    cfg = _cfg()
    main, _, (loss, _, _), exe, scope = _program(cfg, 1e-3, layer_prefix)
    program = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, places=jax.devices()[:2])
    (batch,) = _batches(cfg, 1)

    def run():
        with fluid.scope_guard(scope):
            exe.run(program, feed=batch, fetch_list=[loss])

    return barriers_a_walk(run, 2, monkeypatch)


def test_under_a_mesh_only_a_shared_weight_s_entries_are_behind_a_barrier(
        monkeypatch):
    """Where a mesh sums the gradients across devices the all-reduce stands
    between a weight-gradient product and its update already: only the
    entries that read a parameter several entries read keep the barrier,
    which is there for the order of the partial sums. The looped model has
    one an application and one a pass's final norm; the untied model the
    final norm's alone."""
    cfg = _cfg()
    passes = cfg["total_ut_steps"]
    applications = passes * cfg["num_hidden_layers"]
    assert _mesh_barriers(ouro.shared, monkeypatch) == applications + passes
    assert _mesh_barriers(
        lambda t, i: f"u{t}.blk{i}", monkeypatch) == passes


def test_a_remat_block_hands_out_unread_values_without_cotangents():
    """Of what a remat block writes, the rest of the program reads one
    value; another is only fetched. Both come out right, the step trains as
    without remat, and only the first is an output the backward pass has a
    cotangent for."""
    from paddle_tpu.core import executor as ex
    from paddle_tpu.core.program import unit

    def build(remat):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            main.random_seed = startup.random_seed = 3
            x = layers.data("x", [8])
            with unit("a", remat=True):
                h = layers.fc(x, 6, act="tanh",
                              param_attr=fluid.ParamAttr(name="w0"))
                probe = layers.scale(h, 2.0)
                y = layers.fc(h, 1, param_attr=fluid.ParamAttr(name="w1"))
            loss = layers.reduce_mean(layers.square(y))
            fluid.optimizer.SGD(0.1).minimize(loss)
        if remat:
            main.remat_policy = "full"
        return main, startup, loss, probe, y

    feed = {"x": np.random.RandomState(0).rand(4, 8).astype("float32")}
    got = []
    for remat in (True, False):
        main, startup, loss, probe, y = build(remat)
        with fluid.scope_guard(fluid.Scope()) as _:
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup)
            got.append([np.asarray(v) for v in exe.run(
                main, feed=feed, fetch_list=[loss, probe, "w0", "w1"])])
    for a, b in zip(*got):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    ops = [op for op in main.global_block().ops
           if op.attrs.get("__unit__", "").startswith("a")]
    read = ex._read_outside(ops)
    assert y.name in read and probe.name not in read
