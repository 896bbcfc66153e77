"""Laguna (`models/laguna.py`) against its plain reference
(`benchmark/configs/laguna_xs2_reference.py`) at a small size on the CPU, and
the pieces the model forced, each against its written-out form: a head count
by layer over shared key/value heads, the sliding window, the rotation on a
part of each head with YaRN's frequencies, the per-head output gate, and the
shares of the experts adding up to the uncut layer."""
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from benchmark.configs import laguna_xs2_reference as ref
from paddle_tpu import layers
from paddle_tpu.core.program import unit
from paddle_tpu.models import laguna
from paddle_tpu.ops import nn_ops

fa = importlib.import_module("paddle_tpu.ops.pallas_kernels.flash_attention")

FULL, SLIDING = laguna.FULL, laguna.SLIDING


def _rope(full_factor=0.5):
    return {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 4,
               "original_max_position_embeddings": 16, "beta_slow": 1,
               "beta_fast": 2, "attention_factor": 0.1 * math.log(4) + 1,
               "partial_rotary_factor": full_factor},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1}}


def _cfg(experts=16, held=(0, 4), **over):
    cfg = {
        "hidden_size": 64, "num_hidden_layers": 5, "intermediate_size": 96,
        "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
        "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
        "layer_types": [FULL, SLIDING, SLIDING, SLIDING, FULL],
        "mlp_layer_types": ["dense"] + ["sparse"] * 4,
        "sliding_window": 8, "gating": True, "rope_parameters": _rope(),
        "num_experts": held[1], "num_experts_published": experts,
        "experts_held": list(held), "num_experts_per_tok": 4,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "moe_routed_scaling_factor": 2.5, "norm_topk_prob": True,
        "moe_apply_router_weight_on_input": False,
        "rms_norm_eps": 1e-6, "vocab_size": 96, "initializer_range": 0.2,
        "optimizer": {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.999,
                      "epsilon": 1e-8},
        "reference": {"follow_steps": 3, "head_rows": 16}}
    cfg.update(over)
    return cfg


def _model_cfg(cfg):
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "intermediate_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "num_attention_heads_per_layer", "layer_types",
            "mlp_layer_types", "sliding_window", "gating", "rope_parameters",
            "num_experts_per_tok", "moe_intermediate_size",
            "shared_expert_intermediate_size", "moe_routed_scaling_factor",
            "norm_topk_prob", "rms_norm_eps", "initializer_range")
    return laguna.LagunaConfig(
        num_experts=cfg["num_experts_published"],
        experts_held=tuple(cfg["experts_held"]), **{k: cfg[k] for k in keys})


def _batches(cfg, n, b=2, t=32, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        ids = rng.randint(0, cfg["vocab_size"], (b, t + 1)).astype("int32")
        out.append({"ids": ids[:, :-1].copy(),
                    "labels": ids[:, 1:, None].copy()})
    return out


def _program(cfg, b=2, t=32, lr=None):
    opt = (lambda: fluid.optimizer.Adam(lr)) if lr else None
    with fluid.unique_name.guard():
        main, startup, _, loss, counters = laguna.build_pretrain_program(
            _model_cfg(cfg), b, t, opt)
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    return main, loss, counters, exe, scope


def _reference_loss(cfg, weights, batch):
    b, t = batch["ids"].shape
    return sum(ref.sum_loss(weights, jnp.asarray(batch["ids"][r]),
                            jnp.asarray(batch["labels"][r, :, 0]), cfg)
               for r in range(b)) / (b * t)


def _set(scope, weights):
    for k, v in weights.items():
        scope.set_var(k, jnp.copy(v))


def _moment_grad(scope, k):
    return scope.find_var(f"{k}_AdamOptimizer_moment1") / 0.1


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

def test_loss_and_every_gradient_leaf_against_the_reference():
    cfg = _cfg()
    main, loss, _, exe, scope = _program(cfg, lr=1e-3)
    weights = ref.make_weights(cfg, 5)
    params = main.global_block().all_parameters()
    assert sorted(p.name for p in params) == sorted(weights)
    assert all(p.trainable for p in params)
    _set(scope, weights)
    (batch,) = _batches(cfg, 1)
    want_loss, want_grads = jax.value_and_grad(
        lambda p: _reference_loss(cfg, p, batch))(weights)
    (got_loss,) = exe.run(main, feed=batch, fetch_list=[loss], scope=scope)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=2e-6)
    for k in weights:
        got, want = _moment_grad(scope, k), want_grads[k]
        scale = max(float(jnp.abs(want).max()), 1e-6)
        assert float(jnp.abs(got - want).max()) < 2e-4 * scale, k


def test_three_adam_steps_follow_the_reference():
    cfg = _cfg()
    main, loss, counters, exe, scope = _program(cfg, lr=1e-3)
    batches = _batches(cfg, 3, seed=4)
    weights = ref.make_weights(cfg, 11)
    _set(scope, weights)
    want = ref.follow(cfg, weights, batches)
    assert sorted(want["grad_norms"]) == sorted(weights)
    fetch = [loss] + [v for _, t, p in counters for v in (t, p)]
    losses = []
    for i, batch in enumerate(batches):
        out = exe.run(main, feed=batch, fetch_list=fetch, scope=scope)
        losses.append(float(out[0]))
        if i == 0:
            grad_norms = {k: float(jnp.linalg.norm(_moment_grad(scope, k)))
                          for k in weights}
    np.testing.assert_allclose(losses, want["losses"], rtol=2e-6)
    for k in weights:
        assert grad_norms[k] == pytest.approx(want["grad_norms"][k],
                                              rel=1e-4, abs=1e-7), k
        moved = float(jnp.linalg.norm(scope.find_var(k) - weights[k]))
        assert moved == pytest.approx(want["update_norms"][k], rel=2e-3), k
    # four expert layers, each with its counters
    assert [i for i, _, _ in counters] == [1, 2, 3, 4]
    for tokens, pairs in zip(out[1::2], out[2::2]):
        assert tokens.shape == (4,) and int(pairs) == tokens.sum()
    laguna.record_moe_counters(counters, out[1:], 2 * 32, 4)
    from paddle_tpu.observability import get_registry
    series = {(s["name"], s["labels"].get("block")): s["value"]
              for s in get_registry().series()
              if s["name"].startswith("moe/") and "expert" not in s["labels"]}
    assert series[("moe/pairs_routed", "blk1")] == 2 * 32 * 4
    assert series[("moe/pairs_held", "blk4")] == int(out[-1])


def test_the_builder_names_its_units_and_counts_its_parameters():
    cfg = _cfg()
    mcfg = _model_cfg(cfg)
    n = sum(int(np.prod(shape)) for _, shape, _ in ref.weight_specs(cfg))
    assert laguna.param_count(mcfg) == n
    main, *_ = laguna.build_pretrain_program(mcfg, 2, 32)
    ops = main.global_block().ops
    units = {op.attrs.get("__unit__") for op in ops}
    for part in ("qkv", "gate", "rope", "kernel", "o"):
        assert f"blk0/attn/{part}" in units and f"blk4/attn/{part}" in units
    for part in ("qkv", "gate", "rope", "swa", "o"):
        assert f"blk2/attn/{part}" in units
    assert "blk2/attn/kernel" not in units and "blk0/attn/swa" not in units
    assert {"blk0/mlp/gate_up", "blk0/mlp/act", "blk0/mlp/down",
            "blk3/moe/shared/gate_up", "blk3/moe/combine", "blk3/moe",
            "final_norm", "lm_head", "loss"} <= units
    # a window layer's kernel op says its window, a full layer's says none;
    # the full layers' rotation says its part and YaRN, the window layers'
    # neither
    attn = {op.attrs["__unit__"]: op.attrs for op in ops
            if op.type == "flash_attention"}
    assert attn["blk1/attn/swa"]["window"] == 8
    assert attn["blk1/attn/swa"]["num_heads"] == 8
    assert "window" not in attn["blk0/attn/kernel"]
    assert attn["blk0/attn/kernel"]["num_heads"] == 6
    assert attn["blk0/attn/kernel"]["num_kv_heads"] == 2
    rope = {op.attrs["__unit__"]: op.attrs for op in ops
            if op.type == "rotary_embedding"}
    assert rope["blk0/attn/rope"]["rotary_dim"] == 8
    assert rope["blk0/attn/rope"]["yarn"] == [4.0, 16.0, 2.0, 1.0]
    assert rope["blk0/attn/rope"]["num_heads"] == 6 + 2
    assert set(rope["blk1/attn/rope"]) & {"rotary_dim", "yarn",
                                          "attention_factor"} == set()
    assert rope["blk1/attn/rope"]["theta"] == 10000.0
    # remat blocks keep the kernel's residuals and the routing
    assert set(main.remat_keep) == {f"blk{i}" for i in range(5)}
    assert set(fa.KEPT) <= set(main.remat_keep["blk2"])


def test_param_count_at_the_published_keys():
    """33,442,596,864 whole and 3,017,115,648 active, the published
    "33.4B-A3B"; a gate as wide as the head would give 34.1B."""
    cfg = laguna.LagunaConfig()
    assert laguna.param_count(cfg) == 33_442_596_864
    assert laguna.param_count(cfg, touched=True) == 3_017_115_648
    wide_gate = sum(2048 * n * 127 for n in cfg.num_attention_heads_per_layer)
    assert round((33_442_596_864 + wide_gate) / 1e9, 1) == 34.1
    cut = laguna.LagunaConfig(num_hidden_layers=5, vocab_size=12544,
                              experts_held=(0, 32))
    assert laguna.param_count(cut) == 691_623_936
    sixteen = laguna.LagunaConfig(num_hidden_layers=5, vocab_size=12544,
                                  experts_held=(0, 16))
    assert laguna.param_count(sixteen) == 490_297_344


def test_the_configuration_refuses_what_is_not_built():
    with pytest.raises(ValueError, match="unknown attention"):
        laguna.LagunaConfig(layer_types=["linear"] * 40).check()
    with pytest.raises(ValueError, match="not a multiple of the 8"):
        laguna.LagunaConfig(
            num_attention_heads_per_layer=[44] * 40).check()
    with pytest.raises(ValueError, match="names 3 layers of 40"):
        laguna.LagunaConfig(mlp_layer_types=["dense"] * 3).check()
    with pytest.raises(ValueError, match="output gate is built in"):
        laguna.LagunaConfig(gating=False).check()
    bad = laguna.LagunaConfig()
    bad.rope_parameters[FULL]["rope_type"] = "llama3"
    with pytest.raises(ValueError, match="rope_type 'llama3'"):
        laguna.rope_arguments(bad, FULL)


# ---------------------------------------------------------------------------
# the shares of the experts add up to the uncut layer
# ---------------------------------------------------------------------------

def test_four_shares_with_the_shared_expert_once_add_up_to_the_whole_layer():
    whole_cfg = _cfg(held=(0, 16))
    weights = ref.make_weights(whole_cfg, 3)
    x = jnp.asarray(np.random.RandomState(1).randn(32, 64), jnp.float32)
    p = "blk2"
    whole = (ref.routed_experts(x, weights, p, whole_cfg)
             + ref.shared_expert(x, weights, p, whole_cfg))

    # the program's expert layer, a share at a time
    parts = []
    for first in (0, 4, 8, 12):
        cfg = _cfg(held=(first, 4))
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            xin = layers.data("x", [32, 64], dtype="float32")
            with unit(p, remat=True):
                out, tokens, pairs = laguna.experts(_model_cfg(cfg), xin, p)
        exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
        for leaf in (".moe.w1", ".moe.w3", ".moe.w2"):
            scope.set_var(p + leaf, weights[p + leaf][first:first + 4])
        for leaf in (".moe.gate", ".shared.gate_up.w", ".shared.down.w"):
            scope.set_var(p + leaf, jnp.copy(weights[p + leaf]))
        got, held_pairs = exe.run(main, feed={"x": np.asarray(x)[None]},
                                  fetch_list=[out, pairs], scope=scope)
        parts.append((got[0], int(held_pairs)))
    shared = np.asarray(ref.shared_expert(x, weights, p, whole_cfg))
    total = sum(g for g, _ in parts) - 3 * shared
    np.testing.assert_allclose(total, whole, rtol=2e-5, atol=2e-5)
    assert sum(n for _, n in parts) == 32 * 4


# ---------------------------------------------------------------------------
# YaRN's tables and the part that passes
# ---------------------------------------------------------------------------

def test_yarn_s_inverse_frequencies_against_the_literal_formulas():
    got = nn_ops.yarn_inv_freq(500000.0, 64, 64.0, 4096, 64.0, 1.0)
    c = lambda n: 64 * math.log(4096 / (2 * math.pi * n)) / (
        2 * math.log(500000))
    low, high = math.floor(c(64)), math.ceil(c(1))
    assert (low, high) == (5, 16)
    for i in range(32):
        f = 500000.0 ** (-2 * i / 64)
        r = min(max((i - low) / (high - low), 0.0), 1.0)
        assert got[i] == pytest.approx(f * (1 - r) + f / 64 * r, rel=1e-12)
    assert got[5] == 500000.0 ** (-10 / 64)            # extrapolated as is
    assert got[16] == pytest.approx(500000.0 ** (-32 / 64) / 64, rel=1e-12)
    assert 0.1 * math.log(64) + 1 == pytest.approx(1.4158883083359672,
                                                   abs=1e-15)
    # the reference's own formula, written apart, agrees
    np.testing.assert_allclose(
        ref.inverse_frequencies(laguna.LagunaConfig().rope_parameters[FULL],
                                64), got, rtol=1e-12)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_published_full_layer_rotation_turns_half_a_head(dtype):
    """128-wide heads, the first 64 channels turned by YaRN's angles times
    the factor, the other 64 untouched (to the bit), against pairs written
    out in numpy."""
    rule = laguna.LagunaConfig().rope_parameters[FULL]
    t, heads, d = 24, 3, 128
    x = np.random.RandomState(0).randn(2, t, heads * d).astype("float32")
    xj = jnp.asarray(x, dtype)
    yarn = (64.0, 4096.0, 64.0, 1.0)
    got = nn_ops._rope(xj, heads, 500000.0, False,
                       (64, yarn, rule["attention_factor"]))
    assert got.dtype == xj.dtype
    got = np.asarray(got.astype(jnp.float32)).reshape(2, t, heads, d)
    xin = np.asarray(xj.astype(jnp.float32)).reshape(2, t, heads, d)
    assert np.array_equal(got[..., 64:], xin[..., 64:])
    inv = nn_ops.yarn_inv_freq(500000.0, 64, *yarn).astype("float32")
    ang = np.arange(t, dtype="float32")[:, None] * inv[None, :]
    cos = (np.cos(ang) * rule["attention_factor"])[None, :, None, :]
    sin = (np.sin(ang) * rule["attention_factor"])[None, :, None, :]
    a, b = xin[..., :32], xin[..., 32:64]
    want = np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(got[..., :64], want, rtol=tol, atol=tol)


def test_the_rotation_s_rule_against_the_reference_and_its_gradient():
    """Small heads: every combination the op takes (a part, YaRN, a factor)
    equals the reference's `rotate`, and the backward rule is the
    transpose."""
    t, heads, d = 32, 4, 16
    x = jnp.asarray(np.random.RandomState(2).randn(2, t, heads * d),
                    jnp.float32)
    g = jnp.asarray(np.random.RandomState(3).randn(2, t, heads * d),
                    jnp.float32)
    full = _rope()[FULL]
    cases = [
        ((8, (4.0, 16.0, 2.0, 1.0), full["attention_factor"]), full),
        ((8, None, None), {"rope_theta": 500000, "rope_type": "default",
                           "partial_rotary_factor": 0.5}),
        ((None, (4.0, 16.0, 2.0, 1.0), 1.25),
         dict(full, partial_rotary_factor=1, attention_factor=1.25)),
    ]
    for rule, ref_rule in cases:
        f = lambda x: nn_ops._rope(x, heads, 500000.0, False, rule)
        want_f = lambda x: jax.vmap(lambda s: ref.rotate(
            s.reshape(t, heads, d), ref_rule).reshape(t, heads * d))(x)
        np.testing.assert_allclose(f(x), want_f(x), rtol=1e-5, atol=1e-5)
        (got,) = jax.vjp(f, x)[1](g)
        (want,) = jax.vjp(want_f, x)[1](g)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_the_rotation_without_the_new_attributes_is_the_parent_s_jaxpr():
    """Rotate-half and interleaved ops that name no part, no YaRN and no
    factor trace to the jaxprs of the parent commit (2efe038, read from a
    copy of it), to the character; and the layer writes none of the three
    attributes unless asked."""
    import hashlib
    import re
    digests = {}
    for name, (heads, d, inter) in {"half_h40_d128": (40, 128, False),
                                    "inter_h33_d64": (33, 64, True),
                                    "half_h24_d64": (24, 64, False)}.items():
        x = jax.ShapeDtypeStruct((2, 4096, heads * d), jnp.bfloat16)
        f = lambda x: jnp.sum(
            nn_ops._rope(x, heads, 1e6, inter).astype(jnp.float32))
        with jax.default_matmul_precision("default"):
            text = re.sub(r" at 0x[0-9a-f]+", "",
                          str(jax.make_jaxpr(jax.grad(f))(x)))
        digests[name] = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert digests == {"half_h40_d128": "ddece0c16a8aabb0",
                       "inter_h33_d64": "7ab89f1801a7cfb1",
                       "half_h24_d64": "42bd64e5fa0863a4"}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [16, 64], dtype="float32")
        layers.rotary_embedding(x, 4, theta=1e4)
        layers.rotary_embedding(x, 4, theta=5e5, rotary_dim=8, yarn={
            "factor": 4, "original_max_position_embeddings": 16,
            "beta_fast": 2, "beta_slow": 1})
        with pytest.raises(ValueError, match="not an even part of a head"):
            layers.rotary_embedding(x, 4, rotary_dim=18)
    plain, ruled = [op.attrs for op in main.global_block().ops
                    if op.type == "rotary_embedding"]
    assert set(plain) & {"rotary_dim", "yarn", "attention_factor"} == set()
    assert ruled["rotary_dim"] == 8
    assert ruled["attention_factor"] == pytest.approx(0.1 * math.log(4) + 1)


# ---------------------------------------------------------------------------
# the gate, and a window layer against a literal mask
# ---------------------------------------------------------------------------

def _attention_layer(cfg, layer, x, weights):
    """The program's attention of `layer` on x [B, T, D]."""
    p = f"blk{layer}"
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        xin = layers.data("x", list(x.shape[1:]), dtype="float32")
        with unit(p, remat=True):
            out = laguna.attention(_model_cfg(cfg), xin, p, layer)
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    for leaf in (".qkv.w", ".gate.w", ".o.w"):
        scope.set_var(p + leaf, jnp.copy(weights[p + leaf]))
    (got,) = exe.run(main, feed={"x": x}, fetch_list=[out], scope=scope)
    return got


def _numpy_attention(cfg, layer, x, weights, gate=True, window=True):
    """One sequence x [T, D] head by head in numpy float64: the rotation by
    the reference's `rotate`, a literal mask, the gate a scalar a head."""
    p = f"blk{layer}"
    nh, nkv, hd = (cfg["num_attention_heads_per_layer"][layer],
                   cfg["num_key_value_heads"], cfg["head_dim"])
    kind, t = cfg["layer_types"][layer], x.shape[0]
    rule = cfg["rope_parameters"][kind]
    w = {k: np.asarray(weights[p + k], "float64")
         for k in (".qkv.w", ".gate.w", ".o.w")}
    qkv = x.astype("float64") @ w[".qkv.w"]
    q = np.asarray(ref.rotate(jnp.asarray(
        qkv[:, :nh * hd].reshape(t, nh, hd), jnp.float32), rule), "float64")
    k = np.asarray(ref.rotate(jnp.asarray(
        qkv[:, nh * hd:(nh + nkv) * hd].reshape(t, nkv, hd), jnp.float32),
        rule), "float64")
    v = qkv[:, (nh + nkv) * hd:].reshape(t, nkv, hd)
    g = 1 / (1 + np.exp(-(x.astype("float64") @ w[".gate.w"])))   # [T, nh]
    out = np.zeros((t, nh * hd))
    for h in range(nh):
        kvh = h // (nh // nkv)
        s = q[:, h] @ k[:, kvh].T / math.sqrt(hd)
        for i in range(t):
            for j in range(t):
                hidden = j > i or (window and kind == SLIDING
                                   and i - j >= cfg["sliding_window"])
                if hidden:
                    s[i, j] = -np.inf
        prob = np.exp(s - s.max(-1, keepdims=True))
        prob /= prob.sum(-1, keepdims=True)
        ctx = prob @ v[:, kvh]
        out[:, h * hd:(h + 1) * hd] = ctx * (g[:, h:h + 1] if gate else 1.0)
    return out @ w[".o.w"]


@pytest.mark.parametrize("layer", [0, 2])
def test_attention_against_numpy_head_by_head(layer):
    """Layer 0: 6 heads over 2 (groups of 3), full, YaRN on half a head.
    Layer 2: 8 heads over 2 (groups of 4), a window of 8 at T 32, the whole
    head turned. The gate a scalar a head; without it, or without the
    window, the result is another."""
    cfg = _cfg()
    weights = ref.make_weights(cfg, 9)
    x = np.random.RandomState(layer).randn(2, 32, 64).astype("float32")
    got = _attention_layer(cfg, layer, x, weights)
    for row in range(2):
        want = _numpy_attention(cfg, layer, x[row], weights)
        np.testing.assert_allclose(got[row], want, rtol=2e-4, atol=2e-4)
        no_gate = _numpy_attention(cfg, layer, x[row], weights, gate=False)
        assert np.abs(no_gate - want).max() > 0.05 * np.abs(want).max()
    if layer == 2:
        no_window = _numpy_attention(cfg, layer, x[0], weights, window=False)
        assert np.abs(no_window - want).max() > 0.05 * np.abs(want).max()
        # the first `window` positions see the same keys either way
        want0 = _numpy_attention(cfg, layer, x[0], weights)
        np.testing.assert_allclose(no_window[:8], want0[:8], rtol=1e-9)


def test_the_gate_runs_in_float32_under_amp():
    """Under AMP the gate's logits come from a bf16 product; the sigmoid and
    the multiply on the kernel's result are float32 ops, and the output
    product takes the float32 result."""
    from paddle_tpu.contrib import mixed_precision as mp
    cfg = _cfg()
    with fluid.unique_name.guard():
        main, *_ = laguna.build_pretrain_program(
            _model_cfg(cfg), 2, 32, lambda: mp.decorate(
                fluid.optimizer.Adam(1e-3), dtype="bfloat16",
                use_dynamic_loss_scaling=False))
    block = main.global_block()
    gate_ops = [op for op in block.ops
                if op.attrs.get("__unit__") == "blk1/attn/gate"]
    kinds = [op.type for op in gate_ops]
    assert "sigmoid" in kinds and "elementwise_mul" in kinds
    for op in gate_ops:
        if op.type in ("sigmoid", "elementwise_mul"):
            for name in op.output_names():
                assert np.dtype(block.var(name).dtype) == np.float32, op.type
