"""LFM2 (`models/lfm2.py`) against its plain reference
(`benchmark/configs/lfm2_24b_a2b_reference.py`) at a small size on the CPU,
and the pieces the model forced, each against its written-out form: the gated
grouped product, the gated short convolution, the per-head q/k norm, the tied
head, and the shares of the experts adding up to the uncut layer."""
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from benchmark.configs import lfm2_24b_a2b_reference as ref
from paddle_tpu import layers
from paddle_tpu.models import lfm2
from paddle_tpu.parallel import moe

fa = importlib.import_module("paddle_tpu.ops.pallas_kernels.flash_attention")
A, C = "full_attention", "conv"


def _cfg(layer_types=(C, A, C, C, C), dense=1, experts=8, held=(2, 4),
         **over):
    cfg = {
        "hidden_size": 64, "layer_types": list(layer_types),
        "num_dense_layers": dense, "intermediate_size": 96,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "conv_L_cache": 3, "num_experts": held[1],
        "num_experts_published": experts, "experts_held": list(held),
        "num_experts_per_tok": 2, "moe_intermediate_size": 48,
        "routed_scaling_factor": 1.0, "norm_topk_prob": True,
        "use_expert_bias": True, "norm_eps": 1e-5, "vocab_size": 64,
        "rope_parameters": {"rope_theta": 1e6, "rope_type": "default"},
        "initializer_range": 0.2,
        "optimizer": {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.999,
                      "epsilon": 1e-8},
        "reference": {"follow_steps": 3, "head_rows": 16}}
    cfg.update(over)
    return cfg


def _model_cfg(cfg):
    return lfm2.Lfm2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=cfg["layer_types"],
        num_dense_layers=cfg["num_dense_layers"],
        intermediate_size=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        conv_L_cache=cfg["conv_L_cache"],
        num_experts=cfg["num_experts_published"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        experts_held=tuple(cfg["experts_held"]),
        initializer_range=cfg["initializer_range"])


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
        main, startup, _, loss, counters = lfm2.build_pretrain_program(
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


def _eager(op_type, inputs, attrs):
    import paddle_tpu.ops as ops
    return ops.eager_call(op_type, {k: [jnp.asarray(v) for v in vs]
                                    for k, vs in inputs.items()}, attrs)


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

def test_loss_and_every_gradient_leaf_against_the_reference():
    cfg = _cfg()
    main, loss, _, exe, scope = _program(cfg, lr=1e-3)
    weights = ref.make_weights(cfg, 5)
    params = main.global_block().all_parameters()
    assert sorted(p.name for p in params) == sorted(weights)
    assert ([p.name for p in params if not p.trainable]
            == [k for k in weights if k.endswith(ref.FROZEN)])
    for k, v in weights.items():
        scope.set_var(k, jnp.copy(v))
    (batch,) = _batches(cfg, 1)
    want_loss, want_grads = _reference_loss_and_grads(cfg, weights, batch)
    (got_loss,) = exe.run(main, feed=batch, fetch_list=[loss], scope=scope)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=2e-6)
    for k in weights:
        if k.endswith(ref.FROZEN):
            continue
        got = scope.find_var(f"{k}_AdamOptimizer_moment1") / 0.1
        want = want_grads[k]
        scale = max(float(jnp.abs(want).max()), 1e-6)
        assert float(jnp.abs(got - want).max()) < 2e-4 * scale, k


def test_three_adam_steps_follow_the_reference():
    cfg = _cfg()
    main, loss, counters, exe, scope = _program(cfg, lr=1e-3)
    batches = _batches(cfg, 3, seed=4)
    # with the routers' expert biases away from the zero they start at: the
    # program has to choose by score + bias, and leave the bias alone
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
    # four expert layers, each with its counters: nothing dropped
    assert [i for i, _, _ in counters] == [1, 2, 3, 4]
    for tokens, pairs in zip(out[1::2], out[2::2]):
        assert tokens.shape == (4,) and int(pairs) == tokens.sum()
    lfm2.record_moe_counters(counters, out[1:], 2 * 32, 2)
    from paddle_tpu.observability import get_registry
    series = {(s["name"], s["labels"].get("block")): s["value"]
              for s in get_registry().series() if s["name"].startswith("moe/")
              and "expert" not in s["labels"]}
    assert series[("moe/dropped", "blk1")] == 0
    assert series[("moe/pairs_routed", "blk1")] == 2 * 32 * 2
    assert series[("moe/pairs_held", "blk1")] == int(out[2])


def test_the_builder_reads_the_layer_types_and_counts_its_parameters():
    cfg = _cfg()
    mcfg = _model_cfg(cfg)
    n = sum(int(np.prod(shape)) for _, shape, _ in ref.weight_specs(cfg))
    assert lfm2.param_count(mcfg) == n
    main, *_ = lfm2.build_pretrain_program(mcfg, 2, 32)
    units = {op.attrs.get("__unit__") for op in main.global_block().ops}
    assert {"blk0/conv/in_proj", "blk0/conv/gate_in", "blk0/conv/filter",
            "blk0/conv/gate_out", "blk0/conv/out_proj", "blk0/mlp/gate_up",
            "blk0/mlp/act", "blk0/mlp/down", "blk1/attn/qkv",
            "blk1/attn/qk_norm", "blk1/attn/rope", "blk1/attn/kernel",
            "blk1/attn/o", "blk1/moe", "blk0/op_norm", "blk4/ffn_norm",
            "final_norm", "lm_head", "loss"} <= units
    # every layer is made again in the backward pass, all but what it keeps:
    # the in-projection's result or the q/k/v product with the kernel's
    # outputs; the dense gate/up product or the routing and its plan
    assert main.remat_policy == "full"
    produced_in = {n: op.attrs["__unit__"] for op in main.global_block().ops
                   for n in op.output_names()}
    kept = {block: ([produced_in[n] for n in names if n in produced_in],
                    [n for n in names if n not in produced_in])
            for block, names in main.remat_keep.items()}
    assert kept == {
        "blk0": (["blk0/conv/in_proj", "blk0/mlp/gate_up"], []),
        "blk1": (["blk1/attn/qkv"], list(fa.KEPT) + list(moe.KEPT)),
        **{f"blk{i}": ([f"blk{i}/conv/in_proj"], list(moe.KEPT))
           for i in (2, 3, 4)}}
    with pytest.raises(ValueError, match="unknown operator"):
        lfm2.build_pretrain_program(
            _model_cfg(_cfg(layer_types=(C, "sliding"))), 2, 32)
    # the published model, whole and tied: 23.84B, 2.33B touched a token
    whole = lfm2.Lfm2Config()
    assert lfm2.param_count(whole) == pytest.approx(23.84e9, rel=1e-3)
    active = lfm2.param_count(lfm2.Lfm2Config(experts_held=(0, 4)))
    assert active == pytest.approx(2.33e9, rel=5e-3)
    # the cell's cut: 647.8M
    cut = lfm2.Lfm2Config(
        vocab_size=8192, layer_types=[C, A, C, C, C, A, C],
        num_dense_layers=1, experts_held=(0, 8))
    assert lfm2.param_count(cut) == 647_819_904


@pytest.mark.parametrize("policy", ["kept", "full"])
def test_remat_blocks_give_the_same_step(policy):
    cfg = _cfg()
    weights = ref.make_weights(cfg, 3)
    (batch,) = _batches(cfg, 1, seed=2)
    results = []
    for remat in (False, True):
        main, loss, _, exe, scope = _program(cfg, lr=1e-3)
        if not remat:
            main.remat_policy = None
        elif policy == "full":
            main.remat_keep.clear()
        for k, v in weights.items():
            scope.set_var(k, jnp.copy(v))
        (got,) = exe.run(main, feed=batch, fetch_list=[loss], scope=scope)
        results.append((float(got), {
            k: np.asarray(scope.find_var(k)) for k in weights}))
    assert results[0][0] == pytest.approx(results[1][0], rel=1e-6)
    for k in weights:
        np.testing.assert_allclose(results[0][1][k], results[1][1][k],
                                   rtol=1e-4, atol=1e-6, err_msg=k)


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------

def test_two_halves_of_the_experts_give_the_uncut_layer():
    """The parts the two halves of the experts give, added, equal the uncut
    reference's layer (there is no shared expert to count once), and the
    program's layer gives each half's part."""
    cfg = _cfg(experts=8, held=(0, 8))
    full = ref.make_weights(cfg, 9)
    p, t = "blk1", 40
    x = jax.random.normal(jax.random.PRNGKey(1), (t, cfg["hidden_size"]))
    whole = ref.experts_ffn(x, full, p, cfg, held=(0, 8))
    parts = []
    for first in (0, 4):
        share = dict(full)
        for leaf in ("w1", "w3", "w2"):
            share[f"{p}.moe.{leaf}"] = full[f"{p}.moe.{leaf}"][first:first + 4]
        part = ref.experts_ffn(x, share, p, cfg, held=(first, 4))
        got = moe.moe_ffn(
            x, full[f"{p}.moe.gate"], share[f"{p}.moe.w1"], None,
            share[f"{p}.moe.w2"], None, k=2, act=jax.nn.silu,
            experts_held=(first, 4), scoring="sigmoid",
            correction_bias=full[f"{p}.moe.corr_bias"],
            w3=share[f"{p}.moe.w3"])
        np.testing.assert_allclose(got.y, part, rtol=1e-5, atol=1e-6)
        parts.append(part)
    np.testing.assert_allclose(parts[0] + parts[1], whole, rtol=1e-5,
                               atol=1e-6)
    assert float(jnp.abs(parts[0]).max()) > 0 < float(
        jnp.abs(parts[1]).max())


# ---------------------------------------------------------------------------
# the gated grouped product
# ---------------------------------------------------------------------------

def _literal_pairs(x, idx, weight, w1, w3, w2, first):
    """A literal loop over the (token, expert) pairs."""
    y = [jnp.zeros(x.shape[1])] * x.shape[0]
    for n in range(x.shape[0]):
        for j in range(idx.shape[1]):
            e = int(idx[n, j]) - first
            if 0 <= e < w1.shape[0]:
                h = jax.nn.silu(x[n] @ w1[e]) * (x[n] @ w3[e])
                y[n] = y[n] + weight[n, j] * (h @ w2[e])
    return jnp.stack(y)


@pytest.mark.parametrize("routing", ["mixed", "one_expert_all",
                                     "one_expert_none"])
def test_gated_grouped_product_against_a_literal_loop(routing):
    n, d, h, e, k, first = 24, 16, 12, 4, 2, 1
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (n, d))
    w1, w3 = (jax.random.normal(q, (e, d, h)) * 0.3 for q in ks[1:3])
    w2 = jax.random.normal(ks[3], (e, h, d)) * 0.3
    weight = jax.random.uniform(ks[4], (n, k))
    if routing == "mixed":          # experts 0..5 of the layer, 1..4 held
        idx = jax.random.randint(ks[5], (n, k), 0, 6)
    elif routing == "one_expert_all":
        idx = jnp.stack([jnp.full((n,), 2), jnp.full((n,), 5)], axis=1)
    else:                           # held expert 3 gets no token
        idx = jnp.stack([jnp.full((n,), 1), jnp.arange(n) % 2 * 2 + 2],
                        axis=1)
        idx = jnp.where(idx == 3, 5, idx)
    idx = idx.astype(jnp.int32)

    def grouped(x, weight, w1, w3, w2):
        r = moe.Routing(idx, weight, jnp.zeros(()))
        y, tokens, pairs = moe.experts_ffn(x, r, w1, None, w2, None, first,
                                           jax.nn.silu, tile=8, w3=w3)
        return y, tokens, pairs

    y, tokens, pairs = grouped(x, weight, w1, w3, w2)
    want = _literal_pairs(x, idx, weight, w1, w3, w2, first)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    counts = [int(jnp.sum(idx == first + j)) for j in range(e)]
    assert list(np.asarray(tokens)) == counts and int(pairs) == sum(counts)
    if routing == "one_expert_all":
        assert counts == [0, n, 0, 0]
    if routing == "one_expert_none":
        assert counts[2] == 0 and min(counts[0], counts[1]) > 0
    ct = jax.random.normal(jax.random.PRNGKey(7), y.shape)
    got = jax.grad(lambda *a: jnp.sum(grouped(*a)[0] * ct),
                   argnums=(0, 1, 2, 3, 4))(x, weight, w1, w3, w2)
    lit = jax.grad(lambda x, wt, a, b, c: jnp.sum(
        _literal_pairs(x, idx, wt, a, b, c, first) * ct),
        argnums=(0, 1, 2, 3, 4))(x, weight, w1, w3, w2)
    for g, w, name in zip(got, lit, ("x", "weight", "w1", "w3", "w2")):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-6, err_msg=name)


def test_the_plain_form_is_untouched_by_the_gated_one():
    """Without `w3` the layer is act(x W1) W2 as before, and with it
    another layer; `init_moe_params` draws the third matrix on request."""
    p = moe.init_moe_params(jax.random.PRNGKey(2), 16, 12, 4, gated=True)
    gw, w1, b1, w2, b2, w3 = p
    assert w3.shape == w1.shape and not np.allclose(w3, w1)
    assert len(moe.init_moe_params(jax.random.PRNGKey(2), 16, 12, 4)) == 5
    x = jax.random.normal(jax.random.PRNGKey(3), (20, 16))
    plain = moe.moe_ffn(x, gw, w1, None, w2, None, k=2, act=jax.nn.silu)
    r = moe.route(x, gw, 2)
    want = sum(
        jnp.sum(jnp.where(r.idx == e, r.weight, 0.0), -1)[:, None]
        * (jax.nn.silu(x @ w1[e]) @ w2[e]) for e in range(4))
    np.testing.assert_allclose(plain.y, want, rtol=1e-5, atol=1e-6)
    gated = moe.moe_ffn(x, gw, w1, None, w2, None, k=2, act=jax.nn.silu,
                        w3=w3)
    assert not np.allclose(gated.y, plain.y, atol=1e-3)


def test_the_router_s_guard_is_within_a_millionth_of_the_published_normaliser():
    # the published weights are s / (sum of the chosen s + 1e-6); `route`
    # divides by max(sum, 1e-20): a departure `assumed.router` states
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 8))
    gw = jax.random.normal(jax.random.PRNGKey(1), (8, 5))
    got = moe.route(x, gw, 2, "sigmoid")
    s = jax.nn.sigmoid(x @ gw)
    top = jnp.take_along_axis(s, got.idx, -1)
    total = top.sum(-1, keepdims=True)
    np.testing.assert_allclose(got.weight, top / total, rtol=1e-6)
    np.testing.assert_allclose(got.weight, top / (total + 1e-6), rtol=1e-5)
    assert float(jnp.max(1e-6 / total)) < 5e-6


# ---------------------------------------------------------------------------
# the gated short convolution
# ---------------------------------------------------------------------------

def _conv_layer(d=8, t=6, k=3, seed=0):
    """The model's conv operator as a program of its own: (run(x) -> out,
    weights by name)."""
    cfg = lfm2.Lfm2Config(hidden_size=d, conv_L_cache=k,
                          initializer_range=0.5)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", [t, d], dtype="float32")
        with fluid.core.program.unit("blk0", remat=True):
            out = lfm2.short_conv(cfg, x, "blk0")
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    names = ("blk0.in_proj.w", "blk0.conv.w", "blk0.out_proj.w")
    weights = {n: np.asarray(scope.find_var(n)) for n in names}

    def run(value):
        return exe.run(main, feed={"x": value}, fetch_list=[out],
                       scope=scope)[0]
    return run, weights


def test_short_conv_against_its_written_out_sum():
    d, t = 8, 6
    run, w = _conv_layer(d, t)
    assert w["blk0.conv.w"].shape == (d, 3)
    assert np.abs(w["blk0.conv.w"]).max() <= 1 / np.sqrt(3)
    x = np.random.RandomState(0).randn(2, t, d).astype("float32")
    got = run(x)
    bcx = x @ w["blk0.in_proj.w"]
    b, c, xs = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    u = b * xs
    f = w["blk0.conv.w"]
    v = np.zeros_like(u)
    for pos in range(t):            # written out, position by position
        v[:, pos] = f[:, 2] * u[:, pos]
        if pos >= 1:
            v[:, pos] += f[:, 1] * u[:, pos - 1]
        if pos >= 2:
            v[:, pos] += f[:, 0] * u[:, pos - 2]
    want = (c * v) @ w["blk0.out_proj.w"]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the first two positions see one and two taps, and nothing later
    later = x.copy()
    later[:, 2:] += 1.0
    np.testing.assert_allclose(run(later)[:, :2], got[:, :2], rtol=1e-6)
    # and the reference's operator is the same sum
    params = {k: jnp.asarray(a) for k, a in w.items()}
    ref_out = ref.conv_operator(jnp.asarray(x[0]), params, "blk0", {})
    np.testing.assert_allclose(ref_out, want[0], rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# attention's q/k norm, and the tied head
# ---------------------------------------------------------------------------

def test_q_k_norm_per_head_with_one_shared_weight():
    heads, hd, t = 3, 4, 5
    cfg = lfm2.Lfm2Config(hidden_size=heads * hd, num_heads=heads)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        v = layers.data("v", [t, heads * hd], dtype="float32")
        out = lfm2._head_norm(cfg, v, heads, "qn")
    # one weight [head_dim] for all heads, and the packed shape back
    assert main.global_block().vars["qn"].shape == (hd,)
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    w = np.linspace(0.5, 2.0, hd).astype("float32")
    with fluid.scope_guard(scope):
        exe.run(startup)
        scope.set_var("qn", jnp.asarray(w))
    x = np.random.RandomState(0).randn(2, t, heads * hd).astype("float32")
    (got,) = exe.run(main, feed={"v": x}, fetch_list=[out], scope=scope)
    xh = x.reshape(2, t, heads, hd)
    want = xh / np.sqrt((xh ** 2).mean(-1, keepdims=True) + 1e-5) * w
    np.testing.assert_allclose(got, want.reshape(x.shape), rtol=1e-5)
    # and the reference's per-head norm is the same
    ref_out = ref.rms_norm(jnp.asarray(xh[0]), jnp.asarray(w), 1e-5)
    np.testing.assert_allclose(ref_out.reshape(t, -1), got[0], rtol=1e-5)


def _tied_program(v=16, d=8, t=6, tied=True):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        ids = layers.data("ids", [t], dtype="int64")
        labels = layers.data("labels", [t, 1], dtype="int64")
        table = fluid.ParamAttr(
            name="embed.w",
            initializer=fluid.initializer.NormalInitializer(0.0, 0.5))
        x = layers.embedding(ids, [v, d], param_attr=table)
        head = table if tied else fluid.ParamAttr(name="head.w")
        per_token = layers.linear_softmax_with_cross_entropy(
            x, labels, v, param_attr=head, bias_attr=False, tied_table=True)
        loss = layers.reduce_mean(per_token)
        fluid.optimizer.Adam(1e-3).minimize(loss)
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    return main, loss, exe, scope


def test_the_tied_table_s_gradient_is_lookups_plus_projections():
    v, d, t = 16, 8, 6
    rng = np.random.RandomState(1)
    feed = {"ids": rng.randint(0, v, (2, t)).astype("int64"),
            "labels": rng.randint(0, v, (2, t, 1)).astype("int64")}
    main, loss, exe, scope = _tied_program(v, d, t)
    params = main.global_block().all_parameters()
    assert [p.name for p in params] == ["embed.w"]       # one Parameter
    assert tuple(params[0].shape) == (v, d)
    slots = [n for n in scope.var_names() if "moment1" in n]
    assert slots == ["embed.w_AdamOptimizer_moment1"]    # one Adam slot
    table = jnp.asarray(np.asarray(scope.find_var("embed.w")))
    (got_loss,) = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    got = scope.find_var("embed.w_AdamOptimizer_moment1") / 0.1

    def ce(lookup_table, head_table):
        x = lookup_table[feed["ids"]]
        logp = jax.nn.log_softmax(x @ head_table.T, axis=-1)
        picked = jnp.take_along_axis(logp, jnp.asarray(feed["labels"]), -1)
        return -jnp.mean(picked)

    want_loss = ce(table, table)
    g_lookup, g_head = jax.grad(ce, argnums=(0, 1))(table, table)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert float(jnp.abs(g_lookup).max()) > 0 < float(jnp.abs(g_head).max())
    np.testing.assert_allclose(got, g_lookup + g_head, rtol=1e-4, atol=1e-7)
    # untied, the same two gradients go to two parameters
    main2, loss2, exe2, scope2 = _tied_program(v, d, t, tied=False)
    scope2.set_var("embed.w", jnp.copy(table))
    scope2.set_var("head.w", jnp.copy(table))
    exe2.run(main2, feed=feed, fetch_list=[loss2], scope=scope2)
    np.testing.assert_allclose(
        scope2.find_var("embed.w_AdamOptimizer_moment1") / 0.1, g_lookup,
        rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(
        scope2.find_var("head.w_AdamOptimizer_moment1") / 0.1, g_head,
        rtol=1e-4, atol=1e-7)


def test_a_tied_table_of_another_shape_is_refused():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        ids = layers.data("ids", [4], dtype="int64")
        labels = layers.data("labels", [4, 1], dtype="int64")
        x = layers.embedding(ids, [16, 8],
                             param_attr=fluid.ParamAttr(name="embed.w"))
        with pytest.raises(ValueError, match="exists with shape"):
            layers.linear_softmax_with_cross_entropy(
                x, labels, 16, param_attr=fluid.ParamAttr(name="embed.w"),
                bias_attr=False)


@pytest.mark.parametrize("hq,hk,hd", [(4, 2, 16), (4, 1, 64)])
def test_the_rotation_runs_over_q_and_k_in_one_tensor(hq, hk, hd):
    """40 heads of 64 in one tensor is the rotation of q's 32 and k's 8
    apart (here 4 + 2 heads of 16, and 4 + 1 of the cell's 64: two heads a
    128-lane tile and half a tile left over), and the reference's
    rotate-half."""
    t = 10
    rng = np.random.RandomState(0)
    q = rng.randn(1, t, hq * hd).astype("float32")
    k = rng.randn(1, t, hk * hd).astype("float32")
    rot = functools.partial(_eager, "rotary_embedding")
    (both,) = rot({"X": [np.concatenate([q, k], -1)]},
                  {"num_heads": hq + hk, "theta": 1e6})["Out"]
    (q_only,) = rot({"X": [q]}, {"num_heads": hq, "theta": 1e6})["Out"]
    np.testing.assert_allclose(both[..., :hq * hd], q_only, rtol=1e-6)
    want = ref.rotate_half(jnp.asarray(k[0]).reshape(t, hk, hd), 1e6)
    np.testing.assert_allclose(both[0, :, hq * hd:], want.reshape(t, -1),
                               rtol=1e-5, atol=1e-6)
