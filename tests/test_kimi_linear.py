"""Kimi Linear (`models/kimi_linear.py`) against its plain reference
(`benchmark/configs/kimi_linear_48b_a3b_reference.py`, whose delta rule is the
token-by-token recurrence) at a small size on the CPU, and the pieces the
model forced, each against its written-out form: the KDA mixer, latent
attention without position and with a direct query product, the decay
floor's gauge, the 32 shares of an expert layer adding up to the uncut
layer, the parameter counts, and JoyAI-Flash's latent attention traced to the
parent's jaxpr."""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from benchmark.configs import kimi_linear_48b_a3b_reference as ref
from paddle_tpu import layers
from paddle_tpu.models import joyai_flash as jf
from paddle_tpu.models import kimi_linear as kl
from paddle_tpu.ops import linear_attn_ops
from paddle_tpu.parallel import moe


# layers (from 1): KDA + dense MLP; KDA, latent attention, KDA with experts
def _cfg(experts=8, held=(0, 8), **over):
    cfg = {
        "hidden_size": 64, "num_hidden_layers": 4,
        "first_k_dense_replace": 1, "intermediate_size": 96,
        "linear_attn_config": {
            "kda_layers": [1, 2, 4, 5, 6], "full_attn_layers": [3, 7],
            "head_dim": 16, "num_heads": 2, "short_conv_kernel_size": 4},
        "kda_gate_rank": 16, "kda_chunk": 16,
        "num_attention_heads": 4, "q_lora_rank": None, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "mla_use_nope": True,
        "num_experts": held[1], "num_experts_published": experts,
        "experts_held": list(held), "num_experts_per_token": 2,
        "moe_intermediate_size": 32, "num_shared_experts": 1,
        "routed_scaling_factor": 2.446, "moe_renormalize": True,
        "rms_norm_eps": 1e-5, "vocab_size": 96, "initializer_range": 0.2,
        "optimizer": {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.999,
                      "epsilon": 1e-8},
        "reference": {"follow_steps": 3, "head_rows": 16}}
    cfg.update(over)
    return cfg


def _model_cfg(cfg):
    lin = cfg["linear_attn_config"]
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "first_k_dense_replace", "intermediate_size",
            "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "num_experts_per_token",
            "moe_intermediate_size", "num_shared_experts",
            "routed_scaling_factor", "moe_renormalize", "rms_norm_eps",
            "initializer_range", "kda_gate_rank", "kda_chunk")
    return kl.KimiLinearConfig(
        kda_layers=lin["kda_layers"], full_attn_layers=lin["full_attn_layers"],
        kda_num_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        short_conv_kernel_size=lin["short_conv_kernel_size"],
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
        main, startup, _, loss, counters, floors = kl.build_pretrain_program(
            _model_cfg(cfg), b, t, opt)
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    return main, loss, counters, floors, exe, scope


def _lowered(form):
    from paddle_tpu.observability import get_registry
    return sum(s["value"] for s in get_registry().series()
               if s["name"] == "ops/kda_lowered"
               and s["labels"].get("path") == form)


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

@pytest.mark.parametrize("form", ["einsum", "pallas"])
def test_loss_and_every_gradient_leaf_against_the_reference(form,
                                                            monkeypatch):
    """float32 against float32: the chunked rule against the recurrence, the
    grouped product against a loop over experts, the flash form against the
    full softmax. 2e-6 on the loss and 2e-4 of a leaf's largest entry are
    rounding's (the largest read here: 3e-5 of a leaf). "pallas": the rule's
    kernels (ops/pallas_kernels/kda_chunk.py) through the interpreter at a
    shape they take, two heads of 128 over one tile of two chunks of 64: the
    gate's parameters, the floor fetched with the loss and the remat block
    making the kernel's forward again."""
    from paddle_tpu.ops.pallas_kernels import kda_chunk

    cfg, shape = _cfg(), {}
    if form == "pallas":
        monkeypatch.setattr(kda_chunk, "FORCE_PALLAS_INTERPRET", True)
        cfg = _cfg(kda_chunk=64, linear_attn_config=dict(
            _cfg()["linear_attn_config"], head_dim=128))
        shape = {"b": 1, "t": 128}
    before = _lowered(form)
    main, loss, _, floors, exe, scope = _program(cfg, lr=1e-3, **shape)
    weights = ref.make_weights(cfg, 5)
    params = main.global_block().all_parameters()
    assert sorted(p.name for p in params) == sorted(weights)
    assert ([p.name for p in params if not p.trainable]
            == [k for k in weights if k.endswith(ref.FROZEN)])
    _set(scope, weights)
    (batch,) = _batches(cfg, 1, **shape)
    want_loss, want_grads = jax.value_and_grad(
        lambda p: _reference_loss(cfg, p, batch))(weights)
    got_loss, *got_floors = exe.run(
        main, feed=batch, fetch_list=[loss] + [f for _, f in floors],
        scope=scope)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=2e-6)
    assert all(float(f) < 0 for f in got_floors) and len(got_floors) == 3
    # each KDA layer's op lowered by the form asked for, forward and again
    # behind its remat block
    assert _lowered(form) >= before + 3
    for k in weights:
        if k.endswith(ref.FROZEN):
            continue
        got, want = _moment_grad(scope, k), want_grads[k]
        scale = max(float(jnp.abs(want).max()), 1e-6)
        assert float(jnp.abs(got - want).max()) < 2e-4 * scale, k


def test_three_adam_steps_follow_the_reference():
    cfg = _cfg()
    main, loss, counters, floors, exe, scope = _program(cfg, lr=1e-3)
    batches = _batches(cfg, 3, seed=4)
    # with the routers' biases away from the zero they start at: the program
    # has to choose by score + bias, and leave the bias alone
    weights = ref.make_weights(cfg, 11)
    rng = np.random.default_rng(5)
    biases = {k: rng.normal(0, 0.05, v.shape).astype("float32")
              for k, v in weights.items() if k.endswith(ref.FROZEN)}
    assert sorted(biases) == [f"blk{i}.moe.corr_bias" for i in (1, 2, 3)]
    weights.update({k: jnp.asarray(b) for k, b in biases.items()})
    _set(scope, weights)
    want = ref.follow(cfg, weights, batches)
    trained = [k for k in weights if not k.endswith(ref.FROZEN)]
    assert sorted(want["grad_norms"]) == sorted(trained)
    fetch = ([loss] + [v for _, t, p in counters for v in (t, p)]
             + [f for _, f in floors])
    losses = []
    for i, batch in enumerate(batches):
        out = exe.run(main, feed=batch, fetch_list=fetch, scope=scope)
        losses.append(float(out[0]))
        if i == 0:
            grad_norms = {k: float(jnp.linalg.norm(_moment_grad(scope, k)))
                          for k in trained}
    np.testing.assert_allclose(losses, want["losses"], rtol=2e-6)
    for k, b in biases.items():       # no optimizer touched them
        assert np.array_equal(np.asarray(scope.find_var(k)), b)
    for k in trained:
        assert grad_norms[k] == pytest.approx(want["grad_norms"][k],
                                              rel=1e-4, abs=1e-7), k
        moved = float(jnp.linalg.norm(scope.find_var(k) - weights[k]))
        assert moved == pytest.approx(want["update_norms"][k], rel=2e-3), k
    # three expert layers with their counters, three KDA layers with floors
    assert [i for i, _, _ in counters] == [1, 2, 3]
    assert [i for i, _ in floors] == [0, 1, 3]
    for tokens, pairs in zip(out[1:7:2], out[2:7:2]):
        assert tokens.shape == (8,) and int(pairs) == tokens.sum()
    kl.record_counters(counters, floors, out[1:], 2 * 32, 2)
    from paddle_tpu.observability import get_registry
    series = {(s["name"], s["labels"].get("block")): s["value"]
              for s in get_registry().series()
              if s["name"].startswith(("moe/", "kda/"))
              and "expert" not in s["labels"]}
    assert series[("moe/dropped", "blk1")] == 0
    assert series[("moe/pairs_routed", "blk3")] == 2 * 32 * 2
    # the gauge is the worst of the three layers' floors, and what the
    # reference's own gate gives for them
    assert series[("kda/decay_floor", None)] == pytest.approx(
        min(float(f) for f in out[7:]))
    # (weights drawn at a scale of 0.2 make the gates' logits large: a
    # chunk of 16 positions reaches -73 here, a strong decay)
    assert -200 < series[("kda/decay_floor", None)] < -1


def test_the_decay_floor_is_the_reference_gate_s_worst_chunk_sum():
    cfg = _cfg()
    main, _, _, floors, exe, scope = _program(cfg)
    weights = ref.make_weights(cfg, 2)
    _set(scope, weights)
    (batch,) = _batches(cfg, 1, seed=1)
    (got,) = exe.run(main, feed=batch, fetch_list=[floors[0][1]], scope=scope)
    worst = 0.0
    for row in batch["ids"]:
        x = ref.rms_norm(weights["embed.w"][row], weights["blk0.op_norm.w"],
                         cfg["rms_norm_eps"])
        g = ref.kda_decay(x, weights, "blk0", cfg)           # [T, H, K]
        sums = g.reshape(-1, cfg["kda_chunk"], *g.shape[1:]).sum(1)
        worst = min(worst, float(sums.min()))
    assert float(got) == pytest.approx(worst, rel=1e-5)


def test_the_builder_names_its_units_and_counts_its_parameters():
    cfg = _cfg()
    mcfg = _model_cfg(cfg)
    n = sum(int(np.prod(shape)) for name, shape, _ in ref.weight_specs(cfg)
            if not name.endswith(ref.FROZEN))
    assert kl.param_count(mcfg) == n
    main, *_ = kl.build_pretrain_program(mcfg, 2, 32)
    units = {op.attrs.get("__unit__") for op in main.global_block().ops}
    # (no `l2`: q's and k's normalisation and the decay's softplus are made
    # inside the rule's op, a group of chunks at a time)
    kda = ("q", "k", "v", "conv", "decay", "beta", "rule", "out_gate",
           "out_norm", "o")
    attn = ("q_b", "kv_a", "kv_norm", "kv_b", "assemble", "kernel", "o")
    assert ({"embed", "final_norm", "lm_head", "loss", "blk0/op_norm",
             "blk0/ffn_norm", "blk0/mlp/gate_up", "blk0/mlp/act",
             "blk0/mlp/down", "blk1/moe", "blk1/moe/shared/gate_up",
             "blk1/moe/shared/down", "blk1/moe/combine"}
            | {f"{b}/kda/{p}" for b in ("blk0", "blk1", "blk3") for p in kda}
            | {f"blk2/attn/{p}" for p in attn}) <= units
    # no rotation, no query latent: the units are not there to be read
    assert not [u for u in units if u and ("/rope" in u or "/q_a" in u
                                           or "/q_norm" in u)]
    assert not [op for op in main.global_block().ops
                if op.type == "rotary_embedding"]
    # every layer is made again in the backward pass, all but what it keeps:
    # a KDA layer its three projections' results before the filters and the
    # three narrow products; the rule's result and states are not asked for
    assert main.remat_policy == "full"
    produced_in = {n: op.attrs.get("__unit__")
                   for op in main.global_block().ops
                   for n in op.output_names()}
    kept = {block: [produced_in.get(n, n) for n in names]
            for block, names in main.remat_keep.items()}
    kda_kept = lambda b: [f"{b}/kda/{p}" for p in (
        "q", "k", "v", "decay", "beta", "out_gate")]
    fa_kept = list(jf._ATTN_KEPT)
    assert kept == {
        "blk0": kda_kept("blk0") + ["blk0/mlp/gate_up"],
        "blk1": kda_kept("blk1") + list(moe.KEPT)
        + ["blk1/moe/shared/gate_up"],
        "blk2": ["blk2/attn/kv_a"] + fa_kept + list(moe.KEPT)
        + ["blk2/moe/shared/gate_up"],
        "blk3": kda_kept("blk3") + list(moe.KEPT)
        + ["blk3/moe/shared/gate_up"]}
    assert not set(linear_attn_ops.KEPT) & {
        n for names in main.remat_keep.values() for n in names}
    with pytest.raises(ValueError, match="both or neither"):
        kl.build_pretrain_program(
            kl.KimiLinearConfig(num_hidden_layers=2, kda_layers=[1]), 2, 32)


def test_parameter_counts_of_the_published_model_and_of_the_cut():
    """ISSUE 47's table, term by term, from the published widths."""
    d, wide = 2304, 32 * 128
    kda = (3 * d * wide + 3 * wide * 4 + d * 128 + 128 * wide + wide + 32
           + d * 32 + d * 128 + 128 * wide + 128 + wide * d)
    assert kda == 39_514_272
    mla = d * 6144 + d * 576 + 512 + 512 * 8192 + 4096 * d
    assert mla == 29_114_880
    dense, expert, router, norms = 3 * d * 9216, 3 * d * 1024, d * 256, 2 * d
    assert (dense, expert, router) == (63_700_992, 7_077_888, 589_824)
    whole = kl.KimiLinearConfig()
    assert kl.param_count(whole) == 49_122_675_072 == (
        20 * kda + 7 * mla + 27 * norms + dense
        + 26 * (router + 257 * expert) + 2 * 163840 * d + d)
    # a token passes through 8 routed experts and the shared one a layer;
    # the embedding's lookup is not counted: the published "A3B"
    assert kl.param_count(whole, touched=True) == 3_106_965_888 == (
        20 * kda + 7 * mla + 27 * norms + dense
        + 26 * (router + 9 * expert) + 163840 * d + d)
    cut = kl.KimiLinearConfig(vocab_size=20480, num_hidden_layers=5,
                              experts_held=(0, 8))
    layer1 = kda + dense + norms
    kda_moe = kda + router + 9 * expert + norms
    mla_moe = mla + router + 9 * expert + norms
    assert (layer1, kda_moe, mla_moe) == (103_219_872, 103_809_696,
                                          93_410_304)
    assert kl.param_count(cut) == 602_433_408 == (
        layer1 + 3 * kda_moe + mla_moe + 2 * 47_185_920 + d)
    assert 16 * 602_433_408 / 2 ** 30 == pytest.approx(8.98, abs=5e-3)
    # the published lists: 20 KDA layers and 7 of latent attention, 3 : 1
    assert len(whole.kda_layers) == 20 and whole.full_attn_layers == [
        4, 8, 12, 16, 20, 24, 27]
    assert [whole.is_kda(i) for i in range(5)] == [True, True, True, False,
                                                   True]


@pytest.mark.parametrize("policy", ["kept", "full"])
def test_remat_blocks_give_the_same_step(policy):
    cfg = _cfg()
    weights = ref.make_weights(cfg, 3)
    (batch,) = _batches(cfg, 1, seed=2)
    results = []
    for remat in (False, True):
        main, loss, _, _, exe, scope = _program(cfg, lr=1e-3)
        if not remat:
            main.remat_policy = None
        elif policy == "full":
            main.remat_keep.clear()
        _set(scope, weights)
        (got,) = exe.run(main, feed=batch, fetch_list=[loss], scope=scope)
        results.append((float(got), {
            k: np.asarray(scope.find_var(k)) for k in weights}))
    assert results[0][0] == pytest.approx(results[1][0], rel=1e-6)
    # Adam's first step moves an entry by 1e-3 g / (|g| + 1e-8): where an
    # expert's gradient entry is of epsilon's order (one of 16,384 here, 3e-5
    # apart) the step follows the last bits of g; a tenth of a step is far
    # under what a wrong recomputation would move
    for k in weights:
        np.testing.assert_allclose(results[0][1][k], results[1][1][k],
                                   rtol=1e-4, atol=1e-4, err_msg=k)


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------

def test_the_32_shares_of_an_expert_layer_and_one_shared_expert_give_the_layer():
    """The deployment of the cell at a small size: 256 routed experts, top-8,
    32 chips of 8 experts each. The parts the 32 shares give, added to the
    shared expert's counted once, equal the uncut reference's layer; the
    program's grouped product gives each share's routed part."""
    cfg = _cfg(experts=256, held=(0, 256), num_experts_per_token=8,
               moe_intermediate_size=8)
    full = ref.make_weights(cfg, 9)
    p, t = "blk1", 48
    x = jax.random.normal(jax.random.PRNGKey(1), (t, cfg["hidden_size"]))
    whole = ref.experts_ffn(x, full, p, cfg)
    shared = ref.shared_expert(x, full, p, cfg)
    total, busy = jnp.zeros_like(whole), 0
    for first in range(0, 256, 8):
        share = dict(full)
        for leaf in ("w1", "w3", "w2"):
            share[f"{p}.moe.{leaf}"] = full[f"{p}.moe.{leaf}"][first:first + 8]
        part = ref.routed_experts(x, share, p, cfg, held=(first, 8))
        got = moe.moe_ffn(
            x, full[f"{p}.moe.gate"], share[f"{p}.moe.w1"], None,
            share[f"{p}.moe.w2"], None, k=8, act=jax.nn.silu,
            experts_held=(first, 8), scoring="sigmoid",
            correction_bias=full[f"{p}.moe.corr_bias"], routed_scaling=2.446,
            w3=share[f"{p}.moe.w3"])
        np.testing.assert_allclose(got.y, part, rtol=1e-5, atol=1e-5)
        total, busy = total + part, busy + bool(jnp.abs(part).max() > 0)
    np.testing.assert_allclose(total + shared, whole, rtol=1e-5, atol=2e-5)
    assert busy > 16 and float(jnp.abs(shared).max()) > 0
    # each chip's layer output, summed, would count the shared expert 32 times
    assert float(jnp.abs(total + 32 * shared - whole).max()) > 0


# ---------------------------------------------------------------------------
# the mixers against their written-out forms
# ---------------------------------------------------------------------------

def _mixer(cfg, build, t=32, seed=0):
    """One of the program's mixers alone on x [1, T, D]: (x, the weights it
    made, its output)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", [t, cfg["hidden_size"]], dtype="float32")
        with fluid.core.program.unit("blk0", remat=True):
            out = build(x)
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    return main, out, exe, scope


def test_the_kda_mixer_against_its_written_out_form():
    cfg = _cfg()
    main, (out, floor), exe, scope = _mixer(
        cfg, lambda x: kl.kda_attention(_model_cfg(cfg), x, "blk0"))
    weights = {k: v for k, v in ref.make_weights(cfg, 0).items()
               if scope.has_var(k)}
    assert len(weights) == 15
    _set(scope, weights)
    x = np.random.RandomState(0).randn(1, 32, 64).astype("float32")
    got, _ = exe.run(main, feed={"x": x}, fetch_list=[out, floor],
                     scope=scope)
    np.testing.assert_allclose(np.asarray(got)[0],
                               ref.kda(x[0], weights, "blk0", cfg),
                               rtol=2e-5, atol=2e-6)


def test_the_builder_s_own_initial_decays_are_the_stated_ranges():
    mcfg = kl.KimiLinearConfig()
    a_log, dt_bias = kl.kda_defaults(mcfg)
    assert a_log.shape == (32,) and dt_bias.shape == (4096,)
    np.testing.assert_allclose(np.exp(a_log[[0, -1]]), [1.0, 16.0], rtol=1e-6)
    dt = np.log1p(np.exp(dt_bias.astype(np.float64)))
    np.testing.assert_allclose(dt[[0, -1]], [1e-3, 1e-1], rtol=1e-4)
    # a step's log-decay then lies in [-1.6, -1e-3]; a chunk's sum above -103
    assert -16.0 * 0.1 * 64 > -103


def test_latent_attention_without_position_against_its_written_out_form():
    """q straight from x, nothing rotated, the one shared 64-wide key head
    kept: the reference's literal heads."""
    cfg = _cfg()
    main, out, exe, scope = _mixer(
        cfg, lambda x: jf.latent_attention(_model_cfg(cfg), x, "blk2"), t=16)
    weights = {k: v for k, v in ref.make_weights(cfg, 0).items()
               if scope.has_var(k)}
    assert sorted(weights) == ["blk2.kv_a.w", "blk2.kv_a_norm.w",
                               "blk2.kv_b.w", "blk2.o.w", "blk2.q.w"]
    _set(scope, weights)
    x = np.random.RandomState(0).randn(1, 16, 64).astype("float32")
    (got,) = exe.run(main, feed={"x": x}, fetch_list=[out], scope=scope)
    np.testing.assert_allclose(
        np.asarray(got)[0], ref.latent_attention(x[0], weights, "blk2", cfg),
        rtol=2e-5, atol=2e-6)
    # the shared key head matters: without it the result is another
    zeroed = dict(weights)
    zeroed["blk2.kv_a.w"] = weights["blk2.kv_a.w"].at[:, 16:].set(0.0)
    assert float(jnp.abs(
        ref.latent_attention(x[0], zeroed, "blk2", cfg)
        - np.asarray(got)[0]).max()) > 1e-3


def test_joyai_s_latent_attention_traces_to_the_parent_s_jaxpr():
    """`latent_attention` with a query latent (q_lora_rank 1,536) and the
    rotation on, at JoyAI-Flash's published sizes and T 256, forward and
    backward: the jaxpr of the parent commit (93609cf, read from a copy of
    it), to the character."""
    cfg = jf.JoyaiFlashConfig()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", [256, cfg.hidden_size], dtype="float32")
        with fluid.core.program.unit("blk0", remat=True):
            out = jf.latent_attention(cfg, x, "blk0")
        loss = layers.reduce_mean(out)
        fluid.optimizer.SGD(0.1).minimize(loss)
    assert [op.type for op in main.global_block().ops].count(
        "rotary_embedding") == 1
    exe = fluid.Executor(fluid.TPUPlace())
    state = {v.name: jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
             for v in startup.list_vars() if v.persistable}
    names = sorted(state)
    step = exe._build(main, ["x"], [loss.name], names, names)
    with jax.default_matmul_precision("default"):
        text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(step._step)(
            state, {"x": jax.ShapeDtypeStruct((2, 256, 2048), jnp.float32)},
            jax.ShapeDtypeStruct((2,), jnp.uint32))))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == "8d8fe2d9236bea73"
