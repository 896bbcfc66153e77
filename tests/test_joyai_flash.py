"""JoyAI-LLM-Flash (`models/joyai_flash.py`) against its plain reference
(`benchmark/configs/joyai_llm_flash_reference.py`) at a small size on the CPU,
and the pieces the model forced, each against its written-out form: latent
attention with one rope key head for all heads, the interleaved rotation, the
head matrix and the table with two readers each, the prediction module's
last position, and the shares of the experts adding up to the uncut layer."""
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from benchmark.configs import joyai_llm_flash_reference as ref
from paddle_tpu import layers
from paddle_tpu.models import joyai_flash as jf
from paddle_tpu.ops import nn_ops
from paddle_tpu.parallel import moe

fa = importlib.import_module("paddle_tpu.ops.pallas_kernels.flash_attention")


def _cfg(layers_=3, experts=16, held=(0, 8), **over):
    cfg = {
        "hidden_size": 64, "num_hidden_layers": layers_,
        "first_k_dense_replace": 1, "intermediate_size": 96,
        "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "rope_theta": 32e6, "rope_interleave": True,
        "n_routed_experts": held[1], "n_routed_experts_published": experts,
        "experts_held": list(held), "num_experts_per_tok": 4,
        "moe_intermediate_size": 32, "n_shared_experts": 1,
        "routed_scaling_factor": 2.5, "norm_topk_prob": True,
        "num_nextn_predict_layers": 1, "mtp_loss_weight": 0.3,
        "rms_norm_eps": 1e-6, "vocab_size": 96, "initializer_range": 0.2,
        "optimizer": {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.999,
                      "epsilon": 1e-8},
        "reference": {"follow_steps": 3, "head_rows": 16}}
    cfg.update(over)
    return cfg


def _model_cfg(cfg):
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "first_k_dense_replace", "intermediate_size",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rope_theta", "rope_interleave", "num_experts_per_tok",
            "moe_intermediate_size", "n_shared_experts",
            "routed_scaling_factor", "norm_topk_prob", "mtp_loss_weight",
            "rms_norm_eps", "initializer_range")
    return jf.JoyaiFlashConfig(
        n_routed_experts=cfg["n_routed_experts_published"],
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
        main, startup, _, loss, counters, terms = jf.build_pretrain_program(
            _model_cfg(cfg), b, t, opt)
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    return main, loss, counters, terms, exe, scope


def _reference_terms(cfg, weights, batch):
    """(L_main, L_mtp) of a batch by the reference."""
    b, t = batch["ids"].shape
    sums = [ref.loss_sums(weights, jnp.asarray(batch["ids"][r]),
                          jnp.asarray(batch["labels"][r, :, 0]), cfg)
            for r in range(b)]
    return (sum(s[0] for s in sums) / (b * t),
            sum(s[1] for s in sums) / (b * (t - 1)))


def _reference_loss_and_grads(cfg, weights, batch):
    def total(p):
        main, mtp = _reference_terms(cfg, p, batch)
        return main + cfg["mtp_loss_weight"] * mtp
    return jax.value_and_grad(total)(weights)


def _set(scope, weights):
    for k, v in weights.items():
        scope.set_var(k, jnp.copy(v))


def _moment_grad(scope, k):
    return scope.find_var(f"{k}_AdamOptimizer_moment1") / 0.1


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

def test_loss_both_terms_and_every_gradient_leaf_against_the_reference():
    cfg = _cfg()
    main, loss, _, terms, exe, scope = _program(cfg, lr=1e-3)
    weights = ref.make_weights(cfg, 5)
    params = main.global_block().all_parameters()
    assert sorted(p.name for p in params) == sorted(weights)
    assert ([p.name for p in params if not p.trainable]
            == [k for k in weights if k.endswith(ref.FROZEN)])
    _set(scope, weights)
    (batch,) = _batches(cfg, 1)
    want_loss, want_grads = _reference_loss_and_grads(cfg, weights, batch)
    want_main, want_mtp = _reference_terms(cfg, weights, batch)
    got_loss, got_main, got_mtp = exe.run(
        main, feed=batch, fetch_list=[loss, terms["main"], terms["mtp"]],
        scope=scope)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=2e-6)
    assert float(got_main) == pytest.approx(float(want_main), rel=2e-6)
    assert float(got_mtp) == pytest.approx(float(want_mtp), rel=2e-6)
    assert float(got_loss) == pytest.approx(
        float(got_main) + 0.3 * float(got_mtp), rel=1e-6)
    for k in weights:
        if k.endswith(ref.FROZEN):
            continue
        got, want = _moment_grad(scope, k), want_grads[k]
        scale = max(float(jnp.abs(want).max()), 1e-6)
        assert float(jnp.abs(got - want).max()) < 2e-4 * scale, k


def test_three_adam_steps_follow_the_reference():
    cfg = _cfg()
    main, loss, counters, terms, exe, scope = _program(cfg, lr=1e-3)
    batches = _batches(cfg, 3, seed=4)
    # with the routers' biases away from the zero they start at: the program
    # has to choose by score + bias, and leave the bias alone
    weights = ref.make_weights(cfg, 11)
    rng = np.random.default_rng(5)
    biases = {k: rng.normal(0, 0.05, v.shape).astype("float32")
              for k, v in weights.items() if k.endswith(ref.FROZEN)}
    assert sorted(biases) == ["blk1.moe.corr_bias", "blk2.moe.corr_bias",
                              "mtp.blk.moe.corr_bias"]
    weights.update({k: jnp.asarray(b) for k, b in biases.items()})
    _set(scope, weights)
    want = ref.follow(cfg, weights, batches)
    trained = [k for k in weights if not k.endswith(ref.FROZEN)]
    assert sorted(want["grad_norms"]) == sorted(trained)
    fetch = ([loss] + [v for _, t, p in counters for v in (t, p)]
             + [terms["main"], terms["mtp"]])
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
    # two expert layers and the module's, each with its counters
    assert [i for i, _, _ in counters] == [1, 2, jf.MTP_BLOCK]
    for tokens, pairs in zip(out[1:7:2], out[2:7:2]):
        assert tokens.shape == (8,) and int(pairs) == tokens.sum()
    jf.record_counters(counters, out[1:-2] + out[-1:], 2 * 32, 4)
    from paddle_tpu.observability import get_registry
    series = {(s["name"], s["labels"].get("block")): s["value"]
              for s in get_registry().series()
              if s["name"].startswith(("moe/", "mtp/"))
              and "expert" not in s["labels"]}
    assert series[("moe/dropped", "blk1")] == 0
    assert series[("moe/pairs_routed", "blk_mtp")] == 2 * 32 * 4
    assert series[("moe/pairs_held", "blk_mtp")] == int(out[6])
    assert series[("mtp/loss", None)] == pytest.approx(float(out[-1]))
    assert ("mtp/main_loss", None) not in series
    assert losses[-1] == pytest.approx(
        float(out[-2]) + 0.3 * float(out[-1]), rel=1e-6)


def test_the_builder_names_its_units_and_counts_its_parameters():
    cfg = _cfg()
    mcfg = _model_cfg(cfg)
    n = sum(int(np.prod(shape)) for name, shape, _ in ref.weight_specs(cfg)
            if not name.endswith(ref.FROZEN))
    assert jf.param_count(mcfg) == n
    main, *_ = jf.build_pretrain_program(mcfg, 2, 32)
    units = {op.attrs.get("__unit__") for op in main.global_block().ops}
    attn = ("q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "rope",
            "assemble", "kernel", "o")
    assert ({"embed", "final_norm", "lm_head", "loss", "blk0/op_norm",
             "blk0/ffn_norm", "blk0/mlp/gate_up", "blk0/mlp/act",
             "blk0/mlp/down", "blk1/moe", "blk1/moe/shared/gate_up",
             "blk1/moe/shared/act", "blk1/moe/shared/down",
             "blk1/moe/combine", "mtp/embed", "mtp/enorm", "mtp/hnorm",
             "mtp/eh_proj", "mtp/blk/op_norm", "mtp/blk/moe",
             "mtp/blk/moe/shared/down", "mtp/final_norm", "mtp/head",
             "mtp/loss"}
            | {f"{b}/attn/{p}" for b in ("blk0", "blk2", "mtp/blk")
               for p in attn}) <= units
    # every layer is made again in the backward pass, all but what it keeps:
    # the two down-projections' results, the kernel's outputs, a gate/up
    # product, the routing and its plan; never q, k or v
    assert main.remat_policy == "full"
    produced_in = {n: op.attrs.get("__unit__")
                   for op in main.global_block().ops
                   for n in op.output_names()}
    kept = {block: ([produced_in[n] for n in names if n in produced_in],
                    [n for n in names if n not in produced_in])
            for block, names in main.remat_keep.items()}
    assert kept == {
        "blk0": (["blk0/attn/q_a", "blk0/attn/kv_a", "blk0/mlp/gate_up"],
                 list(fa.KEPT)),
        **{b: ([f"{b}/attn/q_a", f"{b}/attn/kv_a",
                f"{b}/moe/shared/gate_up"], list(fa.KEPT) + list(moe.KEPT))
           for b in ("blk1", "blk2", "mtp/blk")}}
    with pytest.raises(ValueError, match="one multi-token-prediction"):
        jf.build_pretrain_program(
            jf.JoyaiFlashConfig(num_nextn_predict_layers=2), 2, 32)
    # the published model whole, 48.9B with its module, 2.7B of it touched
    # a token in the 39 expert layers; the cell's cut, 680.5M
    d, nh = 2048, 32
    whole = jf.JoyaiFlashConfig()
    assert jf.param_count(whole) == pytest.approx(48.9e9 + 1.24e9, rel=2e-3)
    mla = (d * 1536 + 1536 + 1536 * nh * 192 + d * 576 + 512
           + 512 * nh * 256 + nh * 128 * d)
    assert mla == 26_347_520
    active = 39 * (mla + 2 * d + d * 256 + 9 * 3 * d * 768)
    assert active == pytest.approx(2.70e9, rel=2e-3)
    cut = jf.JoyaiFlashConfig(vocab_size=16160, num_hidden_layers=5,
                              experts_held=(0, 16))
    # ISSUE 39's table sums to 680,453,376: it counts the five routers' 256
    # biases (buffers, not trained) and 2,048 too many in each of the six
    # attention layers (the two latent norms, 1,536 + 512, twice)
    assert jf.param_count(cut) == 680_453_376 - 5 * 256 - 6 * 2048


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
    for k in weights:
        np.testing.assert_allclose(results[0][1][k], results[1][1][k],
                                   rtol=1e-4, atol=1e-5, err_msg=k)


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------

def test_two_halves_of_the_experts_and_one_shared_expert_give_the_layer():
    """The parts the two halves of the routed experts give, added to the
    shared expert's counted once, equal the uncut reference's layer; the
    program's grouped product gives each half's routed part."""
    cfg = _cfg(experts=16, held=(0, 16))
    full = ref.make_weights(cfg, 9)
    p, t = "blk1", 40
    x = jax.random.normal(jax.random.PRNGKey(1), (t, cfg["hidden_size"]))
    whole = ref.experts_ffn(x, full, p, cfg)
    shared = ref.shared_expert(x, full, p, cfg)
    parts = []
    for first in (0, 8):
        share = dict(full)
        for leaf in ("w1", "w3", "w2"):
            share[f"{p}.moe.{leaf}"] = full[f"{p}.moe.{leaf}"][first:first + 8]
        part = ref.routed_experts(x, share, p, cfg, held=(first, 8))
        got = moe.moe_ffn(
            x, full[f"{p}.moe.gate"], share[f"{p}.moe.w1"], None,
            share[f"{p}.moe.w2"], None, k=4, act=jax.nn.silu,
            experts_held=(first, 8), scoring="sigmoid",
            correction_bias=full[f"{p}.moe.corr_bias"], routed_scaling=2.5,
            w3=share[f"{p}.moe.w3"])
        np.testing.assert_allclose(got.y, part, rtol=1e-5, atol=1e-5)
        parts.append(part)
    np.testing.assert_allclose(parts[0] + parts[1] + shared, whole,
                               rtol=1e-5, atol=1e-5)
    for part in (*parts, shared):
        assert float(jnp.abs(part).max()) > 0
    # each chip's layer output, summed, counts the shared expert twice
    assert float(jnp.abs(parts[0] + parts[1] + 2 * shared - whole).max()) > 0


# ---------------------------------------------------------------------------
# latent attention and the rotation
# ---------------------------------------------------------------------------

def _attention_layer(cfg, t=16, seed=0):
    """The program's latent attention alone on x [1, T, D]."""
    mcfg = _model_cfg(cfg)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", [t, cfg["hidden_size"]], dtype="float32")
        with fluid.core.program.unit("blk0", remat=True):
            out = jf.latent_attention(mcfg, x, "blk0")
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    weights = {k: v for k, v in ref.make_weights(cfg, seed).items()
               if scope.has_var(k)}
    _set(scope, weights)
    value = np.random.RandomState(seed).randn(
        1, t, cfg["hidden_size"]).astype("float32")
    (got,) = exe.run(main, feed={"x": value}, fetch_list=[out], scope=scope)
    return value[0], weights, np.asarray(got)[0]


def test_latent_attention_against_its_written_out_form():
    """Head by head in numpy: two low-rank products with a norm between,
    the split of kv_a's result into a normed latent and ONE raw rope head
    that every query head reads, the rotation of pairs (2j, 2j + 1) on the
    rope parts only, scores over 24 = 16 + 8 channels at 24^-1/2, values 16
    wide."""
    cfg = _cfg()
    x, w, got = _attention_layer(cfg)
    w = {k.split(".", 1)[1]: np.asarray(v, np.float64) for k, v in w.items()}
    x = x.astype(np.float64)
    t, nh, nope, rope, dv = 16, 4, 16, 8, 16

    def norm(a, g):
        return a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-6) * g

    def turn(a):                       # a [T, rope]
        out = np.empty_like(a)
        for pos in range(t):
            for j in range(rope // 2):
                ang = pos * 32e6 ** (-2.0 * j / rope)
                c, s = math.cos(ang), math.sin(ang)
                out[pos, 2 * j] = a[pos, 2 * j] * c - a[pos, 2 * j + 1] * s
                out[pos, 2 * j + 1] = (a[pos, 2 * j + 1] * c
                                       + a[pos, 2 * j] * s)
        return out

    q = (norm(x @ w["q_a.w"], w["q_a_norm.w"]) @ w["q_b.w"]).reshape(
        t, nh, nope + rope)
    a = x @ w["kv_a.w"]
    kv = (norm(a[:, :16], w["kv_a_norm.w"]) @ w["kv_b.w"]).reshape(
        t, nh, nope + dv)
    k_rope = turn(a[:, 16:])
    ctx = np.zeros((t, nh, dv))
    for h in range(nh):
        qh = np.concatenate([q[:, h, :nope], turn(q[:, h, nope:])], -1)
        kh = np.concatenate([kv[:, h, :nope], k_rope], -1)
        s = qh @ kh.T / math.sqrt(nope + rope)
        s[np.triu_indices(t, 1)] = -np.inf
        p = np.exp(s - s.max(-1, keepdims=True))
        ctx[:, h] = (p / p.sum(-1, keepdims=True)) @ kv[:, h, nope:]
    want = ctx.reshape(t, nh * dv) @ w["o.w"]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
    # and it is the reference's
    cfg_w = {f"blk0.{k}": jnp.asarray(v, jnp.float32) for k, v in w.items()}
    np.testing.assert_allclose(
        got, ref.latent_attention(jnp.asarray(x, jnp.float32), cfg_w, "blk0",
                                  cfg), rtol=2e-4, atol=2e-6)


@pytest.mark.parametrize("heads,d", [(3, 8), (33, 64)])
def test_the_interleaved_rotation_against_pairs_written_out(heads, d):
    t, theta = 10, 32e6
    x = jax.random.normal(jax.random.PRNGKey(heads), (2, t, heads * d))
    got = nn_ops._rope(x, heads, theta, True)
    xh = np.asarray(x, np.float64).reshape(2, t, heads, d // 2, 2)
    ang = (np.arange(t)[:, None]
           * theta ** (-2.0 * np.arange(d // 2) / d)[None])[None, :, None]
    want = np.stack([xh[..., 0] * np.cos(ang) - xh[..., 1] * np.sin(ang),
                     xh[..., 1] * np.cos(ang) + xh[..., 0] * np.sin(ang)],
                    -1).reshape(x.shape)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        got, jnp.stack([ref.rotate_pairs(x[b].reshape(t, heads, d),
                                         theta).reshape(t, heads * d)
                        for b in range(2)]), rtol=1e-5, atol=1e-5)
    # the backward rule is the same pass at the negative angle
    g = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    _, pull = jax.vjp(lambda x: nn_ops._rope(x, heads, theta, True), x)
    np.testing.assert_allclose(
        pull(g)[0], nn_ops._rope_turn(g, heads, theta, -1.0, True),
        atol=1e-6)
    np.testing.assert_allclose(
        nn_ops._rope_turn(got, heads, theta, -1.0, True), x, atol=1e-5)


def test_interleaved_scores_equal_de_interleave_then_rotate_half():
    """The published code permutes q_rope's and k_rope's channels to the
    rotate-half layout and rotates there; the scores q . k are those of the
    pairs turned where they lie."""
    t, d, theta = 9, 8, 32e6
    q = jax.random.normal(jax.random.PRNGKey(0), (1, t, 2 * d))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, t, d))
    perm = np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])
    de = lambda x, h: x.reshape(1, t, h, d)[..., perm].reshape(1, t, h * d)
    qi = nn_ops._rope(q, 2, theta, True)
    ki = nn_ops._rope(k, 1, theta, True)
    qh, kh = nn_ops._rope(de(q, 2), 2, theta), nn_ops._rope(de(k, 1), 1,
                                                           theta)
    for scores in (qi, qh):
        assert scores.shape == (1, t, 2 * d)
    s_pairs = jnp.einsum("bqhd,bkd->bhqk", qi.reshape(1, t, 2, d), ki)
    s_half = jnp.einsum("bqhd,bkd->bhqk", qh.reshape(1, t, 2, d), kh)
    np.testing.assert_allclose(s_pairs, s_half, rtol=1e-5, atol=1e-5)
    # the two conventions on the same channels are not the same rotation
    assert float(jnp.abs(qi - nn_ops._rope(q, 2, theta)).max()) > 0.1


def test_the_layer_says_the_convention_in_an_attribute():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = layers.data("x", [6, 16], dtype="float32")
        layers.rotary_embedding(x, 2, theta=1e4)
        layers.rotary_embedding(x, 2, theta=1e4, interleaved=True)
    a, b = [op.attrs for op in main.global_block().ops
            if op.type == "rotary_embedding"]
    assert "interleaved" not in a and b["interleaved"] is True


# ---------------------------------------------------------------------------
# one head matrix and one table, two readers each
# ---------------------------------------------------------------------------

def test_head_and_table_gradients_are_the_sums_of_their_two_readers():
    """`lm_head.w` is read by the trunk's head and by the module's, `embed.w`
    by the trunk's lookup and by the module's lookup of the next tokens: one
    parameter and one Adam slot each, and a gradient that is the sum of what
    each reader alone would give (the reference with a copy of the leaf for
    each reader says what that is)."""
    cfg = _cfg()
    main, loss, _, _, exe, scope = _program(cfg, lr=1e-3)
    names = [p.name for p in main.global_block().all_parameters()]
    assert names.count("lm_head.w") == 1 and names.count("embed.w") == 1
    state = [v.name for v in main.list_vars() if v.persistable]
    for leaf in ("lm_head.w", "embed.w"):
        assert sum(n.startswith(f"{leaf}_AdamOptimizer_moment1")
                   for n in state) == 1
    weights = ref.make_weights(cfg, 2)
    _set(scope, weights)
    (batch,) = _batches(cfg, 1, seed=3)
    exe.run(main, feed=batch, fetch_list=[loss], scope=scope)

    def split_readers(copies):
        """The reference's loss with the module reading its own copies."""
        b, t = batch["ids"].shape
        total = 0.0
        for r in range(b):
            ids = jnp.asarray(batch["ids"][r])
            labels = jnp.asarray(batch["labels"][r, :, 0])
            main_sum, _ = ref.loss_sums(weights | {
                "embed.w": copies["embed_trunk"],
                "lm_head.w": copies["head_trunk"]}, ids, labels, cfg)
            # the module's term: the trunk's state from the trunk's table,
            # the next token's embedding and the logits from the module's
            p = weights | {"embed.w": copies["embed_trunk"]}
            x = p["embed.w"][ids]
            for i in range(cfg["num_hidden_layers"]):
                x = ref.layer(x, p, f"blk{i}", ref.is_dense(cfg, i), cfg)
            e = ref.rms_norm(copies["embed_mtp"][labels], p["mtp.enorm.w"],
                             1e-6)
            h = ref.rms_norm(x, p["mtp.hnorm.w"], 1e-6)
            y = jnp.concatenate([e, h], -1) @ p["mtp.eh_proj.w"]
            y = ref.rms_norm(ref.layer(y, p, ref.MTP, False, cfg),
                             p["mtp.final_norm.w"], 1e-6)
            logp = jax.nn.log_softmax(y @ copies["head_mtp"], -1)
            mtp_sum = -jnp.sum(jnp.take_along_axis(
                logp[:-1], labels[1:, None], -1))
            total = (total + main_sum / (b * t)
                     + 0.3 * mtp_sum / (b * (t - 1)))
        return total

    copies = {"embed_trunk": weights["embed.w"],
              "embed_mtp": weights["embed.w"],
              "head_trunk": weights["lm_head.w"],
              "head_mtp": weights["lm_head.w"]}
    g = jax.grad(split_readers)(copies)
    for leaf, a, b in (("embed.w", "embed_trunk", "embed_mtp"),
                       ("lm_head.w", "head_trunk", "head_mtp")):
        assert float(jnp.abs(g[a]).max()) > 0 < float(jnp.abs(g[b]).max())
        want = g[a] + g[b]
        scale = float(jnp.abs(want).max())
        assert float(jnp.abs(_moment_grad(scope, leaf) - want).max()) < (
            2e-4 * scale), leaf


def test_the_last_position_of_a_sequence_carries_no_module_loss():
    """The module's target at position i is the label at i + 1: the last
    position has none. Changing the last label moves the trunk's loss and
    the embedding the module reads there, but no target of the module."""
    cfg = _cfg()
    main, _, _, terms, exe, scope = _program(cfg)
    _set(scope, ref.make_weights(cfg, 1))
    (batch,) = _batches(cfg, 1, seed=6)
    head = [op for op in main.global_block().ops
            if op.attrs.get("__unit__") == "mtp/head"]
    assert [op.type for op in head] == [
        "slice", "pad", "linear_softmax_with_cross_entropy"]
    ce = head[-1]
    assert ce.attrs["ignore_index"] == jf.IGNORE
    per_token, labelled, targets = exe.run(
        main, feed=batch, scope=scope,
        fetch_list=[ce.outputs["Loss"][0], ce.outputs["Labelled"][0],
                    ce.inputs["Label"][0]])
    b, t = batch["ids"].shape
    assert int(labelled) == b * (t - 1)
    np.testing.assert_array_equal(targets[:, :-1], batch["labels"][:, 1:])
    assert (targets[:, -1] == jf.IGNORE).all()
    assert (per_token[:, -1] == 0).all() and (per_token[:, :-1] > 0).all()
    (mtp,) = exe.run(main, feed=batch, fetch_list=[terms["mtp"]], scope=scope)
    assert float(mtp) == pytest.approx(float(per_token.sum()) / (b * (t - 1)),
                                       rel=1e-6)
