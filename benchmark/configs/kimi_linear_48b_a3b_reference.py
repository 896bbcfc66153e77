"""Plain reference for Kimi-Linear-48B-A3B (moonshotai, `model_type`
`kimi_linear`) pretraining: Kimi Delta Attention 3 : 1 with latent attention
that carries no position, gated experts beside a shared one.

Written from the architecture's public description (the `config.json` named
in kimi_linear_48b_a3b.json and the equations listed there under `assumed`;
the Kimi Linear report, arXiv:2510.26692, section 3, for the delta rule with
a decay per channel; the DeepSeek-V3 report, arXiv:2412.19437, section 2.1.1,
for latent attention; Kingma & Ba 2015, section 2, for Adam) in
straightforward `jax.numpy`: float32 throughout, every matrix product at
`Precision.HIGHEST`, **the delta rule as the token-by-token recurrence** (a
`lax.scan` over positions, vmapped over heads: no chunk, no triangular
system, no sub-block), literal loops over heads and experts, no kernels, no
cache, one sequence and one layer at a time. It imports nothing of the
program and takes nothing the program made: the weights come from
`make_weights` below (the benchmark's own, from the configuration's
`weights_seed`), which the harness also hands to the program.

A layer (pre-norm residual twice, RMSNorm eps `rms_norm_eps` with one learnt
weight, no bias but `dt_bias`): h <- h + mixer(RMSNorm(h));
h <- h + ffn(RMSNorm(h)). Layers are counted from 1 as the published
`kda_layers` / `full_attn_layers` count them.

  KDA               q, k, v = silu(conv4(x W_q | W_k | W_v)): H heads of 128,
                    depthwise causal filters of 4 taps; q and k divided by
                    max(their norm, 1e-6) a head and position;
                    g = -exp(A_log[h]) softplus((x W_fa) W_fb + dt_bias),
                    a log-decay a CHANNEL; beta = sigmoid(x W_b) a head;
                    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1}
                          + beta_t k_t v_t^T,  S_0 = 0, [128, 128] a head;
                    o_t = 128^-1/2 S_t^T q_t;
                    y = (RMSNorm_128(o) * sigmoid((x W_ga) W_gb)) W_o
  latent attention  q = x W_q -> H heads of nope + rope channels, NOT
                    rotated. a = x W_kva; c_kv = RMSNorm(a[:kv_lora_rank]);
                    k_pe = a[kv_lora_rank:], one head for all H, not rotated
                    either. c_kv W_kvb -> H heads of k_nope | v. Head h:
                    k_h = k_nope | k_pe; causal softmax(q_h k_h^T
                    (nope + rope)^-1/2) v_h; out = [ctx_h] W_o
  ffn, dense        (silu(x W1) * x W3) W2 (W1 | W3 one leaf, `gate_up`)
  ffn, experts      s = sigmoid(x W_g) over all the layer's experts; the
                    `top_k` largest of s + bias chosen; weights
                    s[chosen] / (sum s[chosen] + 1e-20) x
                    `routed_scaling_factor`; out = sum over the chosen of
                    w_e (silu(x W1e) * x W3e) W2e + shared(x), the shared
                    expert the same gated MLP, unweighted

then a final RMSNorm and the head (its own matrix): the mean cross entropy
with the next token.

Departure from the published description, also under `assumed` in the json:
a chip's share. Only `experts_held` of each layer's routed experts are here,
and a (token, expert) pair on an absent expert adds nothing, as on that chip
of the deployment; the vocabulary is the chip's slice.

`control=True` is the same mathematics with every matrix product's operands
rounded to int8 (per-tensor absmax, forward and backward): the nearest
precision below the bf16 the configuration states. It exists to show that the
limits in kimi_linear_48b_a3b.json fail it; no benchmark run calls it.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# what a reference shares with the other ones whatever the model: the seed's
# key, the int8 control's product, Adam and the norms by leaf
from benchmark.configs.ernie_base_reference import (  # noqa: F401
    _adam, _diff_norms, _leaf_norms, _mm_int8, seed_key)

_HI = jax.lax.Precision.HIGHEST
ROUTER_NORM_EPS = 1e-20
L2_EPS = 1e-6
FROZEN = ".moe.corr_bias"       # leaves no optimizer touches
A_RANGE = (1.0, 16.0)           # A = exp(A_log), uniform a head
DT_RANGE = (1e-3, 1e-1)         # softplus(dt_bias), log-uniform a channel
SEGMENT = 64                    # positions a kept state of the recurrence


# ---------------------------------------------------------------------------
# sizes and weights, from the configuration and the seed
# ---------------------------------------------------------------------------

def sizes(cfg: dict) -> dict:
    experts = cfg.get("num_experts_published", cfg["num_experts"])
    lin = cfg["linear_attn_config"]
    return {
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "dv": cfg["v_head_dim"], "kv_rank": cfg["kv_lora_rank"],
        "kda_heads": lin["num_heads"], "kda_dim": lin["head_dim"],
        "taps": lin["short_conv_kernel_size"],
        "rank": cfg["kda_gate_rank"],   # the two low-rank gates' inner width
        "experts": experts,
        "held": tuple(cfg.get("experts_held", (0, experts))),
        "shared": cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
    }


def is_dense(cfg: dict, i: int) -> bool:
    """Layer i, counted from 0."""
    return i < cfg["first_k_dense_replace"]


def is_kda(cfg: dict, i: int) -> bool:
    """Layer i, counted from 0; the published lists count from 1."""
    lin = cfg["linear_attn_config"]
    if (i + 1 in lin["kda_layers"]) == (i + 1 in lin["full_attn_layers"]):
        raise ValueError(f"layer {i + 1} is in both or neither of kda_layers "
                         "and full_attn_layers")
    return i + 1 in lin["kda_layers"]


def _layer_specs(cfg: dict, p: str, kda: bool, dense: bool) -> list:
    z = sizes(cfg)
    d = z["d"]
    specs = [(f"{p}.op_norm.w", (d,), "ones")]
    if kda:
        wide = z["kda_heads"] * z["kda_dim"]
        specs += [(f"{p}.{n}.w", (d, wide), "normal") for n in "qkv"]
        specs += [(f"{p}.{n}_conv.w", (wide, z["taps"]), "normal")
                  for n in "qkv"]
        specs += [(f"{p}.f_a.w", (d, z["rank"]), "normal"),
                  (f"{p}.f_b.w", (z["rank"], wide), "normal"),
                  (f"{p}.A_log", (z["kda_heads"],), "a_log"),
                  (f"{p}.dt_bias", (wide,), "dt_bias"),
                  (f"{p}.beta.w", (d, z["kda_heads"]), "normal"),
                  (f"{p}.g_a.w", (d, z["rank"]), "normal"),
                  (f"{p}.g_b.w", (z["rank"], wide), "normal"),
                  (f"{p}.o_norm.w", (z["kda_dim"],), "ones"),
                  (f"{p}.o.w", (wide, d), "normal")]
    else:
        nh = z["heads"]
        specs += [(f"{p}.q.w", (d, nh * (z["nope"] + z["rope"])), "normal"),
                  (f"{p}.kv_a.w", (d, z["kv_rank"] + z["rope"]), "normal"),
                  (f"{p}.kv_a_norm.w", (z["kv_rank"],), "ones"),
                  (f"{p}.kv_b.w", (z["kv_rank"], nh * (z["nope"] + z["dv"])),
                   "normal"),
                  (f"{p}.o.w", (nh * z["dv"], d), "normal")]
    specs.append((f"{p}.ffn_norm.w", (d,), "ones"))
    if dense:
        f = cfg["intermediate_size"]
        return specs + [(f"{p}.gate_up.w", (d, 2 * f), "normal"),
                        (f"{p}.down.w", (f, d), "normal")]
    held, f = z["held"][1], cfg["moe_intermediate_size"]
    return specs + [(f"{p}.moe.gate", (d, z["experts"]), "normal"),
                    (f"{p}.moe.corr_bias", (z["experts"],), "zeros"),
                    (f"{p}.moe.w1", (held, d, f), "normal"),
                    (f"{p}.moe.w3", (held, d, f), "normal"),
                    (f"{p}.moe.w2", (held, f, d), "normal"),
                    (f"{p}.shared.gate_up.w", (d, 2 * z["shared"]), "normal"),
                    (f"{p}.shared.down.w", (z["shared"], d), "normal")]


def weight_specs(cfg: dict) -> list:
    """[(leaf name, shape, init)]; the leaf names are the parameter names of
    paddle_tpu/models/kimi_linear.py. init: "normal" (0, initializer_range),
    "ones", "zeros", "a_log" (the log of a uniform draw over `A_RANGE`),
    "dt_bias" (the inverse softplus of a log-uniform draw over `DT_RANGE`).
    The routers' biases (`*.moe.corr_bias`) are not trained: `FROZEN`."""
    d = cfg["hidden_size"]
    specs = [("embed.w", (cfg["vocab_size"], d), "normal")]
    for i in range(cfg["num_hidden_layers"]):
        specs += _layer_specs(cfg, f"blk{i}", is_kda(cfg, i),
                              is_dense(cfg, i))
    return specs + [("final_norm.w", (d,), "ones"),
                    ("lm_head.w", (d, cfg["vocab_size"]), "normal")]


def make_weights(cfg: dict, seed: int, batches=None, devices=None) -> dict:
    """Every weight, on the device, float32 (the master precision), in one
    jitted call. `batches` is not needed: every weight is made (the routers'
    biases zero).

    Where the configuration names a `weights_seed`, the weights are that one
    draw whatever `seed` is, and `seed` decides the batches alone: which
    experts a freshly drawn router favours decides how many (token, expert)
    pairs fall on the experts held, so a draw for each run gives every run
    another amount of work (kimi_linear_48b_a3b.json, `assumed.weights`)."""
    specs = weight_specs(cfg)
    std = cfg["initializer_range"]

    def make(key):
        out = {}
        for i, (name, shape, init) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            if init == "normal":
                out[name] = std * jax.random.normal(k, shape, jnp.float32)
            elif init == "a_log":
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, *A_RANGE))
            elif init == "dt_bias":      # softplus^-1 of the time steps
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(DT_RANGE[0]),
                    math.log(DT_RANGE[1])))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            else:
                out[name] = jnp.full(shape, 1.0 if init == "ones" else 0.0,
                                     jnp.float32)
        return out

    return jax.jit(make)(seed_key(cfg.get("weights_seed", seed)))


# ---------------------------------------------------------------------------
# matrix products: float32 at full precision (the int8 control: `_mm_int8`)
# ---------------------------------------------------------------------------

def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


# ---------------------------------------------------------------------------
# the layers (one sequence: x [T, D])
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def short_conv(x, w):
    """Depthwise causal filter. x [T, C], w [C, taps]: y[t, c] = sum_j
    w[c, j] x[t - (taps - 1) + j, c], x before the sequence's start zero."""
    t, taps = x.shape[0], w.shape[1]
    xp = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(xp[j:j + t] * w[:, j] for j in range(taps))


def l2_normalize(x):
    """Over the last axis: x / max(|x|, 1e-6)."""
    norm = jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True))
    return x / jnp.maximum(norm, L2_EPS)


def delta_rule_head(q, k, v, g, beta, scale, segment=SEGMENT):
    """One head's gated delta rule, position by position. q, k, g [T, K];
    v [T, V]; beta [T]. Returns o [T, V]. The state [K, V] is decayed channel
    by channel, then corrected by what the key now recalls:
    S~ = Diag(exp g_t) S; S = S~ + beta_t k_t (v_t - S~^T k_t)^T;
    o_t = scale S^T q_t. Every sum is written out (no matrix unit). The
    backward pass keeps the state entering each `segment` of positions and
    makes the segment's states again."""
    def step(state, inp):
        qt, kt, vt, gt, bt = inp
        state = jnp.exp(gt)[:, None] * state
        recalled = jnp.sum(state * kt[:, None], axis=0)
        state = state + bt * kt[:, None] * (vt - recalled)[None, :]
        return state, scale * jnp.sum(state * qt[:, None], axis=0)

    @jax.checkpoint
    def run(state, inputs):
        return jax.lax.scan(step, state, inputs)

    t = q.shape[0]
    segment = math.gcd(t, segment)
    split = lambda x: x.reshape((t // segment, segment) + x.shape[1:])
    state0 = jnp.zeros((k.shape[1], v.shape[1]), jnp.float32)
    _, out = jax.lax.scan(run, state0, tuple(map(split, (q, k, v, g, beta))))
    return out.reshape(t, v.shape[1])


def delta_rule(q, k, v, g, beta, scale):
    """q, k, g [T, H, K]; v [T, H, V]; beta [T, H] -> o [T, H, V]."""
    return jax.vmap(partial(delta_rule_head, scale=scale),
                    in_axes=1, out_axes=1)(q, k, v, g, beta)


def kda_decay(x, params, p, cfg, mm=_mm):
    """The log-decay g [T, H, K] (<= 0) of the layer whose leaves start
    with `p`."""
    z = sizes(cfg)
    raw = mm(mm(x, params[f"{p}.f_a.w"]), params[f"{p}.f_b.w"])
    step = jax.nn.softplus(raw + params[f"{p}.dt_bias"])
    step = step.reshape(x.shape[0], z["kda_heads"], z["kda_dim"])
    return -jnp.exp(params[f"{p}.A_log"])[None, :, None] * step


def kda(x, params, p, cfg, mm=_mm):
    z = sizes(cfg)
    t, nh, hd = x.shape[0], z["kda_heads"], z["kda_dim"]
    eps = cfg["rms_norm_eps"]

    def branch(n):
        y = short_conv(mm(x, params[f"{p}.{n}.w"]), params[f"{p}.{n}_conv.w"])
        return jax.nn.silu(y).reshape(t, nh, hd)

    q, k, v = l2_normalize(branch("q")), l2_normalize(branch("k")), branch("v")
    g = kda_decay(x, params, p, cfg, mm)
    beta = jax.nn.sigmoid(mm(x, params[f"{p}.beta.w"]))
    o = delta_rule(q, k, v, g, beta, 1.0 / math.sqrt(hd))
    gate = jax.nn.sigmoid(mm(mm(x, params[f"{p}.g_a.w"]),
                             params[f"{p}.g_b.w"])).reshape(t, nh, hd)
    y = rms_norm(o, params[f"{p}.o_norm.w"], eps) * gate
    return mm(y.reshape(t, nh * hd), params[f"{p}.o.w"])


def latent_attention(x, params, p, cfg, mm=_mm):
    z = sizes(cfg)
    nh, nope, rope, dv = z["heads"], z["nope"], z["rope"], z["dv"]
    t, eps = x.shape[0], cfg["rms_norm_eps"]
    if not cfg["mla_use_nope"] or cfg["q_lora_rank"] is not None:
        raise ValueError("the reference has the position-free latent "
                         "attention with a direct query product: another "
                         "setting is another model")
    q = mm(x, params[f"{p}.q.w"]).reshape(t, nh, nope + rope)
    a = mm(x, params[f"{p}.kv_a.w"])
    c_kv = rms_norm(a[:, :z["kv_rank"]], params[f"{p}.kv_a_norm.w"], eps)
    k_pe = a[:, z["kv_rank"]:]                          # [T, rope], one head
    kv = mm(c_kv, params[f"{p}.kv_b.w"]).reshape(t, nh, nope + dv)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scale = 1.0 / math.sqrt(nope + rope)

    @jax.checkpoint
    def one_head(args):
        qh, kn, vh = args                  # [T, nope + rope], [T, nope], .
        kh = jnp.concatenate([kn, k_pe], axis=-1)       # the shared head
        s = mm(qh, kh.T) * scale
        probs = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return mm(probs, vh)

    heads_first = lambda y: y.transpose(1, 0, 2)
    ctx = jax.lax.map(one_head, (heads_first(q), heads_first(kv[..., :nope]),
                                 heads_first(kv[..., nope:])))
    return mm(ctx.transpose(1, 0, 2).reshape(t, nh * dv), params[f"{p}.o.w"])


def gated_mlp(x, w1, w3, w2, mm=_mm):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def fused_gated_mlp(x, gate_up, down, mm=_mm):
    w1, w3 = jnp.split(gate_up, 2, axis=-1)
    return gated_mlp(x, w1, w3, down, mm)


def dense_ffn(x, params, p, cfg, mm=_mm):
    return fused_gated_mlp(x, params[f"{p}.gate_up.w"], params[f"{p}.down.w"],
                           mm)


def route(x, gate_w, cfg, bias=None):
    """(chosen experts [T, k], their weights [T, k]) over all the layer's
    experts: the choice is by score + bias, the weights are the plain scores
    over their sum plus 1e-20, times the scaling factor."""
    scores = jax.nn.sigmoid(jnp.matmul(x, gate_w, precision=_HI))
    choose = scores if bias is None else scores + jax.lax.stop_gradient(bias)
    _, idx = jax.lax.top_k(choose, cfg["num_experts_per_token"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["moe_renormalize"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTER_NORM_EPS)
    return idx, w * cfg["routed_scaling_factor"]


def routed_experts(x, params, p, cfg, mm=_mm, held=None):
    """The held routed experts' part: a loop over them, each over every
    token, weighted by the token's weight for it (0 where it was not
    chosen). An expert that is not held adds nothing. `held` = (first,
    count) reads the experts `first ..` of the layer from the first `count`
    of the weights. (A `lax.scan`, one body for the layer's experts:
    unrolled, the executable outgrows the chip machines' compile cache.)"""
    first, count = held if held is not None else sizes(cfg)["held"]
    idx, w = route(x, params[f"{p}.moe.gate"], cfg,
                   params.get(f"{p}.moe.corr_bias"))

    @jax.checkpoint         # an expert's hidden halves are made again
    def one_expert(out, expert):
        j, w1, w3, w2 = expert
        weight = jnp.sum(jnp.where(idx == first + j, w, 0.0), axis=-1)
        return out + gated_mlp(x, w1, w3, w2, mm) * weight[:, None], None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (jnp.arange(count), params[f"{p}.moe.w1"][:count],
         params[f"{p}.moe.w3"][:count], params[f"{p}.moe.w2"][:count]))
    return out


def shared_expert(x, params, p, cfg, mm=_mm):
    return fused_gated_mlp(x, params[f"{p}.shared.gate_up.w"],
                           params[f"{p}.shared.down.w"], mm)


def experts_ffn(x, params, p, cfg, mm=_mm):
    return (routed_experts(x, params, p, cfg, mm)
            + shared_expert(x, params, p, cfg, mm))


def layer(x, params, p: str, kda_layer: bool, dense: bool, cfg, mm=_mm):
    """The layer whose leaves start with `p`, on one sequence x [T, D]."""
    h = rms_norm(x, params[f"{p}.op_norm.w"], cfg["rms_norm_eps"])
    x = x + (kda if kda_layer else latent_attention)(h, params, p, cfg, mm=mm)
    h = rms_norm(x, params[f"{p}.ffn_norm.w"], cfg["rms_norm_eps"])
    return x + (dense_ffn if dense else experts_ffn)(h, params, p, cfg, mm=mm)


def _head_sum(x, head_w, labels, rows: int, mm):
    """Sum over the rows of x [T, D] of the cross entropy of x W_head
    against `labels`, `rows` rows at a time."""
    n = x.shape[0] // rows

    @jax.checkpoint
    def block(args):
        xc, lc = args
        logp = jax.nn.log_softmax(mm(xc, head_w), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lc[:, None], axis=-1))

    return jnp.sum(jax.lax.map(block, (x.reshape(n, rows, -1),
                                       labels.reshape(n, rows))))


def sum_loss(params: dict, ids, labels, cfg: dict, mm=_mm):
    """The sum over one sequence (ids, labels [T]; a position's label is its
    next token) of the cross entropy over all T positions."""
    x = params["embed.w"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(partial(
            layer, p=f"blk{i}", kda_layer=is_kda(cfg, i),
            dense=is_dense(cfg, i), cfg=cfg, mm=mm))(x, params)
    rows = min(cfg["reference"]["head_rows"], ids.shape[0])
    return _head_sum(rms_norm(x, params["final_norm.w"], cfg["rms_norm_eps"]),
                     params["lm_head.w"], labels, rows, mm)


# ---------------------------------------------------------------------------
# following the optimizer
# ---------------------------------------------------------------------------

def learning_rate(opt: dict, t: int) -> float:
    """Step t = 1, 2, ...: the peak rate, reached by a linear warm-up over
    the first `warmup_steps` steps where the configuration names them."""
    warm = opt.get("warmup_steps")
    return opt["learning_rate"] * (min(1.0, t / warm) if warm else 1.0)


def follow(cfg: dict, weights: dict, batches: list, devices=None,
           control: bool = False, seed: int = 0) -> dict:
    """Follow `len(batches)` Adam steps from `weights`, one sequence at a
    time. `batches` are host feeds ({"ids": [B, T], "labels": [B, T, 1]},
    int32) as the traffic generator made them. Returns losses, the first
    gradient's norm by leaf and the norm of the parameters' change by leaf,
    as floats."""
    mm = _mm_int8 if control else _mm
    opt = cfg["optimizer"]

    frozen = {k: v for k, v in weights.items() if k.endswith(FROZEN)}
    weights = {k: v for k, v in weights.items() if k not in frozen}

    @partial(jax.jit, donate_argnums=(1, 2))
    def accumulate(params, grads, loss, ids, labels, inv):
        l, g = jax.value_and_grad(lambda p: inv * sum_loss(
            dict(p, **frozen), ids, labels, cfg, mm))(params)
        return jax.tree_util.tree_map(jnp.add, grads, g), loss + l

    adam = jax.jit(partial(_adam, b1=opt["beta1"], b2=opt["beta2"],
                           eps=opt["epsilon"]), donate_argnums=(0, 1, 2, 3))
    zeros = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree))

    # the harness keeps `weights` on the device; beside them the parameters,
    # the gradient and its temporaries fill the chip, so Adam's two moments
    # wait on the host while a gradient is made
    params = jax.tree_util.tree_map(jnp.copy, weights)
    moments = None
    losses, grad_norms = [], None
    for t, batch in enumerate(batches, start=1):
        ids = np.asarray(batch["ids"])
        labels = np.asarray(batch["labels"]).reshape(ids.shape)
        b, length = ids.shape
        grads, loss = zeros(weights), jnp.zeros((), jnp.float32)
        for row in range(b):
            grads, loss = accumulate(params, grads, loss,
                                     jnp.asarray(ids[row]),
                                     jnp.asarray(labels[row]),
                                     1.0 / (b * length))
        losses.append(float(loss))
        if t == 1:
            grad_norms = {k: float(n) for k, n in
                          jax.jit(_leaf_norms)(grads).items()}
        m, v = ((zeros(weights), zeros(weights)) if moments is None
                else jax.device_put(moments))
        params, m, v = adam(params, grads, m, v, jnp.float32(t),
                            lr=jnp.float32(learning_rate(opt, t)))
        if t < len(batches):
            moments = jax.device_get((m, v))
        del m, v, grads
    update_norms = {k: float(n) for k, n in
                    jax.jit(_diff_norms)(params, weights).items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms}
