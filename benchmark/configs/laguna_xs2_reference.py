"""Plain reference for Laguna-XS.2 (poolside, `model_type` `laguna`)
pretraining.

Written from the architecture's public description (the `config.json` named
in laguna_xs2.json and the equations listed there under `assumed`; Su et al.
2021 for the rotary embedding; Peng et al. 2023, arXiv:2309.00071, for YaRN,
as `transformers`' `_compute_yarn_parameters` has its formulas; Qiu et al.
2025, arXiv:2505.06708, for the head-wise output gate; Kingma & Ba 2015,
section 2, for Adam) in straightforward `jax.numpy`: float32 throughout,
every matrix product at `Precision.HIGHEST`, a head at a time under a literal
[T, T] mask, the rotation written as halves, no kernels, no cache, one
sequence and one layer at a time. It imports nothing of the program and takes
nothing the program made: the weights come from `make_weights` below (the
benchmark's own, from the configuration's `weights_seed`), which the harness
also hands to the program.

A layer l (pre-norm residual twice, RMSNorm eps `rms_norm_eps` with one
learnt weight, no bias): h <- h + attn_l(RMSNorm(h)); h <- h + ffn_l(RMSNorm(h)).

  attention         n_l = `num_attention_heads_per_layer[l]` query heads over
                    `num_key_value_heads` key/value heads of `head_dim`;
                    q ‖ k ‖ v = x W_qkv; g = sigmoid(x W_g) [n_l]. The
                    rotation of the layer's kind turns q and k: the first
                    D_r = `partial_rotary_factor` x `head_dim` channels of a
                    head, pairs (j, j + D_r / 2), by t x inv_freq_j; the rest
                    pass. `rope_type` `default`: inv_freq_j = theta^(-2j/D_r).
                    `yarn`: f_j (1 - r_j) + f_j / factor x r_j with f_j the
                    default, r_j = clip((j - low) / (high - low), 0, 1),
                    low = floor(c(beta_fast)), high = ceil(c(beta_slow)),
                    c(n) = D_r ln(original_max / (2 pi n)) / (2 ln theta);
                    cosines and sines times `attention_factor`. Query head h
                    reads key/value head h // (n_l / n_kv); key j is visible
                    to query i iff j <= i, and in a `sliding_attention` layer
                    also i - j < `sliding_window`; ctx_h = softmax(q_h k^T
                    head_dim^-1/2) v; out = [g_h ctx_h]_h W_o
  ffn, dense        (silu(x W1) * x W3) W2 (W1 ‖ W3 one leaf, `gate_up`)
  ffn, experts      s = sigmoid(x W_r) over all the layer's experts; the
                    `top_k` largest chosen; weights s[chosen] /
                    (sum s[chosen] + 1e-20) x `moe_routed_scaling_factor`;
                    out = sum over the chosen of w_e (silu(x W1e) * x W3e)
                    W2e + shared(x), the shared expert the same gated MLP,
                    unweighted

then a final RMSNorm and the head (its own matrix); the loss is the mean
cross entropy with the next token.

Departure from the published description, also under `assumed` in the json:
a chip's share. Only `experts_held` of each layer's routed experts are here,
and a (token, expert) pair on an absent expert adds nothing, as on that chip
of the deployment; the vocabulary is the chip's slice.

`control=True` is the same mathematics with every matrix product's operands
rounded to int8 (per-tensor absmax, forward and backward): the nearest
precision below the bf16 the configuration states. It exists to show that the
limits in laguna_xs2.json fail it; no benchmark run calls it.
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
FULL, SLIDING = "full_attention", "sliding_attention"


# ---------------------------------------------------------------------------
# sizes and weights, from the configuration and the seed
# ---------------------------------------------------------------------------

def held(cfg: dict) -> tuple:
    experts = cfg.get("num_experts_published", cfg["num_experts"])
    return experts, tuple(cfg.get("experts_held", (0, experts)))


def is_dense(cfg: dict, i: int) -> bool:
    return cfg["mlp_layer_types"][i] == "dense"


def weight_specs(cfg: dict) -> list:
    """[(leaf name, shape, init)]; the leaf names are the parameter names of
    paddle_tpu/models/laguna.py. init: "normal" (0, initializer_range),
    "ones"."""
    d, hd, nkv = (cfg["hidden_size"], cfg["head_dim"],
                  cfg["num_key_value_heads"])
    experts, (_, count) = held(cfg)
    specs = [("embed.w", (cfg["vocab_size"], d), "normal")]
    for i in range(cfg["num_hidden_layers"]):
        p, nh = f"blk{i}", cfg["num_attention_heads_per_layer"][i]
        specs += [(f"{p}.op_norm.w", (d,), "ones"),
                  (f"{p}.qkv.w", (d, (nh + 2 * nkv) * hd), "normal"),
                  (f"{p}.gate.w", (d, nh), "normal"),
                  (f"{p}.o.w", (nh * hd, d), "normal"),
                  (f"{p}.ffn_norm.w", (d,), "ones")]
        if is_dense(cfg, i):
            f = cfg["intermediate_size"]
            specs += [(f"{p}.gate_up.w", (d, 2 * f), "normal"),
                      (f"{p}.down.w", (f, d), "normal")]
        else:
            f, fs = (cfg["moe_intermediate_size"],
                     cfg["shared_expert_intermediate_size"])
            specs += [(f"{p}.moe.gate", (d, experts), "normal"),
                      (f"{p}.moe.w1", (count, d, f), "normal"),
                      (f"{p}.moe.w3", (count, d, f), "normal"),
                      (f"{p}.moe.w2", (count, f, d), "normal"),
                      (f"{p}.shared.gate_up.w", (d, 2 * fs), "normal"),
                      (f"{p}.shared.down.w", (fs, d), "normal")]
    return specs + [("final_norm.w", (d,), "ones"),
                    ("lm_head.w", (d, cfg["vocab_size"]), "normal")]


def make_weights(cfg: dict, seed: int, batches=None, devices=None) -> dict:
    """Every weight, on the device, float32 (the master precision), in one
    jitted call. `batches` is not needed: every weight is made.

    Where the configuration names a `weights_seed`, the weights are that one
    draw whatever `seed` is, and `seed` decides the batches alone: which
    experts a freshly drawn router favours decides how many (token, expert)
    pairs fall on the experts held, so a draw for each run gives every run
    another amount of work (laguna_xs2.json, `assumed.weights`)."""
    specs = weight_specs(cfg)
    std = cfg["initializer_range"]

    def make(key):
        out = {}
        for i, (name, shape, init) in enumerate(specs):
            if init == "normal":
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            else:
                out[name] = jnp.ones(shape, jnp.float32)
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


def inverse_frequencies(rule: dict, rotary_dim: int) -> np.ndarray:
    """The `rotary_dim // 2` pairs' inverse frequencies under a layer kind's
    published rule, float64."""
    theta = float(rule["rope_theta"])
    j = np.arange(rotary_dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / rotary_dim)
    if rule.get("rope_type", "default") == "default":
        return f
    if rule["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rule['rope_type']!r}: the reference "
                         f"knows default and yarn")

    def c(turns):
        return (rotary_dim * math.log(
            rule["original_max_position_embeddings"] / (2 * math.pi * turns))
            / (2 * math.log(theta)))

    low = max(math.floor(c(rule["beta_fast"])), 0)
    high = min(math.ceil(c(rule["beta_slow"])), rotary_dim - 1)
    r = np.clip((j - low) / (high - low), 0.0, 1.0)
    return f * (1.0 - r) + f / rule["factor"] * r


def rotate(x, rule: dict):
    """x [T, heads, D]: the first D_r = partial_rotary_factor x D channels of
    each head turn, pairs (j, j + D_r / 2) by t x inv_freq_j, cosines and
    sines times the rule's `attention_factor` (YaRN; 1 otherwise); the other
    channels pass as they are."""
    t, _, d = x.shape
    d_r = int(d * rule.get("partial_rotary_factor", 1))
    inv_freq = jnp.asarray(inverse_frequencies(rule, d_r), jnp.float32)
    factor = (float(rule.get("attention_factor") or 1.0)
              if rule.get("rope_type", "default") == "yarn" else 1.0)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    a, b, rest = x[..., :d_r // 2], x[..., d_r // 2:d_r], x[..., d_r:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def attention(x, params, p, i, cfg, mm=_mm):
    nh, nkv, hd = (cfg["num_attention_heads_per_layer"][i],
                   cfg["num_key_value_heads"], cfg["head_dim"])
    kind, t = cfg["layer_types"][i], x.shape[0]
    rule = cfg["rope_parameters"][kind]
    qkv = mm(x, params[f"{p}.qkv.w"])
    q = rotate(qkv[:, :nh * hd].reshape(t, nh, hd), rule)
    k = rotate(qkv[:, nh * hd:(nh + nkv) * hd].reshape(t, nkv, hd), rule)
    v = qkv[:, (nh + nkv) * hd:].reshape(t, nkv, hd)
    pos = jnp.arange(t)
    visible = pos[None, :] <= pos[:, None]                  # key j <= query i
    if kind == SLIDING and cfg.get("sliding_window"):
        visible &= pos[:, None] - pos[None, :] < cfg["sliding_window"]
    scale = 1.0 / math.sqrt(hd)
    group = nh // nkv

    @jax.checkpoint
    def one_head(args):
        qh, kh, vh = args                                   # [T, hd] each
        s = mm(qh, kh.T) * scale
        return mm(jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1),
                  vh)

    heads_first = lambda y: y.transpose(1, 0, 2)
    ctx = jax.lax.map(one_head, (
        heads_first(q), jnp.repeat(heads_first(k), group, axis=0),
        jnp.repeat(heads_first(v), group, axis=0)))         # [nh, T, hd]
    ctx = ctx.transpose(1, 0, 2)                            # [T, nh, hd]
    if cfg.get("gating", True):
        gate = jax.nn.sigmoid(mm(x, params[f"{p}.gate.w"]))  # [T, nh]
        ctx = ctx * gate[:, :, None]
    return mm(ctx.reshape(t, nh * hd), params[f"{p}.o.w"])


def gated_mlp(x, w1, w3, w2, mm=_mm):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def fused_gated_mlp(x, gate_up, down, mm=_mm):
    w1, w3 = jnp.split(gate_up, 2, axis=-1)
    return gated_mlp(x, w1, w3, down, mm)


def route(x, gate_w, cfg):
    """(chosen experts [T, k], their weights [T, k]) over all the layer's
    experts: sigmoid scores, the largest chosen, the chosen scores over
    their sum plus 1e-20, times the scaling factor."""
    scores = jax.nn.sigmoid(jnp.matmul(x, gate_w, precision=_HI))
    w, idx = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTER_NORM_EPS)
    return idx, w * cfg["moe_routed_scaling_factor"]


def routed_experts(x, params, p, cfg, mm=_mm, share=None):
    """The held routed experts' part: a loop over them, each over every
    token, weighted by the token's weight for it (0 where it was not
    chosen). An expert that is not held adds nothing. `share` = (first,
    count) reads the experts `first ..` of the layer from the first `count`
    of the weights. (The loop is a `lax.scan`, one body for the layer's
    experts: unrolled, the experts of five layers make an executable too
    large for the chip machines' compile cache, PERF.md section 6, PR 39.)"""
    first, count = share if share is not None else held(cfg)[1]
    idx, w = route(x, params[f"{p}.moe.gate"], cfg)

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


def layer(x, params, i: int, cfg, mm=_mm):
    """Layer i on one sequence x [T, D]."""
    p, eps = f"blk{i}", cfg["rms_norm_eps"]
    h = rms_norm(x, params[f"{p}.op_norm.w"], eps)
    x = x + attention(h, params, p, i, cfg, mm=mm)
    h = rms_norm(x, params[f"{p}.ffn_norm.w"], eps)
    if is_dense(cfg, i):
        return x + fused_gated_mlp(h, params[f"{p}.gate_up.w"],
                                   params[f"{p}.down.w"], mm)
    return x + (routed_experts(h, params, p, cfg, mm)
                + shared_expert(h, params, p, cfg, mm))


def sum_loss(params: dict, ids, labels, cfg: dict, mm=_mm):
    """The sum over one sequence (ids, labels [T]; a position's label is its
    next token) of the head's cross entropy, the head `head_rows` rows at a
    time."""
    t = ids.shape[0]
    x = params["embed.w"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(partial(layer, i=i, cfg=cfg, mm=mm))(x, params)
    x = rms_norm(x, params["final_norm.w"], cfg["rms_norm_eps"])
    rows = min(cfg["reference"]["head_rows"], t)

    @jax.checkpoint
    def block(args):
        xc, lc = args
        logp = jax.nn.log_softmax(mm(xc, params["lm_head.w"]), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lc[:, None], axis=-1))

    return jnp.sum(jax.lax.map(block, (x.reshape(t // rows, rows, -1),
                                       labels.reshape(t // rows, rows))))


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
    int32) as the traffic generator made them. Returns the losses, the first
    gradient's norm by leaf and the norm of the parameters' change by leaf,
    as floats."""
    mm = _mm_int8 if control else _mm
    opt = cfg["optimizer"]

    @partial(jax.jit, donate_argnums=(1, 2))
    def accumulate(params, grads, loss, ids, labels, inv):
        l, g = jax.value_and_grad(lambda p: sum_loss(
            p, ids, labels, cfg, mm) * inv)(params)
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
