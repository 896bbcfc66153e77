"""Plain reference for LFM2-24B-A2B (LiquidAI, `model_type` `lfm2_moe`)
next-token pretraining.

Written from the architecture's public description (the `config.json` named
in lfm2_24b_a2b.json and the layer equations listed there under `assumed`;
Su et al. 2021 for the rotary embedding; Kingma & Ba 2015, section 2, for
Adam) in straightforward `jax.numpy`: float32 throughout, every matrix
product at `Precision.HIGHEST`, literal loops over heads and experts, no
kernels, no cache, one sequence and one layer at a time. It imports nothing
of the program and takes nothing the program made: the weights come from
`make_weights` below (the benchmark's own, from the configuration's
`weights_seed`), which the harness also hands to the program.

A layer (pre-norm residual twice, RMSNorm eps `norm_eps`, no bias, no
dropout): h <- h + operator(RMSNorm(h)); h <- h + ffn(RMSNorm(h)).

  operator `conv`            [B | C | x~] = x W_in; u = B * x~;
                             v_t = sum_j w[:, j] * u_{t-(K-1)+j} (u before
                             the start is zero); out = (C * v) W_out
  operator `full_attention`  q, k, v = x W_q, x W_k, x W_v (H, H_kv, H_kv
                             heads of d); q, k <- RMSNorm_d(q), RMSNorm_d(k)
                             per head, one weight [d] each; rotary embedding
                             (rotate-half, theta) on q and k; causal
                             softmax(q k^T / sqrt(d)) v, query head h reading
                             key/value head h // (H / H_kv); out = . W_o
  ffn, dense                 (silu(x W1) * x W3) W2
  ffn, experts               s = sigmoid(x W_g) over all the layer's experts;
                             the `top_k` largest of s + expert bias chosen;
                             weights s[chosen] / (sum s[chosen] + 1e-6) x
                             `routed_scaling_factor`; out = sum over the
                             chosen of w_e (silu(x W1e) * x W3e) W2e

then a final RMSNorm and the head, which is the embedding's table.

Departure from the published description, also under `assumed` in the json:
a chip's share. Only `experts_held` of each layer's experts are here, and a
(token, expert) pair on an absent expert adds nothing, as on that chip of the
deployment; the vocabulary is the chip's slice.

`control=True` is the same mathematics with every matrix product's operands
rounded to int8 (per-tensor absmax, forward and backward): the nearest
precision below the bf16 the configuration states. It exists to show that the
limits in lfm2_24b_a2b.json fail it; no benchmark run calls it.
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
ROUTER_NORM_EPS = 1e-6
FROZEN = ".moe.corr_bias"       # leaves no optimizer touches


# ---------------------------------------------------------------------------
# sizes and weights, from the configuration and the seed
# ---------------------------------------------------------------------------

def sizes(cfg: dict) -> dict:
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return {
        "d": cfg["hidden_size"], "head_dim": hd,
        "q_dim": cfg["num_attention_heads"] * hd,
        "kv_dim": cfg["num_key_value_heads"] * hd,
        "experts": cfg.get("num_experts_published", cfg["num_experts"]),
        "held": tuple(cfg.get("experts_held", (0, cfg["num_experts"]))),
    }


def is_dense(cfg: dict, i: int) -> bool:
    return i < cfg["num_dense_layers"]


def weight_specs(cfg: dict) -> list:
    """[(leaf name, shape, init)]; the leaf names are the parameter names of
    paddle_tpu/models/lfm2.py. init: "normal" (0, initializer_range), "conv"
    (uniform +-1/sqrt(K), a depthwise Conv1d's default), "ones", "zeros".
    The router's expert bias (`*.moe.corr_bias`) is not trained: `FROZEN`."""
    z = sizes(cfg)
    d, hd = z["d"], z["head_dim"]
    specs = [("embed.w", (cfg["vocab_size"], d), "normal")]
    for i, kind in enumerate(cfg["layer_types"]):
        p = f"blk{i}"
        specs.append((f"{p}.op_norm.w", (d,), "ones"))
        if kind == "conv":
            specs += [(f"{p}.in_proj.w", (d, 3 * d), "normal"),
                      (f"{p}.conv.w", (d, cfg["conv_L_cache"]), "conv"),
                      (f"{p}.out_proj.w", (d, d), "normal")]
        elif kind == "full_attention":
            specs += [(f"{p}.qkv.w", (d, z["q_dim"] + 2 * z["kv_dim"]),
                       "normal"),
                      (f"{p}.q_norm.w", (hd,), "ones"),
                      (f"{p}.k_norm.w", (hd,), "ones"),
                      (f"{p}.o.w", (z["q_dim"], d), "normal")]
        else:
            raise ValueError(f"unknown operator {kind!r}")
        specs.append((f"{p}.ffn_norm.w", (d,), "ones"))
        if is_dense(cfg, i):
            f = cfg["intermediate_size"]
            specs += [(f"{p}.gate_up.w", (d, 2 * f), "normal"),
                      (f"{p}.down.w", (f, d), "normal")]
        else:
            held, f = z["held"][1], cfg["moe_intermediate_size"]
            specs += [(f"{p}.moe.gate", (d, z["experts"]), "normal"),
                      (f"{p}.moe.corr_bias", (z["experts"],), "zeros"),
                      (f"{p}.moe.w1", (held, d, f), "normal"),
                      (f"{p}.moe.w3", (held, d, f), "normal"),
                      (f"{p}.moe.w2", (held, f, d), "normal")]
    specs.append(("final_norm.w", (d,), "ones"))
    return specs


def make_weights(cfg: dict, seed: int, batches=None, devices=None) -> dict:
    """Every weight, on the device, float32 (the master precision), in one
    jitted call. `batches` is not needed: every weight is made (the routers'
    expert biases zero).

    Where the configuration names a `weights_seed`, the weights are that one
    draw whatever `seed` is, and `seed` decides the batches alone: which
    experts a freshly drawn router favours decides how many (token, expert)
    pairs fall on the experts held, so a draw for each run gives every run
    another amount of work (lfm2_24b_a2b.json, `assumed.weights`)."""
    specs = weight_specs(cfg)
    std = cfg["initializer_range"]

    def make(key):
        out = {}
        for i, (name, shape, init) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            if init == "normal":
                out[name] = std * jax.random.normal(k, shape, jnp.float32)
            elif init == "conv":
                bound = 1.0 / math.sqrt(shape[1])
                out[name] = jax.random.uniform(k, shape, jnp.float32,
                                               -bound, bound)
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


def short_filter(u, w):
    """u [T, C], w [C, K]: v[t] = sum_j w[:, j] u[t - (K-1) + j], u before
    the start taken as zero."""
    t, k = u.shape[0], w.shape[1]
    up = jnp.concatenate([jnp.zeros((k - 1, u.shape[1]), u.dtype), u])
    return sum(up[j:j + t] * w[:, j] for j in range(k))


def conv_operator(x, params, p, cfg, mm=_mm):
    b, c, xs = jnp.split(mm(x, params[f"{p}.in_proj.w"]), 3, axis=-1)
    v = short_filter(b * xs, params[f"{p}.conv.w"])
    return mm(c * v, params[f"{p}.out_proj.w"])


def rotate_half(x, theta):
    """x [T, heads, d]: channel j of a head pairs with channel j + d/2, the
    pair turned by the angle t * theta^(-2j/d) at position t."""
    t, _, d = x.shape
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def attention_operator(x, params, p, cfg, mm=_mm):
    z = sizes(cfg)
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   z["head_dim"])
    t = x.shape[0]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    qkv = mm(x, params[f"{p}.qkv.w"])
    q, k, v = jnp.split(qkv, [z["q_dim"], z["q_dim"] + z["kv_dim"]], axis=-1)
    q = rms_norm(q.reshape(t, nh, hd), params[f"{p}.q_norm.w"],
                 cfg["norm_eps"])
    k = rms_norm(k.reshape(t, nkv, hd), params[f"{p}.k_norm.w"],
                 cfg["norm_eps"])
    q, k = rotate_half(q, theta), rotate_half(k, theta)
    q = q.reshape(t, nkv, nh // nkv, hd)       # query head = group * r + j
    v = v.reshape(t, nkv, hd)
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def one_head(qh, kh, vh):                  # [T, d] each
        s = mm(qh, kh.T) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return mm(probs, vh)

    def one_group(args):                       # a key/value head's queries
        qg, kh, vh = args                      # [r, T, d], [T, d], [T, d]
        return jax.lax.map(lambda qh: one_head(qh, kh, vh), qg)

    ctx = jax.lax.map(one_group, (q.transpose(1, 2, 0, 3),
                                  k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    ctx = ctx.transpose(2, 0, 1, 3).reshape(t, z["q_dim"])
    return mm(ctx, params[f"{p}.o.w"])


def gated_mlp(x, w1, w3, w2, mm=_mm):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def dense_ffn(x, params, p, cfg, mm=_mm):
    w1, w3 = jnp.split(params[f"{p}.gate_up.w"], 2, axis=-1)
    return gated_mlp(x, w1, w3, params[f"{p}.down.w"], mm)


def route(x, gate_w, cfg, bias=None):
    """(chosen experts [T, k], their weights [T, k]) over all the layer's
    experts: the choice is by score + expert bias, the weights are the plain
    scores over their sum plus 1e-6."""
    scores = jax.nn.sigmoid(jnp.matmul(x, gate_w, precision=_HI))
    choose = scores if bias is None else scores + jax.lax.stop_gradient(bias)
    _, idx = jax.lax.top_k(choose, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTER_NORM_EPS)
    return idx, w * cfg["routed_scaling_factor"]


def experts_ffn(x, params, p, cfg, mm=_mm, held=None):
    """The held experts' part: a loop over them, each over every token,
    weighted by the token's weight for it (0 where it was not chosen). An
    expert that is not held adds nothing. `held` = (first, count) reads the
    experts `first ..` of the layer from the first `count` of the weights."""
    first, count = held if held is not None else sizes(cfg)["held"]
    idx, w = route(x, params[f"{p}.moe.gate"], cfg,
                   params.get(f"{p}.moe.corr_bias"))
    out = jnp.zeros_like(x)
    for j in range(count):
        weight = jnp.sum(jnp.where(idx == first + j, w, 0.0), axis=-1)
        y = gated_mlp(x, params[f"{p}.moe.w1"][j], params[f"{p}.moe.w3"][j],
                      params[f"{p}.moe.w2"][j], mm)
        out = out + y * weight[:, None]
    return out


OPERATORS = {"conv": conv_operator, "full_attention": attention_operator}


def layer(x, params, i: int, cfg, mm=_mm):
    """Layer i of one sequence x [T, D]."""
    p = f"blk{i}"
    h = rms_norm(x, params[f"{p}.op_norm.w"], cfg["norm_eps"])
    x = x + OPERATORS[cfg["layer_types"][i]](h, params, p, cfg, mm=mm)
    h = rms_norm(x, params[f"{p}.ffn_norm.w"], cfg["norm_eps"])
    ffn = dense_ffn if is_dense(cfg, i) else experts_ffn
    return x + ffn(h, params, p, cfg, mm=mm)


def sum_loss(params: dict, ids, labels, cfg: dict, mm=_mm):
    """Sum over the positions of one sequence (ids, labels [T]) of the
    next-token cross entropy under the tied head; the caller divides by the
    step's positions."""
    table = params["embed.w"]
    x = table[ids]
    for i in range(len(cfg["layer_types"])):
        x = jax.checkpoint(partial(layer, i=i, cfg=cfg, mm=mm))(x, params)
    x = rms_norm(x, params["final_norm.w"], cfg["norm_eps"])
    rows = min(cfg["reference"]["head_rows"], x.shape[0])
    n = x.shape[0] // rows

    @jax.checkpoint
    def head(args):
        xc, lc = args
        logp = jax.nn.log_softmax(mm(xc, table.T), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lc[:, None], axis=-1))

    return jnp.sum(jax.lax.map(
        head, (x.reshape(n, rows, -1), labels.reshape(n, rows))))


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
    """Follow `len(batches)` Adam steps from `weights` (the first being step
    1 of the warm-up), one sequence at a time. `batches` are host feeds ({"ids": [B, T], "labels": [B, T, 1]},
    int32) as the traffic generator made them. Returns losses, the first
    gradient's norm by leaf and the norm of the parameters' change by leaf,
    as floats."""
    mm = _mm_int8 if control else _mm
    opt = cfg["optimizer"]

    frozen = {k: v for k, v in weights.items() if k.endswith(FROZEN)}
    weights = {k: v for k, v in weights.items() if k not in frozen}

    @partial(jax.jit, donate_argnums=(1, 2))
    def accumulate(params, grads, loss, ids, labels, inv_count):
        l, g = jax.value_and_grad(lambda p: sum_loss(
            dict(p, **frozen), ids, labels, cfg, mm) * inv_count)(params)
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
        inv_count = 1.0 / float(ids.size)
        grads, loss = zeros(weights), jnp.zeros((), jnp.float32)
        for row in range(ids.shape[0]):
            grads, loss = accumulate(params, grads, loss,
                                     jnp.asarray(ids[row]),
                                     jnp.asarray(labels[row]), inv_count)
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
