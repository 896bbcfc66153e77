"""Plain reference for Ouro (ByteDance/Ouro-2.6B) next-token pretraining: a
looped decoder, one stack of layers run `total_ut_steps` times over the same
weights, every pass an exit, a learned gate weighting the exits' losses.

Written from the architecture's public description (the `ouro` `config.json`
named in ouro_2_6b.json; "Scaling Latent Reasoning via Looped Language
Models", 2025, for the loop, the exit gate and the first-stage objective; Su
et al. 2021 for the rotary embedding; Kingma & Ba 2015, section 2, for Adam)
in straightforward `jax.numpy`: float32 throughout, every matrix product at
`Precision.HIGHEST`, literal loops over the passes and the layers, plain
[T, T] attention with the rotation written out, full logits an exit in row
blocks, no kernels, no cache, one sequence at a time. It imports nothing of
the program and takes nothing the program made: the weights come from
`make_weights` below, which the harness also hands to the program.

    h <- Embed[ids]
    for pass t = 1..P:                       (the same weights every pass)
      for layer i = 0..L-1:
        a = Attn_i(RMSNorm1_i(h));  h <- h + RMSNorm2_i(a)
        m = MLP_i(RMSNorm3_i(h));   h <- h + RMSNorm4_i(m)
      h <- RMSNorm_f(h);  h_t = h            (exit t, and the next pass's input)
    Attn: q, k, v = xW_q, xW_k, xW_v (H heads of D); q, k rotated: channel
      pair (j, j + D/2) of a head by the angle pos * theta^(-2j/D);
      causal softmax(q k^T / sqrt(D)) v; . W_o
    MLP:  (silu(xW_gate) * xW_up) W_down
    z_t = h_t W_head;  lambda_t = sigmoid(h_t . w_g + b_g), t < P
    p_1 = lambda_1, p_t = lambda_t prod_{j<t}(1 - lambda_j),
    p_P = prod_{j<P}(1 - lambda_j)
    loss = mean over positions of sum_t p_t CE(z_t, y) - beta H(p),
    H(p) = -sum_t p_t log p_t

Departures from the published description, each also under `assumed` in the
json: (1) the depth is a pipeline stage's (the first `num_hidden_layers` of
the published 48), every pass crossing it; (2) W_q, W_k, W_v are the column
blocks of one [d, 3 H D] matrix `qkv.w` and W_gate, W_up of one [d, 2 F]
matrix `gate_up.w` (the same parameters, the program's names); (3) the place
of the four norms and of RMSNorm_f inside the loop, the rotate-half pairing,
the gate's bias, the remainder going to the last exit and beta are from
memory of the published modelling code and paper.

`control=True` is the same mathematics with every matrix product's operands
rounded to int8 (per-tensor absmax, forward and backward): the nearest
precision below the bf16 the configuration states. It exists to show that the
limits in ouro_2_6b.json fail it; no benchmark run calls it.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# what a reference shares with the others whatever the model: the seed's key,
# the int8 control's product, Adam and the norms by leaf
from benchmark.configs.ernie_base_reference import (  # noqa: F401
    _adam, _diff_norms, _leaf_norms, _mm_int8, seed_key)

_HI = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# sizes and weights, from the configuration and the seed
# ---------------------------------------------------------------------------

def weight_specs(cfg: dict) -> list:
    """[(leaf name, shape, init)]; the leaf names are the parameter names of
    paddle_tpu/models/ouro.py. init: "normal" (0, initializer_range), "ones",
    "zeros". One entry a layer's weight, whatever the number of passes."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    specs = [("embed.w", (cfg["vocab_size"], d), "normal")]
    for i in range(cfg["num_hidden_layers"]):
        p = f"blk{i}"
        specs += [(f"{p}.norm1.w", (d,), "ones"),
                  (f"{p}.qkv.w", (d, 3 * qd), "normal"),
                  (f"{p}.o.w", (qd, d), "normal"),
                  (f"{p}.norm2.w", (d,), "ones"),
                  (f"{p}.norm3.w", (d,), "ones"),
                  (f"{p}.gate_up.w", (d, 2 * f), "normal"),
                  (f"{p}.down.w", (f, d), "normal"),
                  (f"{p}.norm4.w", (d,), "ones")]
    specs += [("final_norm.w", (d,), "ones"),
              ("exit_gate.w", (d, 1), "zeros"),
              ("exit_gate.b", (1,), "zeros"),
              ("lm_head.w", (d, cfg["vocab_size"]), "normal")]
    return specs


def make_weights(cfg: dict, seed: int, batches=None, devices=None) -> dict:
    """Every weight, on the device, float32 (the master precision), in one
    jitted call from the seed. `batches` is not needed: every weight is
    made."""
    specs = weight_specs(cfg)
    std = cfg["initializer_range"]

    def make(key):
        out = {}
        for i, (name, shape, init) in enumerate(specs):
            if init == "normal":
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            else:
                out[name] = jnp.full(shape, 1.0 if init == "ones" else 0.0,
                                     jnp.float32)
        return out

    return jax.jit(make)(seed_key(seed))


# ---------------------------------------------------------------------------
# the layer (one sequence: x [T, D])
# ---------------------------------------------------------------------------

def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def rope(x, theta: float):
    """x [T, H, D]: channel j < D/2 of a head pairs with channel j + D/2;
    the pair at position t turns by t * theta^(-2j/D)."""
    t, _, d = x.shape
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def attention(x, params, p, cfg, mm=_mm):
    nh, hd = cfg["num_attention_heads"], cfg["head_dim"]
    t = x.shape[0]
    q, k, v = jnp.split(mm(x, params[f"{p}.qkv.w"]), 3, axis=-1)
    q = rope(q.reshape(t, nh, hd), float(cfg["rope_theta"]))
    k = rope(k.reshape(t, nh, hd), float(cfg["rope_theta"]))
    v = v.reshape(t, nh, hd)
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def one_head(args):                        # [T, D] each
        qh, kh, vh = args
        s = mm(qh, kh.T) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return mm(probs, vh)

    ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                                 v.transpose(1, 0, 2)))
    return mm(ctx.transpose(1, 0, 2).reshape(t, nh * hd), params[f"{p}.o.w"])


def mlp(x, params, p, mm=_mm):
    gate, up = jnp.split(mm(x, params[f"{p}.gate_up.w"]), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, params[f"{p}.down.w"])


def layer(h, params, i: int, cfg, mm=_mm):
    """Layer i applied once to one sequence h [T, D]."""
    p, eps = f"blk{i}", cfg["rms_norm_eps"]
    a = attention(rms_norm(h, params[f"{p}.norm1.w"], eps), params, p, cfg, mm)
    h = h + rms_norm(a, params[f"{p}.norm2.w"], eps)
    m = mlp(rms_norm(h, params[f"{p}.norm3.w"], eps), params, p, mm)
    return h + rms_norm(m, params[f"{p}.norm4.w"], eps)


def exit_states(params: dict, ids, cfg: dict, mm=_mm, layer_params=None):
    """[h_1, ..., h_P] of one sequence (ids [T]), each [T, D]. `layer_params`
    (pass, layer) -> a parameter dict and prefix index lets a test hand every
    application weights of its own (the untied model of the loop test)."""
    h = params["embed.w"][ids]
    out = []
    for t in range(cfg["total_ut_steps"]):
        for i in range(cfg["num_hidden_layers"]):
            src, j = (params, i) if layer_params is None else layer_params(t, i)
            h = jax.checkpoint(partial(layer, i=j, cfg=cfg, mm=mm))(h, src)
        h = rms_norm(h, params["final_norm.w"], cfg["rms_norm_eps"])
        out.append(h)
    return out


def exit_distribution(states, params):
    """p [P, T] from the states [P, T, D]: the gate of pass t < P says how
    much of what has not left yet leaves at t; the last pass takes the rest."""
    lam = jax.nn.sigmoid(jnp.matmul(states[:-1], params["exit_gate.w"],
                                    precision=_HI)[..., 0]
                         + params["exit_gate.b"][0])
    p, left = [], jnp.ones_like(lam[0])
    for t in range(lam.shape[0]):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(p + [left])


def cross_entropies(states, labels, params, cfg, mm=_mm):
    """CE [P, T] of every exit's logits against the labels [T], the logits
    made `reference.head_rows` rows at a time."""
    rows = min(cfg["reference"]["head_rows"], labels.shape[0])
    n = labels.shape[0] // rows

    @jax.checkpoint
    def head(args):
        xc, lc = args
        logp = jax.nn.log_softmax(mm(xc, params["lm_head.w"]), axis=-1)
        return -jnp.take_along_axis(logp, lc[:, None], axis=-1)[:, 0]

    def one_exit(h):
        return jax.lax.map(head, (h.reshape(n, rows, -1),
                                  labels.reshape(n, rows))).reshape(-1)

    return jnp.stack([one_exit(h) for h in states])


def sum_loss(params: dict, ids, labels, cfg: dict, mm=_mm, layer_params=None):
    """(sum over the positions of one sequence of sum_t p_t CE_t - beta H(p),
    the sums of p [P] and of H(p)); the caller divides by the positions."""
    states = jnp.stack(exit_states(params, ids, cfg, mm, layer_params))
    p = exit_distribution(states, params)
    ce = cross_entropies(states, labels, params, cfg, mm)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    loss = jnp.sum(jnp.sum(p * ce, axis=0) - cfg["entropy_beta"] * entropy)
    return loss, (jnp.sum(p, axis=1), jnp.sum(entropy))


# ---------------------------------------------------------------------------
# following the optimizer
# ---------------------------------------------------------------------------

def follow(cfg: dict, weights: dict, batches: list, devices=None,
           control: bool = False, seed: int = 0) -> dict:
    """Follow `len(batches)` Adam steps from `weights`, one sequence at a
    time. `batches` are host feeds ({"ids": [B, T], "labels": [B, T, 1]},
    int32) as the traffic generator made them. Returns losses, the first
    gradient's norm by leaf and the norm of the parameters' change by leaf,
    as floats, and the first step's exit shares and mean exit entropy."""
    mm = _mm_int8 if control else _mm
    opt = cfg["optimizer"]

    @partial(jax.jit, donate_argnums=(1, 2))
    def accumulate(params, grads, sums, ids, labels, inv_count):
        (l, aux), g = jax.value_and_grad(
            lambda p: sum_loss(p, ids, labels, cfg, mm), has_aux=True)(params)
        sums = jax.tree_util.tree_map(
            lambda a, b: a + b * inv_count, sums, (l, aux))
        return jax.tree_util.tree_map(lambda a, b: a + b * inv_count,
                                      grads, g), sums

    adam = jax.jit(partial(_adam, lr=opt["learning_rate"], b1=opt["beta1"],
                           b2=opt["beta2"], eps=opt["epsilon"]),
                   donate_argnums=(0, 1, 2, 3))
    zeros = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree))

    # the harness keeps `weights` on the device; beside them the parameters,
    # the gradient and its temporaries fill the chip, so Adam's two moments
    # wait on the host while a gradient is made
    params = jax.tree_util.tree_map(jnp.copy, weights)
    moments = None
    losses, grad_norms, exits = [], None, None
    for t, batch in enumerate(batches, start=1):
        ids = np.asarray(batch["ids"])
        labels = np.asarray(batch["labels"]).reshape(ids.shape)
        inv_count = 1.0 / float(ids.size)
        grads = zeros(weights)
        sums = (jnp.zeros((), jnp.float32),
                (jnp.zeros((cfg["total_ut_steps"],), jnp.float32),
                 jnp.zeros((), jnp.float32)))
        for row in range(ids.shape[0]):
            grads, sums = accumulate(params, grads, sums,
                                     jnp.asarray(ids[row]),
                                     jnp.asarray(labels[row]), inv_count)
        losses.append(float(sums[0]))
        if t == 1:
            grad_norms = {k: float(n) for k, n in
                          jax.jit(_leaf_norms)(grads).items()}
            exits = {"exit_share": [float(s) for s in sums[1][0]],
                     "exit_entropy": float(sums[1][1])}
        m, v = ((zeros(weights), zeros(weights)) if moments is None
                else jax.device_put(moments))
        params, m, v = adam(params, grads, m, v, jnp.float32(t))
        if t < len(batches):
            moments = jax.device_get((m, v))
        del m, v, grads
    update_norms = {k: float(n) for k, n in
                    jax.jit(_diff_norms)(params, weights).items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms, **exits}
