"""Plain reference for JoyAI-LLM-Flash (jdopensource, `model_type`
`joyai_llm_flash`: the DeepSeek-V3 layout) pretraining with one
multi-token-prediction module.

Written from the architecture's public description (the `config.json` named
in joyai_llm_flash.json and the equations listed there under `assumed`; the
DeepSeek-V3 report, arXiv:2412.19437, sections 2.1-2.2; Su et al. 2021 for the
rotary embedding; Kingma & Ba 2015, section 2, for Adam) in straightforward
`jax.numpy`: float32 throughout, every matrix product at `Precision.HIGHEST`,
literal loops over heads and experts, the rotation written as pairs, no
kernels, no cache, one sequence and one layer at a time. It imports nothing
of the program and takes nothing the program made: the weights come from
`make_weights` below (the benchmark's own, from the configuration's
`weights_seed`), which the harness also hands to the program.

A layer (pre-norm residual twice, RMSNorm eps `rms_norm_eps` with one learnt
weight, no bias): h <- h + attn(RMSNorm(h)); h <- h + ffn(RMSNorm(h)).

  latent attention  c_q = RMSNorm(x W_qa); q = c_q W_qb -> H heads of
                    nope + rope channels. a = x W_kva; c_kv =
                    RMSNorm(a[:kv_lora_rank]); k_rope = a[kv_lora_rank:], one
                    head for all H. c_kv W_kvb -> H heads of k_nope ‖ v.
                    The rotation turns q's rope channels and k_rope:
                    channels (2j, 2j + 1) are a pair, turned by
                    t * theta^(-2j / rope). Head h: q_h = q_nope ‖ q_rope,
                    k_h = k_nope ‖ k_rope; causal softmax(q_h k_h^T
                    (nope + rope)^-1/2) v_h; out = [ctx_h] W_o
  ffn, dense        (silu(x W1) * x W3) W2 (W1 ‖ W3 one leaf, `gate_up`)
  ffn, experts      s = sigmoid(x W_g) over all the layer's experts; the
                    `top_k` largest of s + bias chosen; weights
                    s[chosen] / (sum s[chosen] + 1e-20) x
                    `routed_scaling_factor`; out = sum over the chosen of
                    w_e (silu(x W1e) * x W3e) W2e + shared(x), the shared
                    expert the same gated MLP, unweighted

then a final RMSNorm and the head (its own matrix): L_main, the mean cross
entropy with the next token. The prediction module: h'_i = [RMSNorm_e(
Emb(t_{i+1})) ‖ RMSNorm_h(h_i)] W_eh with h_i the trunk's state before the
final norm and the trunk's table, one expert layer of its own, RMSNorm, the
trunk's head matrix, cross entropy with t_{i+2} over the positions that have
one: L_mtp. The loss is L_main + `mtp_loss_weight` L_mtp.

Departure from the published description, also under `assumed` in the json:
a chip's share. Only `experts_held` of each layer's routed experts are here,
and a (token, expert) pair on an absent expert adds nothing, as on that chip
of the deployment; the vocabulary is the chip's slice.

`control=True` is the same mathematics with every matrix product's operands
rounded to int8 (per-tensor absmax, forward and backward): the nearest
precision below the bf16 the configuration states. It exists to show that the
limits in joyai_llm_flash.json fail it; no benchmark run calls it.
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
FROZEN = ".moe.corr_bias"       # leaves no optimizer touches
MTP = "mtp.blk"                 # the module's layer, by its leaves' prefix


# ---------------------------------------------------------------------------
# sizes and weights, from the configuration and the seed
# ---------------------------------------------------------------------------

def sizes(cfg: dict) -> dict:
    experts = cfg.get("n_routed_experts_published", cfg["n_routed_experts"])
    return {
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "dv": cfg["v_head_dim"], "q_rank": cfg["q_lora_rank"],
        "kv_rank": cfg["kv_lora_rank"], "experts": experts,
        "held": tuple(cfg.get("experts_held", (0, experts))),
        "shared": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
    }


def is_dense(cfg: dict, i: int) -> bool:
    return i < cfg["first_k_dense_replace"]


def _layer_specs(cfg: dict, p: str, dense: bool) -> list:
    z = sizes(cfg)
    d, nh = z["d"], z["heads"]
    specs = [(f"{p}.op_norm.w", (d,), "ones"),
             (f"{p}.q_a.w", (d, z["q_rank"]), "normal"),
             (f"{p}.q_a_norm.w", (z["q_rank"],), "ones"),
             (f"{p}.q_b.w", (z["q_rank"], nh * (z["nope"] + z["rope"])),
              "normal"),
             (f"{p}.kv_a.w", (d, z["kv_rank"] + z["rope"]), "normal"),
             (f"{p}.kv_a_norm.w", (z["kv_rank"],), "ones"),
             (f"{p}.kv_b.w", (z["kv_rank"], nh * (z["nope"] + z["dv"])),
              "normal"),
             (f"{p}.o.w", (nh * z["dv"], d), "normal"),
             (f"{p}.ffn_norm.w", (d,), "ones")]
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
    paddle_tpu/models/joyai_flash.py. init: "normal" (0, initializer_range),
    "ones", "zeros". The routers' biases (`*.moe.corr_bias`) are not
    trained: `FROZEN`."""
    d = cfg["hidden_size"]
    specs = [("embed.w", (cfg["vocab_size"], d), "normal")]
    for i in range(cfg["num_hidden_layers"]):
        specs += _layer_specs(cfg, f"blk{i}", is_dense(cfg, i))
    specs += [("final_norm.w", (d,), "ones"),
              ("lm_head.w", (d, cfg["vocab_size"]), "normal"),
              ("mtp.enorm.w", (d,), "ones"), ("mtp.hnorm.w", (d,), "ones"),
              ("mtp.eh_proj.w", (2 * d, d), "normal")]
    specs += _layer_specs(cfg, MTP, False)
    specs.append(("mtp.final_norm.w", (d,), "ones"))
    return specs


def make_weights(cfg: dict, seed: int, batches=None, devices=None) -> dict:
    """Every weight, on the device, float32 (the master precision), in one
    jitted call. `batches` is not needed: every weight is made (the routers'
    biases zero).

    Where the configuration names a `weights_seed`, the weights are that one
    draw whatever `seed` is, and `seed` decides the batches alone: which
    experts a freshly drawn router favours decides how many (token, expert)
    pairs fall on the experts held, so a draw for each run gives every run
    another amount of work (joyai_llm_flash.json, `assumed.weights`)."""
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


def rotate_pairs(x, theta):
    """x [T, heads, d]: channels (2j, 2j + 1) of a head are pair j, turned by
    the angle t * theta^(-2j/d) at position t (the interleaved convention,
    `rope_interleave`)."""
    t, _, d = x.shape
    inv_freq = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def latent_attention(x, params, p, cfg, mm=_mm):
    z = sizes(cfg)
    nh, nope, rope, dv = z["heads"], z["nope"], z["rope"], z["dv"]
    t, eps, theta = x.shape[0], cfg["rms_norm_eps"], float(cfg["rope_theta"])
    if not cfg["rope_interleave"]:
        raise ValueError("the reference rotates interleaved pairs: "
                         "rope_interleave false is another model")
    rotate = rotate_pairs
    c_q = rms_norm(mm(x, params[f"{p}.q_a.w"]), params[f"{p}.q_a_norm.w"],
                   eps)
    q = mm(c_q, params[f"{p}.q_b.w"]).reshape(t, nh, nope + rope)
    a = mm(x, params[f"{p}.kv_a.w"])
    c_kv = rms_norm(a[:, :z["kv_rank"]], params[f"{p}.kv_a_norm.w"], eps)
    k_rope = rotate(a[:, None, z["kv_rank"]:], theta)[:, 0]      # [T, rope]
    kv = mm(c_kv, params[f"{p}.kv_b.w"]).reshape(t, nh, nope + dv)
    q_rope = rotate(q[..., nope:], theta)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scale = 1.0 / math.sqrt(nope + rope)

    @jax.checkpoint
    def one_head(args):
        qn, qr, kn, vh = args              # [T, nope], [T, rope], ., [T, dv]
        qh = jnp.concatenate([qn, qr], axis=-1)
        kh = jnp.concatenate([kn, k_rope], axis=-1)     # the shared rope head
        s = mm(qh, kh.T) * scale
        probs = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return mm(probs, vh)

    heads_first = lambda y: y.transpose(1, 0, 2)
    ctx = jax.lax.map(one_head, (heads_first(q[..., :nope]),
                                 heads_first(q_rope),
                                 heads_first(kv[..., :nope]),
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
    _, idx = jax.lax.top_k(choose, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTER_NORM_EPS)
    return idx, w * cfg["routed_scaling_factor"]


def routed_experts(x, params, p, cfg, mm=_mm, held=None):
    """The held routed experts' part: a loop over them, each over every
    token, weighted by the token's weight for it (0 where it was not
    chosen). An expert that is not held adds nothing. `held` = (first,
    count) reads the experts `first ..` of the layer from the first `count`
    of the weights. (The loop is a `lax.scan`, one body for the layer's
    experts: unrolled, sixteen experts in five layers made an executable
    too large for the chip machines' compile cache, and every run compiled
    it anew: PERF.md section 6, PR 39.)"""
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


def layer(x, params, p: str, dense: bool, cfg, mm=_mm):
    """The layer whose leaves start with `p`, on one sequence x [T, D]."""
    h = rms_norm(x, params[f"{p}.op_norm.w"], cfg["rms_norm_eps"])
    x = x + latent_attention(h, params, p, cfg, mm=mm)
    h = rms_norm(x, params[f"{p}.ffn_norm.w"], cfg["rms_norm_eps"])
    return x + (dense_ffn if dense else experts_ffn)(h, params, p, cfg, mm=mm)


def _head_sum(x, head_w, labels, valid, rows: int, mm):
    """Sum over the rows of x [T, D] with `valid` of the cross entropy of
    x W_head against `labels`, `rows` rows at a time."""
    n = x.shape[0] // rows

    @jax.checkpoint
    def block(args):
        xc, lc, vc = args
        logp = jax.nn.log_softmax(mm(xc, head_w), axis=-1)
        picked = jnp.take_along_axis(logp, lc[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(vc, picked, 0.0))

    return jnp.sum(jax.lax.map(block, (x.reshape(n, rows, -1),
                                       labels.reshape(n, rows),
                                       valid.reshape(n, rows))))


def loss_sums(params: dict, ids, labels, cfg: dict, mm=_mm):
    """(main, mtp): the sums over one sequence (ids, labels [T]; a position's
    label is its next token) of the trunk's cross entropy over all T
    positions and of the module's over the first T - 1."""
    t, eps = ids.shape[0], cfg["rms_norm_eps"]
    table, head_w = params["embed.w"], params["lm_head.w"]
    x = table[ids]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(partial(layer, p=f"blk{i}",
                                   dense=is_dense(cfg, i), cfg=cfg, mm=mm))(
                                       x, params)
    rows = min(cfg["reference"]["head_rows"], t)
    every = jnp.ones((t,), bool)
    main = _head_sum(rms_norm(x, params["final_norm.w"], eps), head_w,
                     labels, every, rows, mm)
    # the module: the next token's embedding beside the trunk's state, one
    # layer, and the same head against the token after the next
    e = rms_norm(table[labels], params["mtp.enorm.w"], eps)
    h = rms_norm(x, params["mtp.hnorm.w"], eps)
    y = mm(jnp.concatenate([e, h], axis=-1), params["mtp.eh_proj.w"])
    y = jax.checkpoint(partial(layer, p=MTP, dense=False, cfg=cfg, mm=mm))(
        y, params)
    y = rms_norm(y, params["mtp.final_norm.w"], eps)
    targets = jnp.concatenate([labels[1:], labels[:1]])   # the last: unused
    has_target = jnp.arange(t) < t - 1
    mtp = _head_sum(y, head_w, targets, has_target, rows, mm)
    return main, mtp


def sum_loss(params, ids, labels, cfg, inv_main, inv_mtp, mm=_mm):
    """One sequence's part of the step's loss L_main + weight * L_mtp."""
    main, mtp = loss_sums(params, ids, labels, cfg, mm)
    return main * inv_main + cfg["mtp_loss_weight"] * mtp * inv_mtp


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
    int32) as the traffic generator made them. Returns losses (L_main +
    weight * L_mtp), the first gradient's norm by leaf and the norm of the
    parameters' change by leaf, as floats."""
    mm = _mm_int8 if control else _mm
    opt = cfg["optimizer"]

    frozen = {k: v for k, v in weights.items() if k.endswith(FROZEN)}
    weights = {k: v for k, v in weights.items() if k not in frozen}

    @partial(jax.jit, donate_argnums=(1, 2))
    def accumulate(params, grads, loss, ids, labels, inv_main, inv_mtp):
        l, g = jax.value_and_grad(lambda p: sum_loss(
            dict(p, **frozen), ids, labels, cfg, inv_main, inv_mtp, mm))(
                params)
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
        inv_main, inv_mtp = 1.0 / (b * length), 1.0 / (b * (length - 1))
        grads, loss = zeros(weights), jnp.zeros((), jnp.float32)
        for row in range(b):
            grads, loss = accumulate(params, grads, loss,
                                     jnp.asarray(ids[row]),
                                     jnp.asarray(labels[row]),
                                     inv_main, inv_mtp)
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
