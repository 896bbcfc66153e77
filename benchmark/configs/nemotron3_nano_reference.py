"""Plain reference for Nemotron-H (NVIDIA-Nemotron-3-Nano-30B-A3B) next-token
pretraining.

Written from the architecture's public description (the `nemotron_h`
`config.json` named in nemotron3_nano.json; Dao & Gu 2024, "Transformers are
SSMs", for the Mamba-2 mixer; Kingma & Ba 2015, section 2, for Adam) in
straightforward `jax.numpy`: float32 throughout, every matrix product at
`Precision.HIGHEST`, no kernels, no cache, one sequence at a time. It imports
nothing of the program and takes nothing the program made: the weights come
from `make_weights` below (the benchmark's own, from the seed or from the
configuration's `weights_seed`), which the harness also hands to the program.

The blocks (pre-norm residual, `x <- x + mixer(RMSNorm(x))`, eps `norm_eps`,
one mixer a block by `hybrid_override_pattern`, a final RMSNorm, an untied
output matrix; no dropout, no bias but the convolution's):

  `*`  q = xW_q (H x d), k, v = xW_k, xW_v (H_kv x d); causal
       softmax(q k^T / sqrt(d)) v with query head h reading key/value head
       h // (H / H_kv); out = . W_o. No position embedding.
  `M`  [z | xBC | dt] = xW_in; xBC <- silu(causal_conv1d(xBC) + b); split into
       x (heads x P), B, C (groups x N); dt <- softplus(dt + dt_bias);
       A = -exp(A_log); S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,
       y_t = S_t C_t + D x_t; y <- RMSNorm over groups of (y * silu(z)) times
       a learned weight; out = y W_out.
  `E`  s = sigmoid(xW_r) over all the layer's experts; the `top_k` largest of
       s + correction bias are chosen; their weights are s of the chosen over
       their sum, times `routed_scaling_factor`; expert e(x) =
       relu(xW_up)^2 W_down; out = sum over the chosen of w_e e(x) +
       shared(x), the shared expert of the same form.

Departures from the published description, each also under `assumed` in the
json: (1) a chip's share: only `experts_held` of each layer's experts are
here, and a (token, expert) pair on an absent expert adds nothing, as on that
chip of the deployment; the vocabulary is the chip's slice. (2) The scan runs
in chunks at the cell's size (`ssd_chunked`: a `lax.scan` over chunks that
carries the state, the positions of a chunk by the masked quadratic form) so
that its backward fits; `ssd_recurrence` is the literal recurrence over t,
and tests/test_nemotron_h.py holds the chunked form to it.

`control=True` is the same mathematics with every matrix product's operands
rounded to int8 (per-tensor absmax, forward and backward): the nearest
precision below the bf16 the configuration states. It exists to show that the
limits in nemotron3_nano.json fail it; no benchmark run calls it.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# what a reference shares with the other one whatever the model: the seed's
# key, the int8 control's product, Adam and the norms by leaf
from benchmark.configs.ernie_base_reference import (  # noqa: F401
    _adam, _diff_norms, _leaf_norms, _mm_int8, seed_key)

_HI = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# sizes and weights, from the configuration and the seed
# ---------------------------------------------------------------------------

def sizes(cfg: dict) -> dict:
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    return {
        "d": cfg["hidden_size"], "d_inner": heads * p, "gn": gn,
        "conv_dim": heads * p + 2 * gn,
        "q_dim": cfg["num_attention_heads"] * cfg["head_dim"],
        "kv_dim": cfg["num_key_value_heads"] * cfg["head_dim"],
        "experts": cfg.get("n_routed_experts_published",
                           cfg["n_routed_experts"]),
        "held": tuple(cfg.get("experts_held",
                              (0, cfg["n_routed_experts"]))),
    }


def weight_specs(cfg: dict) -> list:
    """[(leaf name, shape, init)]; the leaf names are the parameter names of
    paddle_tpu/models/nemotron_h.py. init: "normal" (0, initializer_range),
    "conv" (uniform +-1/sqrt(kernel), a depthwise Conv1d's default), "ones",
    "zeros", "a_log", "dt_bias" (Mamba-2's defaults). The router's
    correction bias (`*.moe.corr_bias`) is not trained: `FROZEN`."""
    z = sizes(cfg)
    d, heads = z["d"], cfg["mamba_num_heads"]
    specs = [("embed.w", (cfg["vocab_size"], d), "normal")]
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        p = f"blk{i}"
        specs.append((f"{p}.norm.w", (d,), "ones"))
        if kind == "M":
            specs += [
                (f"{p}.in_proj.w", (d, z["d_inner"] + z["conv_dim"] + heads),
                 "normal"),
                (f"{p}.conv.w", (z["conv_dim"], cfg["conv_kernel"]), "conv"),
                (f"{p}.conv.b", (z["conv_dim"],), "zeros"),
                (f"{p}.A_log", (heads,), "a_log"),
                (f"{p}.D", (heads,), "ones"),
                (f"{p}.dt_bias", (heads,), "dt_bias"),
                (f"{p}.gnorm.w", (z["d_inner"],), "ones"),
                (f"{p}.out_proj.w", (z["d_inner"], d), "normal")]
        elif kind == "*":
            specs += [(f"{p}.qkv.w", (d, z["q_dim"] + 2 * z["kv_dim"]),
                       "normal"),
                      (f"{p}.o.w", (z["q_dim"], d), "normal")]
        elif kind == "E":
            held, f = z["held"][1], cfg["moe_intermediate_size"]
            fs = cfg["moe_shared_expert_intermediate_size"]
            specs += [(f"{p}.moe.gate", (d, z["experts"]), "normal"),
                      (f"{p}.moe.corr_bias", (z["experts"],), "zeros"),
                      (f"{p}.moe.w1", (held, d, f), "normal"),
                      (f"{p}.moe.w2", (held, f, d), "normal"),
                      (f"{p}.shared.up.w", (d, fs), "normal"),
                      (f"{p}.shared.down.w", (fs, d), "normal")]
        else:
            raise ValueError(f"unknown block kind {kind!r}")
    specs += [("final_norm.w", (d,), "ones"),
              ("lm_head.w", (d, cfg["vocab_size"]), "normal")]
    return specs


FROZEN = ".moe.corr_bias"       # leaves no optimizer touches


def make_weights(cfg: dict, seed: int, batches=None, devices=None) -> dict:
    """Every weight, on the device, float32 (the master precision), in one
    jitted call from the seed. `batches` is not needed: every weight is
    made (the routers' correction biases zero).

    Where the configuration names a `weights_seed`, the weights are that one
    draw whatever `seed` is, and `seed` decides the batches alone: which
    experts a freshly drawn router favours decides how many (token, expert)
    pairs fall on the experts held, so a draw for each run gives every run
    another amount of work (nemotron3_nano.json, `assumed.weights`)."""
    specs = weight_specs(cfg)
    std, heads = cfg["initializer_range"], cfg["mamba_num_heads"]
    dt = jnp.maximum(jnp.exp(jnp.linspace(
        math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"]),
        heads)), cfg["time_step_floor"])

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
            elif init == "a_log":
                out[name] = jnp.log(jnp.arange(1, heads + 1,
                                               dtype=jnp.float32))
            elif init == "dt_bias":      # softplus^-1 of the time steps
                out[name] = (dt + jnp.log(-jnp.expm1(-dt))).astype(
                    jnp.float32)
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
# the blocks (one sequence: x [T, D])
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def causal_conv1d(x, w, b):
    """x [T, C], w [C, K], b [C]: y[t] = b + sum_j w[:, j] x[t - (K-1) + j],
    x before the start taken as zero."""
    t, k = x.shape[0], w.shape[1]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return b + sum(xp[j:j + t] * w[:, j] for j in range(k))


def ssd_recurrence(x, dt, a, b, c):
    """The literal recurrence over t. x [T, H, P]; dt [T, H] (> 0); a [H]
    (< 0); b, c [T, G, N], head h reading group h // (H / G). Returns
    y [T, H, P], without the D skip."""
    h, g = x.shape[1], b.shape[1]
    bh, ch = jnp.repeat(b, h // g, axis=1), jnp.repeat(c, h // g, axis=1)

    def step(state, inp):
        xt, dtt, bt, ct = inp
        state = (jnp.exp(dtt * a)[:, None, None] * state
                 + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, ct, precision=_HI)

    state0 = jnp.zeros((h, x.shape[2], b.shape[2]), jnp.float32)
    return jax.lax.scan(step, state0, (x, dt, bh, ch))[1]


def ssd_chunked(x, dt, a, b, c, chunk):
    """The same numbers in chunks of `chunk` positions: a scan over chunks
    carries the state; inside a chunk position l reads the entering state
    decayed to l plus the chunk's own earlier positions through the masked
    [chunk, chunk] form."""
    t, h, p = x.shape
    g, n = b.shape[1], b.shape[2]
    nc = t // chunk
    bh, ch = jnp.repeat(b, h // g, axis=1), jnp.repeat(c, h // g, axis=1)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    @jax.checkpoint
    def one_chunk(state, inp):
        xc, dtc, bc, cc = inp               # [L, H, P], [L, H], [L, H, N] x2
        cum = jnp.cumsum(dtc * a, axis=0)                    # [L, H]
        y_state = jnp.einsum("lhn,hpn->lhp", cc, state,
                             precision=_HI) * jnp.exp(cum)[:, :, None]
        gap = cum[:, None, :] - cum[None, :, :]              # [l, s, H]
        decay = jnp.where(causal[:, :, None], jnp.exp(
            jnp.where(causal[:, :, None], gap, 0.0)), 0.0)
        scores = jnp.einsum("lhn,shn->lsh", cc, bc, precision=_HI)
        y_local = jnp.einsum("lsh,shp->lhp", scores * decay * dtc[None],
                             xc, precision=_HI)
        to_end = jnp.exp(cum[-1][None] - cum) * dtc           # [L, H]
        state = (jnp.exp(cum[-1])[:, None, None] * state
                 + jnp.einsum("lhp,lhn->hpn", xc * to_end[:, :, None], bc,
                              precision=_HI))
        return state, y_state + y_local

    def split(v):
        return v.reshape((nc, chunk) + v.shape[1:])

    state0 = jnp.zeros((h, p, n), jnp.float32)
    _, y = jax.lax.scan(one_chunk, state0,
                        (split(x), split(dt), split(bh), split(ch)))
    return y.reshape(t, h, p)


def mamba_mixer(x, params, p, cfg, mm=_mm, scan=None):
    z = sizes(cfg)
    heads, hp = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    t = x.shape[0]
    zxbcdt = mm(x, params[f"{p}.in_proj.w"])
    gate, xbc, dt = jnp.split(
        zxbcdt, [z["d_inner"], z["d_inner"] + z["conv_dim"]], axis=-1)
    xbc = jax.nn.silu(causal_conv1d(xbc, params[f"{p}.conv.w"],
                                    params[f"{p}.conv.b"]))
    xs, b, c = jnp.split(xbc, [z["d_inner"], z["d_inner"] + z["gn"]], axis=-1)
    xs = xs.reshape(t, heads, hp)
    dt = jax.nn.softplus(dt + params[f"{p}.dt_bias"])
    a = -jnp.exp(params[f"{p}.A_log"])
    if scan is None:
        scan = partial(ssd_chunked, chunk=cfg["chunk_size"])
    y = scan(xs, dt, a, b.reshape(t, g, n), c.reshape(t, g, n))
    y = (y + params[f"{p}.D"][:, None] * xs).reshape(t, z["d_inner"])
    y = y * jax.nn.silu(gate)
    group = z["d_inner"] // g
    y = rms_norm(y.reshape(t, g, group), 1.0, cfg["norm_eps"]).reshape(
        t, z["d_inner"]) * params[f"{p}.gnorm.w"]
    return mm(y, params[f"{p}.out_proj.w"])


def attention_mixer(x, params, p, cfg, mm=_mm):
    z = sizes(cfg)
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    t = x.shape[0]
    qkv = mm(x, params[f"{p}.qkv.w"])
    q, k, v = jnp.split(qkv, [z["q_dim"], z["q_dim"] + z["kv_dim"]], axis=-1)
    q = q.reshape(t, nkv, nh // nkv, hd)       # query head = group * r + j
    k, v = k.reshape(t, nkv, hd), v.reshape(t, nkv, hd)
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


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def route(x, gate_w, cfg, bias=None):
    """(chosen experts [T, k], their weights [T, k]) over all the layer's
    experts: the choice is by score + correction bias, the weights are the
    plain scores."""
    scores = jax.nn.sigmoid(jnp.matmul(x, gate_w, precision=_HI))
    choose = scores if bias is None else scores + jax.lax.stop_gradient(bias)
    _, idx = jax.lax.top_k(choose, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w * cfg["routed_scaling_factor"]


def routed_experts(x, params, p, cfg, mm=_mm, held=None):
    """The held experts' part: a loop over them, each over every token,
    weighted by the token's weight for it (0 where it was not chosen). An
    expert that is not held adds nothing."""
    first, count = held if held is not None else sizes(cfg)["held"]
    idx, w = route(x, params[f"{p}.moe.gate"], cfg,
                   params.get(f"{p}.moe.corr_bias"))
    out = jnp.zeros_like(x)
    for j in range(count):
        weight = jnp.sum(jnp.where(idx == first + j, w, 0.0), axis=-1)
        y = mm(relu2(mm(x, params[f"{p}.moe.w1"][j])),
               params[f"{p}.moe.w2"][j])
        out = out + y * weight[:, None]
    return out


def shared_expert(x, params, p, mm=_mm):
    return mm(relu2(mm(x, params[f"{p}.shared.up.w"])),
              params[f"{p}.shared.down.w"])


def moe_mixer(x, params, p, cfg, mm=_mm):
    return routed_experts(x, params, p, cfg, mm) + shared_expert(
        x, params, p, mm)


MIXERS = {"M": mamba_mixer, "*": attention_mixer, "E": moe_mixer}


def block(x, params, i: int, cfg, mm=_mm):
    """Block i of one sequence x [T, D]."""
    kind = cfg["hybrid_override_pattern"][i]
    h = rms_norm(x, params[f"blk{i}.norm.w"], cfg["norm_eps"])
    return x + MIXERS[kind](h, params, f"blk{i}", cfg, mm=mm)


def sum_loss(params: dict, ids, labels, cfg: dict, mm=_mm):
    """Sum over the positions of one sequence (ids, labels [T]) of the
    next-token cross entropy; the caller divides by the step's positions."""
    x = params["embed.w"][ids]
    for i in range(len(cfg["hybrid_override_pattern"])):
        x = jax.checkpoint(partial(block, i=i, cfg=cfg, mm=mm))(x, params)
    x = rms_norm(x, params["final_norm.w"], cfg["norm_eps"])
    rows = cfg["reference"]["head_rows"]
    n = x.shape[0] // rows

    @jax.checkpoint
    def head(args):
        xc, lc = args
        logp = jax.nn.log_softmax(mm(xc, params["lm_head.w"]), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lc[:, None], axis=-1))

    return jnp.sum(jax.lax.map(
        head, (x.reshape(n, rows, -1), labels.reshape(n, rows))))


# ---------------------------------------------------------------------------
# following the optimizer
# ---------------------------------------------------------------------------

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
    def accumulate(params, grads, loss, ids, labels, inv_count):
        l, g = jax.value_and_grad(lambda p: sum_loss(
            dict(p, **frozen), ids, labels, cfg, mm) * inv_count)(params)
        return jax.tree_util.tree_map(jnp.add, grads, g), loss + l

    adam = jax.jit(partial(_adam, lr=opt["learning_rate"], b1=opt["beta1"],
                           b2=opt["beta2"], eps=opt["epsilon"]),
                   donate_argnums=(0, 1, 2, 3))
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
        params, m, v = adam(params, grads, m, v, jnp.float32(t))
        if t < len(batches):
            moments = jax.device_get((m, v))
        del m, v, grads
    update_norms = {k: float(n) for k, n in
                    jax.jit(_diff_norms)(params, weights).items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms}
