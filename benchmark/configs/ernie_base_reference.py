"""Plain reference for ERNIE 1.0 / BERT-base masked-LM pretraining.

Written from the papers' equations (Devlin et al. 2018, arXiv:1810.04805,
section 3 and appendix A.2; Vaswani et al. 2017 for the encoder block; Kingma
& Ba 2015, section 2, for Adam): post-LN encoder blocks, exact (erf) GELU,
an untied output matrix over all positions, cross entropy averaged over the
positions that carry a label. Float32 throughout, every matrix product at
`Precision.HIGHEST`, no kernels, no cache.

Dropout (BERT's 0.1 "on all layers": on the embeddings' sum after its layer
norm, on each sub-layer's output before it is added to the residual, and on
the attention probabilities) draws its masks from the reference's own
generator, from the seed. The program draws other masks, from generators no
reference can follow (the TPU's PRNG inside the attention kernel), so the
comparison is of statistics that a mask hardly moves: the step's loss and,
leaf by leaf, the norms of the gradient and of the parameters' change. The
limits in ernie_base.json were read with both sides under their own masks.

It imports nothing of the program and takes nothing the program made: the
weights come from `make_weights` below (the benchmark's own, from the seed),
which the harness also hands to the program.

`follow` walks the first optimizer steps in blocks of rows, so that the
float32 activations of a block fit beside the weights, and returns the three
kinds of number the harness compares (see benchmark/check.py).

`control=True` is the same mathematics with every matrix product's operands
rounded to int8 (per-tensor absmax, forward and backward): the nearest
precision below the bf16 the configuration states, under masks of a third
stream, as a program in that precision would have its own. It exists to show
that the limits in ernie_base.json fail it; no benchmark run calls it.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
IGNORE = -100


# ---------------------------------------------------------------------------
# weights, from the seed
# ---------------------------------------------------------------------------

def weight_specs(cfg: dict) -> list:
    """[(leaf name, shape, init)] with init one of "normal", "zeros", "ones".
    Leaf names are the parameter names the program's model file uses."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    specs = [("word_embedding", (v, h), "normal"),
             ("pos_embedding", (cfg["max_position_embeddings"], h), "normal"),
             ("sent_embedding", (cfg["type_vocab_size"], h), "normal"),
             ("emb.ln.scale", (h,), "ones"), ("emb.ln.bias", (h,), "zeros")]
    for i in range(cfg["num_hidden_layers"]):
        p = f"encoder_{i}"
        specs += [(f"{p}.qkv.w", (h, 3 * h), "normal"),
                  (f"{p}.qkv.b", (3 * h,), "zeros"),
                  (f"{p}.attn_out.w", (h, h), "normal"),
                  (f"{p}.attn_out.b", (h,), "zeros"),
                  (f"{p}.ln1.scale", (h,), "ones"),
                  (f"{p}.ln1.bias", (h,), "zeros"),
                  (f"{p}.ffn1.w", (h, f), "normal"),
                  (f"{p}.ffn1.b", (f,), "zeros"),
                  (f"{p}.ffn2.w", (f, h), "normal"),
                  (f"{p}.ffn2.b", (h,), "zeros"),
                  (f"{p}.ln2.scale", (h,), "ones"),
                  (f"{p}.ln2.bias", (h,), "zeros")]
    specs += [("mlm_out.w", (h, v), "normal"), ("mlm_out.b", (v,), "zeros")]
    return specs


def seed_key(seed: int, stream: int = 0):
    """A threefry key from any whole-number seed (the driver's are larger
    than 32 signed bits hold)."""
    data = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(data, jnp.uint32),
                                    impl="threefry2x32")


def _mesh(devices):
    from jax.sharding import Mesh
    return Mesh(np.asarray(devices), ("rows",))


def make_weights(cfg: dict, seed: int, batches=None, devices=None) -> dict:
    """Every weight, on the device (replicated where there are several), in
    one jitted call from the seed: normal(0, initializer_range) matrices and
    embeddings, zero biases, unit layer-norm scales, float32 (the master
    precision). `batches` is not needed: every weight is made."""
    from jax.sharding import NamedSharding, PartitionSpec
    sharding = None
    if devices is not None and len(devices) > 1:
        sharding = NamedSharding(_mesh(devices), PartitionSpec())
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

    return jax.jit(make, out_shardings=sharding)(seed_key(seed))


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


def _q8(x):
    s = jnp.max(jnp.abs(x))
    s = jnp.where(s > 0, s, 1.0)
    return jnp.round(x / s * 127.0) * (s / 127.0)


@jax.custom_vjp
def _mm_int8(a, b):
    return jnp.matmul(_q8(a), _q8(b), precision=_HI)


def _mm_int8_fwd(a, b):
    return _mm_int8(a, b), (a, b)


def _mm_int8_bwd(res, g):
    a, b = res
    g8 = _q8(g)
    return (jnp.matmul(g8, jnp.swapaxes(_q8(b), -1, -2), precision=_HI),
            jnp.matmul(jnp.swapaxes(_q8(a), -1, -2), g8, precision=_HI))


_mm_int8.defvjp(_mm_int8_fwd, _mm_int8_bwd)


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _dropout(x, rate: float, key, site: int):
    """Inverted dropout: zero with probability `rate`, the rest scaled by
    1 / (1 - rate). `site` numbers the place in the network."""
    if not rate:
        return x
    keep = jax.random.bernoulli(jax.random.fold_in(key, site), 1.0 - rate,
                                x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


def sum_loss(params: dict, batch: dict, cfg: dict, mm=_mm, key=None):
    """Sum of the masked-LM cross entropy over the labelled positions of
    `batch` (rows [B, T]); the caller divides by the step's label count.
    `key` draws the dropout masks; without one there is no dropout."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    hd, eps = h // nh, cfg["layer_norm_eps"]
    p_hidden = cfg["hidden_dropout_prob"] if key is not None else 0.0
    p_attn = cfg["attention_probs_dropout_prob"] if key is not None else 0.0
    src = batch["src_ids"]
    b, t = src.shape

    def linear(x, w, bias):        # x [B*T, in]
        return mm(x, params[w]) + params[bias]

    x = (params["word_embedding"][src]
         + params["pos_embedding"][batch["pos_ids"]]
         + params["sent_embedding"][batch["sent_ids"]])
    x = _layer_norm(x, params["emb.ln.scale"], params["emb.ln.bias"], eps)
    x = _dropout(x.reshape(b * t, h), p_hidden, key, 0)
    # additive key mask, 0 for a token and -10000 for padding
    key_bias = ((batch["input_mask"] - 1.0) * 10000.0)[:, None, None, :]

    def heads(y):
        return y.reshape(b, t, nh, hd).transpose(0, 2, 1, 3)

    for i in range(cfg["num_hidden_layers"]):
        p = f"encoder_{i}"
        qkv = linear(x, f"{p}.qkv.w", f"{p}.qkv.b")
        q, k, v = (heads(y) for y in jnp.split(qkv, 3, axis=-1))
        scores = mm(q, jnp.swapaxes(k, -1, -2)) / math.sqrt(hd) + key_bias
        probs = _dropout(jax.nn.softmax(scores, axis=-1), p_attn, key,
                         3 * i + 1)
        ctx = mm(probs, v).transpose(0, 2, 1, 3).reshape(b * t, h)
        attn = _dropout(linear(ctx, f"{p}.attn_out.w", f"{p}.attn_out.b"),
                        p_hidden, key, 3 * i + 2)
        x = _layer_norm(x + attn,
                        params[f"{p}.ln1.scale"], params[f"{p}.ln1.bias"], eps)
        ffn = _dropout(linear(_gelu(linear(x, f"{p}.ffn1.w", f"{p}.ffn1.b")),
                              f"{p}.ffn2.w", f"{p}.ffn2.b"),
                       p_hidden, key, 3 * i + 3)
        x = _layer_norm(x + ffn, params[f"{p}.ln2.scale"],
                        params[f"{p}.ln2.bias"], eps)

    logits = linear(x, "mlm_out.w", "mlm_out.b")            # [B*T, V]
    labels = batch["mlm_labels"].reshape(b * t)
    valid = labels != IGNORE
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.where(valid, labels, 0)[:, None], axis=-1)[:, 0]
    return -jnp.sum(jnp.where(valid, picked, 0.0))


# ---------------------------------------------------------------------------
# following the optimizer
# ---------------------------------------------------------------------------

def _leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def _diff_norms(a: dict, b: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k]))) for k in a}


def _adam(params, grads, m, v, t, lr, b1, b2, eps):
    """Kingma & Ba 2015, section 2, the form its last paragraph gives (and
    Paddle and TensorFlow implement): the bias corrections folded into the
    step size, epsilon beside the uncorrected sqrt(v)."""
    lr_t = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    out_p, out_m, out_v = {}, {}, {}
    for k, g in grads.items():
        out_m[k] = b1 * m[k] + (1.0 - b1) * g
        out_v[k] = b2 * v[k] + (1.0 - b2) * g * g
        out_p[k] = params[k] - lr_t * out_m[k] / (jnp.sqrt(out_v[k]) + eps)
    return out_p, out_m, out_v


def follow(cfg: dict, weights: dict, batches: list, devices=None,
           control: bool = False, seed: int = 0) -> dict:
    """Follow `len(batches)` Adam steps from `weights`. `batches` are host
    feeds (numpy, int32/float32) as the traffic generator made them. Rows go
    through in blocks of `cfg["reference"]["tokens_per_block"]` tokens a
    device, split over `devices` where there are several; each block of each
    step draws its own dropout masks from `seed`. Returns losses, the first
    gradient's norm by leaf and the norm of the parameters' change by leaf,
    as floats."""
    from jax.sharding import NamedSharding, PartitionSpec
    mm = _mm_int8 if control else _mm
    mask_key = seed_key(seed, stream=2 if control else 1)
    opt = cfg["optimizer"]
    n_dev = len(devices) if devices else 1
    seq = batches[0]["src_ids"].shape[1]
    rows_per_block = max(1, cfg["reference"]["tokens_per_block"] // seq) * n_dev
    if n_dev > 1:
        by_rows = NamedSharding(_mesh(devices), PartitionSpec("rows"))

        def place(block):
            return {k: jax.device_put(v, by_rows) for k, v in block.items()}
    else:
        def place(block):
            return {k: jnp.asarray(v) for k, v in block.items()}

    @partial(jax.jit, donate_argnums=(1, 2))
    def accumulate(params, grads, loss, block, inv_count, key):
        l, g = jax.value_and_grad(
            lambda p: sum_loss(p, block, cfg, mm, key) * inv_count)(params)
        return jax.tree_util.tree_map(jnp.add, grads, g), loss + l

    adam = jax.jit(partial(_adam, lr=opt["learning_rate"], b1=opt["beta1"],
                           b2=opt["beta2"], eps=opt["epsilon"]),
                   donate_argnums=(0, 1, 2, 3))
    zeros = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree))

    params = jax.tree_util.tree_map(jnp.copy, weights)
    m, v = zeros(weights), zeros(weights)
    losses, grad_norms = [], None
    for t, batch in enumerate(batches, start=1):
        rows = batch["src_ids"].shape[0]
        inv_count = 1.0 / float(np.sum(batch["mlm_labels"] != IGNORE))
        grads, loss = zeros(weights), jnp.zeros((), jnp.float32)
        for lo in range(0, rows, rows_per_block):
            block = {k: a[lo:lo + rows_per_block] for k, a in batch.items()}
            key = jax.random.fold_in(jax.random.fold_in(mask_key, t), lo)
            grads, loss = accumulate(params, grads, loss, place(block),
                                     inv_count, key)
        losses.append(float(loss))
        if t == 1:
            grad_norms = {k: float(n) for k, n in
                          jax.jit(_leaf_norms)(grads).items()}
        params, m, v = adam(params, grads, m, v, jnp.float32(t))
    update_norms = {k: float(n) for k, n in
                    jax.jit(_diff_norms)(params, weights).items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms}
