"""Plain reference for DeepFM (Guo et al. 2017, arXiv:1703.04247, equations
1-4) trained with Adagrad on the embedding rows (Duchi et al. 2011) and Adam
on the dense net (Kingma & Ba 2015, section 2, the epsilon-hat form).

  y = sigmoid( sum_f w[id_f]  +  1/2 sum_d ((sum_f v[id_f])^2 - sum_f v[id_f]^2)_d
               +  MLP(concat_f v[id_f], dense) )
  loss = mean binary cross entropy

Float32, every matrix product at `Precision.HIGHEST`, no kernels. Only the
rows the followed steps touch are held: a small table indexed by the sorted
distinct ids. Duplicates of an id in a batch are merged (summed) before the
row's Adagrad update, which is what "exact" means in the configuration.

It imports nothing of the program and takes nothing the program made: dense
weights and the touched rows' initial values come from `make_weights`, from
the seed, and the harness writes the same values into the program's table.

`control=True` is the same mathematics carried out in bfloat16, rows,
accumulators and arithmetic: the nearest precision below the float32 the
configuration states. No benchmark run calls it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


def seed_key(seed: int, stream: int = 0):
    data = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(data, jnp.uint32),
                                    impl="threefry2x32")


def dense_specs(cfg: dict) -> list:
    """[(leaf, shape)] of the dense net, named as the program names them."""
    widths = [cfg["num_fields"] * cfg["embedding_dim"] + cfg["num_dense"]]
    widths += list(cfg["hidden_sizes"])
    specs = []
    for i in range(len(cfg["hidden_sizes"])):
        specs += [(f"deep_{i}.w_0", (widths[i], widths[i + 1])),
                  (f"deep_{i}.b_0", (widths[i + 1],))]
    specs += [("deep_out.w_0", (widths[-1], 1)), ("deep_out.b_0", (1,))]
    return specs


def make_weights(cfg: dict, seed: int, batches, devices=None) -> dict:
    """{"dense": {leaf: array}, "row_ids": int32 [n] the sorted distinct ids
    of `batches`, padded to n = all their lookups with the first id past the
    table, "rows": float32 [n, D+1] their initial embedding and first-order
    weight (zero for the pads)}, in one jitted call from the seed. A row's
    values depend on the seed and its id alone."""
    specs = dense_specs(cfg)
    width = cfg["embedding_dim"] + 1
    r = cfg["table_init_range"]
    found = np.unique(np.concatenate(
        [b["sparse_ids"].reshape(-1) for b in batches]))
    # a fixed length whatever the seed draws (one program for every seed):
    # padded with the first id past the table, which sorts last
    capacity = sum(b["sparse_ids"].size for b in batches)
    row_ids = np.full(capacity, cfg["table_rows"], np.int32)
    row_ids[:found.size] = found

    def make(key, ids):
        dense = {}
        for i, (name, shape) in enumerate(specs):
            if len(shape) == 2:
                lim = math.sqrt(6.0 / (shape[0] + shape[1]))
                dense[name] = jax.random.uniform(
                    jax.random.fold_in(key, i), shape, jnp.float32, -lim, lim)
            else:
                dense[name] = jnp.zeros(shape, jnp.float32)
        row_key = jax.random.fold_in(key, 1000)
        rows = jax.vmap(lambda i: jax.random.uniform(
            jax.random.fold_in(row_key, i), (width,), jnp.float32, -r, r))(ids)
        return dense, jnp.where((ids < cfg["table_rows"])[:, None], rows, 0.0)

    dense, rows = jax.jit(make)(seed_key(seed), jnp.asarray(row_ids))
    return {"dense": dense, "row_ids": row_ids, "rows": rows}


def loss_fn(dense: dict, gathered, batch: dict, cfg: dict, dtype):
    """`gathered` [B, F, D+1]: the rows of this batch's ids."""
    d = cfg["embedding_dim"]
    emb, w1 = gathered[..., :d], gathered[..., d]
    first = jnp.sum(w1, axis=1)
    summed = jnp.sum(emb, axis=1)
    second = 0.5 * jnp.sum(summed * summed - jnp.sum(emb * emb, axis=1),
                           axis=-1)
    x = jnp.concatenate([emb.reshape(emb.shape[0], -1),
                         batch["dense"].astype(dtype)], axis=1)
    for i in range(len(cfg["hidden_sizes"])):
        x = jax.nn.relu(jnp.matmul(x, dense[f"deep_{i}.w_0"], precision=_HI)
                        + dense[f"deep_{i}.b_0"])
    deep = (jnp.matmul(x, dense["deep_out.w_0"], precision=_HI)
            + dense["deep_out.b_0"])[:, 0]
    logit = (first + second + deep).astype(jnp.float32)
    y = batch["label"][:, 0]
    # binary cross entropy on the logit: max(z,0) - z*y + log(1 + exp(-|z|))
    bce = (jnp.maximum(logit, 0) - logit * y
           + jnp.log1p(jnp.exp(-jnp.abs(logit))))
    return jnp.mean(bce)


def follow(cfg: dict, weights: dict, batches: list, devices=None,
           control: bool = False, seed: int = 0) -> dict:
    dtype = jnp.bfloat16 if control else jnp.float32
    d = cfg["embedding_dim"]
    topt, dopt = cfg["table_optimizer"], cfg["dense_optimizer"]
    row_ids = weights["row_ids"]
    n = row_ids.shape[0]

    @jax.jit
    def step(dense, m, v, rows, acc, t, idx, batch):
        def f(dense_, gathered):
            return loss_fn(dense_, gathered, batch, cfg, dtype)
        gathered = rows[idx]
        loss, (g_dense, g_rows) = jax.value_and_grad(f, argnums=(0, 1))(
            dense, gathered)
        # merge duplicates, then Adagrad on the touched rows
        merged = jnp.zeros((n, d + 1), dtype).at[idx.reshape(-1)].add(
            g_rows.reshape(-1, d + 1))
        acc = (acc + merged * merged).astype(dtype)
        rows = (rows - topt["learning_rate"] * merged
                / (jnp.sqrt(acc) + topt["epsilon"])).astype(dtype)
        # Adam on the dense net
        b1, b2 = dopt["beta1"], dopt["beta2"]
        lr_t = dopt["learning_rate"] * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        new_d, new_m, new_v = {}, {}, {}
        for k, g in g_dense.items():
            g = g.astype(jnp.float32)
            new_m[k] = b1 * m[k] + (1 - b1) * g
            new_v[k] = b2 * v[k] + (1 - b2) * g * g
            new_d[k] = (dense[k].astype(jnp.float32) - lr_t * new_m[k]
                        / (jnp.sqrt(new_v[k]) + dopt["epsilon"])).astype(dtype)
        return (new_d, new_m, new_v, rows, acc, loss.astype(jnp.float32),
                g_dense, merged)

    dense = {k: w.astype(dtype) for k, w in weights["dense"].items()}
    m = {k: jnp.zeros(w.shape, jnp.float32) for k, w in dense.items()}
    v = {k: jnp.zeros(w.shape, jnp.float32) for k, w in dense.items()}
    rows = weights["rows"].astype(dtype)
    acc = jnp.full(rows.shape, topt["initial_accumulator_value"], dtype)
    losses, grad_norms = [], None

    def norm(x):
        return float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))

    for t, batch in enumerate(batches, start=1):
        idx = jnp.asarray(np.searchsorted(row_ids, batch["sparse_ids"]))
        feed = {"dense": jnp.asarray(batch["dense"]),
                "label": jnp.asarray(batch["label"])}
        dense, m, v, rows, acc, loss, g_dense, merged = step(
            dense, m, v, rows, acc, jnp.float32(t), idx, feed)
        losses.append(float(loss))
        if t == 1:
            grad_norms = {k: norm(g) for k, g in g_dense.items()}
            grad_norms["fm_t.embedding"] = norm(merged[:, :d])
            grad_norms["fm_t.first_order"] = norm(merged[:, d])
    update_norms = {k: norm(dense[k].astype(jnp.float32) - weights["dense"][k])
                    for k in dense}
    moved = rows.astype(jnp.float32) - weights["rows"]
    update_norms["fm_t.embedding"] = norm(moved[:, :d])
    update_norms["fm_t.first_order"] = norm(moved[:, d])
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms}
