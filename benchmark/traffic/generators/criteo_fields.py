"""Criteo-shaped click batches. Each categorical field draws a rank from a
bounded power law over its own cardinality (density proportional to
x^-exponent on [1, C + 1), floored: p(rank) ~ rank^-exponent), maps ranks to
ids by a seeded affine bijection modulo the cardinality (so hot ids are
scattered, not the low ones), offsets the id by its field and folds it into the
table. Dense features are uniform, labels Bernoulli. Every seed gives the same
sizes; only the ids, features and labels differ. A mix may carry
`field_cardinalities` of its own in place of the configuration's (equal ones
with exponent 0 give ids uniform over the table)."""
from __future__ import annotations

import math

import numpy as np


def _ranks(rng, n, cardinality: int, exponent: float) -> np.ndarray:
    u = rng.random(n)
    if abs(exponent - 1.0) < 1e-9:
        x = np.exp(u * math.log(cardinality + 1.0))
    else:
        top = (cardinality + 1.0) ** (1.0 - exponent)
        x = (1.0 + u * (top - 1.0)) ** (1.0 / (1.0 - exponent))
    return np.minimum(x.astype(np.int64), cardinality) - 1      # 0 .. C-1


def _bijection(rng, cardinality: int):
    """(a, b) with gcd(a, C) = 1: rank -> (a * rank + b) mod C is one to one."""
    while True:
        a = int(rng.integers(1, max(2, cardinality)))
        if math.gcd(a, cardinality) == 1:
            return a, int(rng.integers(0, cardinality))


def make_ring(cfg: dict, traffic: dict, seed: int) -> list:
    b, n = traffic["batch"], traffic["ring"]
    params = traffic["generator_params"]
    cards = params.get("field_cardinalities", cfg["field_cardinalities"])
    rng = np.random.default_rng([int(seed), 2])
    ids = np.empty((n * b, len(cards)), np.int64)
    offset = 0
    for f, card in enumerate(cards):
        a, shift = _bijection(rng, card)
        rank = _ranks(rng, n * b, card, params["zipf_exponent"])
        ids[:, f] = offset + (a * rank + shift) % card
        offset += card
    ids = (ids % cfg["table_rows"]).astype(np.int32).reshape(n, b, len(cards))
    dense = rng.random((n, b, cfg["num_dense"]), dtype=np.float32)
    label = (rng.random((n, b, 1)) < params["label_rate"]).astype(np.float32)
    return [{"sparse_ids": ids[i], "dense": dense[i], "label": label[i]}
            for i in range(n)]
