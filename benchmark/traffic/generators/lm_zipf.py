"""Next-token language-model batches: full-length sequences (documents packed
to length, no boundary reset), ids drawn from the configuration's vocabulary
by a Zipf law P(id) ~ 1 / (id + 1)^s (rank = id; natural text is close to
s = 1), every position labelled with the token that follows it. Every batch
of the ring differs; every seed gives the same sizes. Parameters come from
the traffic file."""
from __future__ import annotations

import numpy as np


def make_ring(cfg: dict, traffic: dict, seed: int) -> list:
    """`traffic["ring"]` host batches, as a user's reader would hand them to
    `Executor.run`: int32 `ids` [B, T] and `labels` [B, T, 1], the label of
    a position being the next position's id (T + 1 ids are drawn a row)."""
    b, t, v = traffic["batch"], traffic["seq_len"], cfg["vocab_size"]
    s = traffic["generator_params"]["zipf_exponent"]
    rng = np.random.default_rng([int(seed), 3])
    cdf = np.cumsum(1.0 / np.arange(1, v + 1, dtype=np.float64) ** s)
    cdf /= cdf[-1]
    ring = []
    for _ in range(traffic["ring"]):
        ids = np.minimum(np.searchsorted(cdf, rng.random((b, t + 1))),
                         v - 1).astype(np.int32)
        ring.append({"ids": np.ascontiguousarray(ids[:, :-1]),
                     "labels": np.ascontiguousarray(ids[:, 1:, None])})
    return ring
