"""Masked-LM pretraining batches: full-length sequences (documents packed to
length), one segment, an all-ones mask, labels on a seeded share of positions
and -100 elsewhere. Every batch of the ring differs; every seed gives the same
sizes. Parameters come from the traffic file."""
from __future__ import annotations

import numpy as np

IGNORE = -100


def make_ring(cfg: dict, traffic: dict, seed: int) -> list:
    """`traffic["ring"]` host batches, as a user's reader would hand them to
    `Executor.run`: numpy arrays, int32 ids and float32 mask."""
    b, t = traffic["batch"], traffic["seq_len"]
    share = traffic["generator_params"]["mlm_share"]
    rng = np.random.default_rng([int(seed), 1])
    pos = np.tile(np.arange(t, dtype=np.int32), (b, 1))
    ring = []
    for _ in range(traffic["ring"]):
        src = rng.integers(0, cfg["vocab_size"], (b, t), dtype=np.int32)
        target = rng.integers(0, cfg["vocab_size"], (b, t, 1), dtype=np.int32)
        labelled = rng.random((b, t, 1)) < share
        ring.append({
            "src_ids": src,
            "pos_ids": pos,
            "sent_ids": np.zeros((b, t), np.int32),
            "input_mask": np.ones((b, t), np.float32),
            "mlm_labels": np.where(labelled, target, IGNORE).astype(np.int32),
        })
    return ring
