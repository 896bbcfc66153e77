"""Device milliseconds a step, per chip, in the bandwidth passes around the
delta rule: unit paths (`models/kimi_linear.py`) holding `/kda/decay` (the
low-rank decay gate, softplus, the log-decay a channel), `/kda/beta`,
`/kda/out_gate` (the low-rank output gate, its sigmoid and product),
`/kda/out_norm` (the head-wise RMS norm) or `/kda/l2` (q's and k's l2
normalisation) — forward, recomputed forward and backward. Nothing where the
step has no such unit."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.part_ms(ctx, "/kda/decay", "/kda/beta",
                               "/kda/out_gate", "/kda/out_norm", "/kda/l2")
