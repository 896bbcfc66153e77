"""Device milliseconds a step, per chip, in the KDA mixers' three short
causal filters with their silu: unit paths (`models/kimi_linear.py`) holding
`/kda/conv` — forward, recomputed forward and backward. Nothing where the
step has no such unit."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.part_ms(ctx, "/kda/conv")
