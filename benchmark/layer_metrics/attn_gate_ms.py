"""Device milliseconds a step, per chip, in the attention's per-head output
gate: unit paths (`models/laguna.py`: `blk<i>/attn/gate`) holding
`/attn/gate` — the product of width n_heads, its sigmoid and the multiply on
the kernel's result, forward, recomputed forward and backward. Nothing where
the step has no such unit."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.part_ms(ctx, "/attn/gate")
