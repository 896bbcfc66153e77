"""Device milliseconds a step, per chip, in the rotary embedding: unit paths
(`models/ouro.py`: `blk<i>.u<t>/attn/rope`) holding `/attn/rope` — the split
of the fused product, the rotation of q and k, forward, recomputed forward
and backward. Nothing where the step has no such unit."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.part_ms(ctx, "/attn/rope")
