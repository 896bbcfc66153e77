"""Device milliseconds a step, per chip, in the per-head RMS norm of q and k
before the rotation: unit paths (`models/lfm2.py`: `blk<i>/attn/qk_norm`)
holding `/attn/qk_norm`, forward, recomputed forward and backward. Nothing
where the step has no such unit."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.part_ms(ctx, "/attn/qk_norm")
