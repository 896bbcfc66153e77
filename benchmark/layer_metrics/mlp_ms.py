"""Device milliseconds a step, per chip, in the gated MLPs: unit paths
(`models/ouro.py`: `blk<i>.u<t>/mlp/<gate_up, act, down>`) holding `/mlp/` —
the fused gate and up product, silu(gate) * up and the down product, forward,
recomputed forward and backward. Nothing where the step has no such unit."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.part_ms(ctx, "/mlp/")
