"""Device milliseconds a step, per chip, in the attention's projections: unit
paths (`models/laguna.py`: `blk<i>/attn/<qkv|o>`) holding `/attn/qkv` or
`/attn/o` — the fused q, k, v product and the output product at the layer's
own head count, forward, recomputed forward and backward. Nothing where the
step has no such unit."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.part_ms(ctx, "/attn/qkv", "/attn/o")
