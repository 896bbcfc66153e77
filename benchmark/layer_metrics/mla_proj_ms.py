"""Device milliseconds a step, per chip, in latent attention's low-rank
products and the norms between them: unit paths (`models/joyai_flash.py`:
`blk<i>/attn/<part>`, `mtp/blk/attn/<part>`) holding `/attn/q_a`,
`/attn/q_norm`, `/attn/q_b`, `/attn/kv_a`, `/attn/kv_norm`, `/attn/kv_b` or
`/attn/o` — forward, recomputed forward and backward. Nothing where the step
has no such unit."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.part_ms(
        ctx, "/attn/q_a", "/attn/q_norm", "/attn/q_b", "/attn/kv_a",
        "/attn/kv_norm", "/attn/kv_b", "/attn/o")
