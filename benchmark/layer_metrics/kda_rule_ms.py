"""Device milliseconds a step, per chip, in the chunked gated delta rule alone
(`gated_delta_rule`, ops/linear_attn_ops.py: unit paths holding `/kda/rule`):
its forward pass, that pass made again behind the remat block, and its own
backward rule (the chunk-local terms made once more, the chunks walked
backwards). Nothing where the step has no such unit."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.part_ms(ctx, "/kda/rule")
