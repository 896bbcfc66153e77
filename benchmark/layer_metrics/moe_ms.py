"""Device milliseconds a step, per chip, in the mixture-of-experts MLPs: unit
paths holding `/moe` (`blk<i>.E/moe/<router|dispatch|experts|combine|shared>`),
all phases. Nothing where the step has no such unit."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.part_ms(ctx, "/moe")
