"""Device milliseconds a step, per chip, in the Kimi Delta Attention mixers:
unit paths (`models/kimi_linear.py`: `blk<i>/kda/<part>`) holding `/kda/` —
the projections, the filters, the norms, the gates, the delta rule and the
output product, forward, recomputed forward and backward alike. Nothing where
the step has no such unit."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.part_ms(ctx, "/kda/")
