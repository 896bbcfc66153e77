"""Device milliseconds a step, per chip, in what the expert layer does beside
multiplying: the router (`/moe/router`), the sort of the pairs into tiles
(`/moe/dispatch`) and the sum of the routed and the shared parts
(`/moe/combine`), all phases."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.part_ms(ctx, "/moe/router", "/moe/dispatch",
                               "/moe/combine")
