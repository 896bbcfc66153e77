"""Device milliseconds a step, per chip, in the shared expert beside the
routed ones: unit paths (`models/joyai_flash.py`, `models/nemotron_h.py`)
holding `/moe/shared` — its gate and up product, the activation and the down
product, forward, recomputed forward and backward. Nothing where the step
has no such unit."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.part_ms(ctx, "/moe/shared")
