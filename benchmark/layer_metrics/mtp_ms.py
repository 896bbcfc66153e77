"""Device milliseconds a step, per chip, in the multi-token-prediction
module: unit paths (`models/joyai_flash.py`) that are `mtp` or start with
`mtp/` — the second lookup, the two norms, `eh_proj`, the module's block
(latent attention and experts), its final norm, its head and its loss, all
phases, a loop counted once. What the second prediction costs a step.
Nothing where the step has no such unit."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.leaf_ms(
        ctx, lambda unit: unit == "mtp" or unit.startswith("mtp/"))
