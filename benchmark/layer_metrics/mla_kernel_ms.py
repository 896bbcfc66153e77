"""Device milliseconds a step, per chip, in latent attention's flash kernels,
taken by unit: unit paths (`models/joyai_flash.py` `latent_attention`)
holding `/attn/kernel` — forward and backward calls. `attn_ms` sums every
Mosaic call of a step and so holds the experts' grouped-product kernels too
(since PR 43); this reader tells the attention's calls from them. Nothing
where the step has no such unit."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.part_ms(ctx, "/attn/kernel")
