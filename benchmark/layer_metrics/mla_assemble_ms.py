"""Device milliseconds a step, per chip, in assembling latent attention's q
and k for the kernel: unit paths (`models/joyai_flash.py`) holding
`/attn/assemble` — the concatenation of each head's nope and rope parts and
the broadcast of the one rope key head over all heads, forward, recomputed
forward and backward. A bandwidth-bound pass
(`counts["mla_assemble_bytes_per_step"]` says how many bytes it must move)
that a kernel taking the parts separately would not make. Nothing where the
step has no such unit."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.part_ms(ctx, "/attn/assemble")
