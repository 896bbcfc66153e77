"""Device milliseconds a step, per chip, in the Mamba-2 mixers: unit paths
(`models/nemotron_h.py`: `blk<i>.M/mamba/<part>`) holding `/mamba/` — the
projections, the convolution, the scan and the gated norm, forward,
recomputed forward and backward alike. Nothing where the step has no such
unit."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.part_ms(ctx, "/mamba/")
