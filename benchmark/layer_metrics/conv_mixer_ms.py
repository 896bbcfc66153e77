"""Device milliseconds a step, per chip, in the gated short convolution
operators: unit paths (`models/lfm2.py`:
`blk<i>/conv/<in_proj, gate_in, filter, gate_out, out_proj>`) holding
`/conv/` — the two projections and the elementwise part between them,
forward, recomputed forward and backward. Nothing where the step has no such
unit."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.part_ms(ctx, "/conv/")
