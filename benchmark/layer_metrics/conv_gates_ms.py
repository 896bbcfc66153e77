"""Device milliseconds a step, per chip, in the elementwise part of the gated
short convolution operators: unit paths (`models/lfm2.py`) holding
`/conv/gate_in` (the split of the [tokens, 3D] in-projection's result and
B * x), `/conv/filter` (the depthwise causal filter) and `/conv/gate_out`
(C * v) — the part bound by bandwidth (`conv_gates_bytes_per_step` in the
configuration's `counts` is the least it has to move), forward, recomputed
forward and backward. Nothing where the step has no such unit."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.part_ms(ctx, "/conv/gate_in", "/conv/filter",
                               "/conv/gate_out")
