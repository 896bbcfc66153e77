"""Device milliseconds a step, per chip, in the exits' gate and objective:
operations whose unit (`models/ouro.py`) is `exit_gate` (the gate's product
and the exit distribution) or `loss` (the exit-weighted cross entropy and the
entropy term), forward and backward; the head's projection is `lm_head_ms`.
Nothing where the step has no such unit."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.unit_ms(ctx, "exit_gate", "loss")
