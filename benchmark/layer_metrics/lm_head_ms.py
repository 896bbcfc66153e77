"""Device milliseconds a step, per chip, in the language-model head and the
loss: operations whose unit (`models/nemotron_h.py`) is `lm_head` or `loss` —
the chunked projection onto the vocabulary slice with its cross entropy,
every position labelled, forward and backward, its two loops counted once.
Nothing where the step has no such unit."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.unit_ms(ctx, "lm_head", "loss")
