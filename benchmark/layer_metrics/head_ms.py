"""Device milliseconds a step, per chip, in the masked-LM head and the loss:
operations whose unit (the model part the op was built in,
`models/bert.py`) is `mlm_head` or `loss`, forward, backward and the kernels
that fuse the head's gradient with its update alike. Nothing where the step
has no such unit."""
from benchmark import scope_join


def read(ctx):
    return scope_join.unit_ms(ctx, ("mlm_head", "loss"))
