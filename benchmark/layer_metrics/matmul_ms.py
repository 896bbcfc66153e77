"""Device milliseconds a step, per chip, in XLA-compiled operations that
hold a matrix product: the instruction, or the computation it calls, has a
`dot` or `convolution` (`OpScope.has_dot`). Mosaic calls are excluded:
`attn_ms` has them. Nothing from a program without the scopes."""
from benchmark import scope_join


def read(ctx):
    return scope_join.device_ms(
        ctx, lambda s, kind: s is not None and s.has_dot and kind == "xla")
