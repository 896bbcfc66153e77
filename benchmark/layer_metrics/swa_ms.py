"""Device milliseconds a step, per chip, in the sliding-window attention
kernels: the Mosaic custom calls whose unit path (`models/laguna.py`:
`blk<i>/attn/swa`) holds `/attn/swa` — the forward kernel, its recomputation
where a block makes it again, and the backward kernel. With the full layers'
kernels (`/attn/kernel`) it makes up `attn_ms`. Nothing where the step has no
such unit."""
from benchmark import scope_join

UNIT = "/attn/swa"


def read(ctx):
    return scope_join.device_ms(
        ctx, lambda scope, kind: kind == "mosaic" and scope is not None
        and UNIT in (scope.unit or "")) or None
