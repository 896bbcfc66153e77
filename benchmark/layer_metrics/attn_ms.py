"""Device milliseconds a step spends in Mosaic custom calls, per chip. In the
ERNIE cells those are the flash-attention forward and backward kernels and
nothing else; a cell without Mosaic calls reports nothing."""


def read(ctx):
    if ctx["trace"].kind_calls_per_step("mosaic") == 0:
        return None
    return ctx["trace"].kind_seconds_per_step("mosaic") * 1e3
