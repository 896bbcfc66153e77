"""Device milliseconds a step spends in operations XLA compiled: everything
that is neither a Mosaic call nor a collective. Per chip."""


def read(ctx):
    return ctx["trace"].kind_seconds_per_step("xla") * 1e3
