"""Device milliseconds a step spends in collectives (all-reduce, all-gather,
reduce-scatter ...), per chip."""


def read(ctx):
    if ctx["trace"].kind_calls_per_step("collective") == 0:
        return None
    return ctx["trace"].kind_seconds_per_step("collective") * 1e3
