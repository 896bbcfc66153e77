"""The part of `collective_ms` during which no other operation runs on that
chip."""


def read(ctx):
    if ctx["trace"].kind_calls_per_step("collective") == 0:
        return None
    return ctx["trace"].exposed_collective_seconds_per_step() * 1e3
