"""Backend compiles after warm-up, counted through jax.monitoring."""


def read(ctx):
    return ctx["late_compiles"]
