"""Seconds of set-up spent in backend compiles and persistent-cache reads
(jax.monitoring), the reference's own programs left out as its time is."""


def read(ctx):
    return ctx["compile_s"]
