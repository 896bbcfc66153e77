"""Seconds of set-up in which jax lowered a jaxpr of the program's to
StableHLO, the Mosaic kernels included: `setup/seconds{phase="lower"}`, every
reason but `foreign` and `executable`. Nothing from a program without the
account."""
from benchmark.layer_metrics import _setup_account


def read(ctx):
    return _setup_account.seconds(ctx, phases=("lower",))
