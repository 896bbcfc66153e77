"""Seconds of set-up in which jax traced a function for the program (Python
to jaxpr: the walk of the Program's ops, the tape's backward walk, remat
wrapping, the Pallas kernels' bodies): `setup/seconds{phase="trace"}`, every
reason but `foreign` and `executable`. Nothing from a program without the
account."""
from benchmark.layer_metrics import _setup_account


def read(ctx):
    return _setup_account.seconds(ctx, phases=("trace",))
