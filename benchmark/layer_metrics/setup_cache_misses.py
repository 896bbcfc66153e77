"""Compiles of the program's that missed the persistent cache (and were
written to it): `setup/cache_misses`. 0 in a warm run; in a cold one the
number of executables the program compiled. Nothing from a program without
the account."""
from benchmark.layer_metrics import _setup_account


def read(ctx):
    return _setup_account.total(ctx, "setup/cache_misses")
