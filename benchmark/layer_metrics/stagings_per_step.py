"""Traces of a step of the program's over the steps that reached an
executable: `setup/stagings` (not `foreign`, not `executable`) over
`setup/executables`. 1.0 is every step traced once. Nothing from a program
without the account, or where no step was compiled."""
from benchmark.layer_metrics import _setup_account


def read(ctx):
    executables = _setup_account.total(ctx, "setup/executables")
    if not executables:
        return None
    return _setup_account.total(ctx, "setup/stagings") / executables
