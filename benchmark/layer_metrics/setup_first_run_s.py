"""Seconds of set-up between an executable's being ready and its first
call's return, the enqueue with its transfers, together with the relayout of
the state into the executable's entry formats:
`setup/seconds{phase="relayout"}` + `{phase="first_run"}`. Nothing from a
program without the account."""
from benchmark.layer_metrics import _setup_account


def read(ctx):
    return _setup_account.seconds(ctx, phases=("relayout", "first_run"))
