"""Seconds of set-up in which a step was staged for anyone but its first
call: all phases of `setup/seconds` under the reasons `cost` (the cost
ledger's lowering), `relayout` (the AUTO-layout path's second compile) and
`direct` (the step lowered by a caller outside the executor: the benchmark's
own `program_access.py`). Nothing from a program without the account."""
from benchmark.layer_metrics import _setup_account


def read(ctx):
    return _setup_account.seconds(
        ctx, reasons=("cost", "relayout", "direct"))
