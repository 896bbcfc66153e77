"""Host milliseconds a step that `Executor.run` spends after the jitted
call on the program's state: its `executor/state_out` span (a `set_var` for
every state leaf) and its `executor/epilogue` span (maintenance programs,
`check_nan_inf`). Median over the traced stretch's steady steps; nothing
from a program that records no such spans."""
from benchmark import scope_join


def read(ctx):
    return scope_join.step_ms_of(
        ctx, ("executor/state_out", "executor/epilogue"))
