"""Host milliseconds a step that the program's own instruments cost inside
`Executor.run`: its `executor/telemetry` span (the `executor/execute_ms`
histogram, `StepProfiler.record`, the perf ledger). Median over the traced
stretch's steady steps; nothing from a program that records no such span."""
from benchmark import scope_join


def read(ctx):
    return scope_join.step_ms_of(ctx, ("executor/telemetry",))
