"""Host milliseconds a step inside the jitted call itself: the program's
`executor/run` span (`compiled_program/run` on the mesh path), which
flattens the state leaves and enqueues the step. Median over the traced
stretch's steady steps; nothing from a program that records no such spans
under `executor/step`."""
from benchmark import scope_join


def read(ctx):
    return scope_join.step_ms_of(ctx, scope_join.CALL_SPANS)
