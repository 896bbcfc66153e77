"""Host milliseconds a step that `Executor.run` spends before the jitted
call: the self time of its `executor/feed` span (feed conversion, signature)
and its `executor/state_in` span (state names, cache key, scope look-ups).
Median over the traced stretch's steady steps; nothing from a program that
records no such spans."""
from benchmark import scope_join


def read(ctx):
    return scope_join.step_ms_of(
        ctx, ("executor/feed", "executor/state_in"), self_time=True)
