"""Device milliseconds a step, per chip, in operations that are the
optimizer's alone: name scope under `opt/` (every Program op lowered after
the block's `autodiff` op). A kernel that holds an update fused with anything
else is `mixed` and not counted here (`scope_coverage` says how much that
is). Nothing from a program without the scopes."""
from benchmark import scope_join


def read(ctx):
    return scope_join.phase_ms(ctx, "opt")
