"""Percent of the device's busy time in operations whose phase the program's
name scopes give as `fwd`, `bwd` or `opt`. The rest is `mixed` (a kernel
that fuses an optimizer update with other work), `none` (copies and the like
that carry no scope) or another executable's. Nothing from a program without
the scopes."""
from benchmark import scope_join

NAMED = ("fwd", "bwd", "opt")


def read(ctx):
    named = scope_join.device_ms(
        ctx, lambda s, kind: s is not None and s.phase in NAMED)
    if named is None:
        return None
    return 100.0 * named / scope_join.device_ms(ctx, lambda s, kind: True)
