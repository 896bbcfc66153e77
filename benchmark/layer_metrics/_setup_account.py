"""What the readers of the program's set-up account share (PR 37).

The program records every staging of a function (trace, lowering, compile or
cache read, relayout, first run) in its registry, by phase and by the reason
it was asked for (`paddle_tpu.observability.setup_account`):
`setup/seconds{phase,reason}` and the counters beside it. The readers run
after the window and see the totals. Everything the program stages in a
benchmark run lies inside `setup_s` but for two reasons, which every reader
leaves out: `foreign` (jits that are not the program's: the reference, the
seeded weights, the harness's read-backs) and `executable` (the step staged
again for `scopes.hottest_step()`, which a traced run asks for after the
window).

A program without the account (the parent of PR 37) gives every reader
`None`: the metric is left out, never a zero. A test hands a registry in as
`ctx["registry"]`.
"""
from __future__ import annotations

LEFT_OUT = ("foreign", "executable")
# the account creates this counter when it is installed, whatever happens
MARK = "setup/cache_misses"


def counters(ctx):
    """{name: [(labels, value), ...]} of the registry's `setup/*` counters,
    or None where the program keeps no account."""
    if "_setup_counters" in ctx:
        return ctx["_setup_counters"]
    registry = ctx.get("registry")
    if registry is None:
        try:
            from paddle_tpu.observability import get_registry
            registry = get_registry()
        except ImportError:
            registry = None
    found = {}
    if registry is not None:
        for s in registry.series(deep=False):
            if s["type"] == "counter" and s["name"].startswith("setup/"):
                found.setdefault(s["name"], []).append(
                    (s["labels"], s["value"]))
    ctx["_setup_counters"] = found = found if MARK in found else None
    return found


def total(ctx, name, **labels):
    """The sum of the counter `name` over the series whose labels agree with
    `labels` (a tuple of values: any of them) and whose reason, where they
    have one, is not in `LEFT_OUT`; None without the account."""
    found = counters(ctx)
    if found is None:
        return None
    result = 0.0
    for have, value in found.get(name, ()):
        if have.get("reason") in LEFT_OUT:
            continue
        if all(have.get(k) in want for k, want in labels.items()):
            result += value
    return result


def seconds(ctx, phases=None, reasons=None):
    """Seconds of `setup/seconds` in `phases` (all) under `reasons` (all but
    `LEFT_OUT`)."""
    labels = {}
    if phases is not None:
        labels["phase"] = phases
    if reasons is not None:
        labels["reason"] = reasons
    return total(ctx, "setup/seconds", **labels)
