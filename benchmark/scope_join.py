"""What the readers of the program's own names share (PR 24).

Device side: the program writes a name scope `[opt/]u.<unit>/op.<op type>`
into every instruction of its compiled step and hands the step out
(`paddle_tpu.observability.scopes`: `hottest_step()`, `op_scopes()`). An
`OpScope` keeps its instruction's text, from which `xtrace.label` makes the
very label the device trace carries; `device_ms` joins the traced operations
with the map by that label and sums the time of those a reader picks.

Host side: `Executor.run` records one `executor/step` span per call with its
phases as children in the program's tracer; `step_ms_of` takes the median
over the steady steps of the traced stretch.

A program without the scopes or the spans (the parent of PR 24) gives every
reader `None`: the metric is left out, never a zero.
"""
from __future__ import annotations

import statistics

from benchmark import xtrace

STEP_SPAN = "executor/step"
CALL_SPANS = ("executor/run", "compiled_program/run")
COMPILING = ("executor/compile+run", "compiled_program/compile+run")


def _scope_of(ctx):
    """{trace label: OpScope} of the step the process ran, or None where
    the program gives no map. A test hands a map in as ctx["op_scopes"]. The
    join is by the whole label (HLO name, opcode, first result shape): by the
    name alone a `fusion.3` of another executable (a fold epilogue, a helper
    jit) would take the phase and unit of the step's `fusion.3`."""
    if "_scope_of" in ctx:
        return ctx["_scope_of"]
    scopes = ctx.get("op_scopes")
    if scopes is None:
        try:
            from paddle_tpu.observability import scopes as program_scopes
            compiled = program_scopes.hottest_step()
            scopes = (program_scopes.op_scopes(compiled)
                      if compiled is not None else None)
        except ImportError:
            scopes = None
    by_label = ({xtrace.label(s.text): s for s in scopes.values()}
                if scopes else None)
    ctx["_scope_of"] = by_label
    return by_label


def device_ms(ctx, pick):
    """Device milliseconds a step, per chip, in the traced operations for
    which `pick(scope, kind)` holds, where `scope` is the operation's OpScope
    (None for one the step's map does not know: another executable's). None
    without a map, or where the map knows none of the traced operations."""
    by_label = _scope_of(ctx)
    if not by_label:
        return None
    trace = ctx["trace"]
    per_chip, known = [], False
    for events in trace.devices.values():
        total = 0
        for label, kind, _, dur in events:
            scope = by_label.get(label)
            known = known or scope is not None
            if pick(scope, kind):
                total += dur
        per_chip.append(total)
    if not known:
        return None
    return sum(per_chip) / len(per_chip) * 1e-6 / trace.steps


def phase_ms(ctx, phase: str):
    return device_ms(ctx, lambda s, kind: s is not None and s.phase == phase)


def unit_ms(ctx, units):
    """Time in operations of the unit paths `units`, all phases; None where
    the step has no such operation."""
    ms = device_ms(ctx, lambda s, kind: s is not None and s.unit in units)
    return ms or None


def steady_steps(ctx):
    """The last `trace.steps` top-level `executor/step` spans that compiled
    nothing, each as {child span name: [duration us, self time us]} with the
    root under its own name; None where the program records no such span."""
    try:
        from paddle_tpu.observability import get_tracer
    except ImportError:
        return None
    read = ctx.get("spans") or getattr(get_tracer(), "spans", None)
    if read is None:
        return None
    spans = read()
    steps = []
    for i, span in enumerate(spans):
        if span["name"] == STEP_SPAN and span["parent"] is None:
            steps.append((i, {STEP_SPAN: [span["dur"], span["self"]]}))
    by_root = dict(steps)
    for span in spans:
        children = by_root.get(span["parent"])
        if children is not None:
            slot = children.setdefault(span["name"], [0.0, 0.0])
            slot[0] += span["dur"]
            slot[1] += span["self"]
    steady = [c for _, c in steps if not any(n in c for n in COMPILING)]
    return steady[-ctx["trace"].steps:] or None


def step_ms_of(ctx, names, self_time: bool = False):
    """Host milliseconds a step in the child spans `names` of `executor/step`
    (their self time: less what their own child spans cover), the median over
    the traced stretch's steady steps. None without such spans."""
    steps = steady_steps(ctx)
    if not steps or not any(n in c for c in steps for n in names):
        return None
    which = 1 if self_time else 0
    return statistics.median(
        sum(c[n][which] for n in names if n in c) for c in steps) * 1e-3
