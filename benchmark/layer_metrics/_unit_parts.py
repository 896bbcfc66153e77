"""What the readers of `models/nemotron_h.py`'s unit paths share: the device
milliseconds a step in the operations whose unit path `pick` takes, all
phases, **a loop counted once**. The profiler records a `while` and, inside
its span, the operations of its body, which carry the same unit (`xtrace.py`
and `scope_join.device_ms` sum both: PERF.md section 7); here the `while`
itself is left out and its body is what is summed. (Not "any operation inside
whose span another starts": a fusion's span often holds a zero-length
custom call.) None where the step has no such unit."""
from benchmark import scope_join

LOOP = "while"      # the opcode, the second word of a trace label


def leaf_ms(ctx, pick):
    by_label = scope_join._scope_of(ctx)
    if not by_label:
        return None
    trace, per_chip = ctx["trace"], []
    for events in trace.devices.values():
        total = 0
        for label, _, _, dur in events:
            scope = by_label.get(label)
            if (scope is not None and pick(scope.unit or "")
                    and label.split()[1:2] != [LOOP]):
                total += dur
        per_chip.append(total)
    return sum(per_chip) / len(per_chip) * 1e-6 / trace.steps or None


def part_ms(ctx, *parts):
    """By part (`/mamba/ssd`, `/moe/router`, ...) and not by the whole path:
    a custom backward's path repeats its unit
    (`blk0.M/mamba/ssd/blk0.M/mamba/ssd`)."""
    return leaf_ms(ctx, lambda unit: any(p in unit for p in parts))


def unit_ms(ctx, *units):
    return leaf_ms(ctx, lambda unit: unit in units)
