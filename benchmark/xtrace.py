"""The benchmark's own reduction of a profiler trace (`.xplane.pb`, read with
`jax.profiler.ProfileData`) to the few facts the per-layer metrics read.

Two stages, so that the arithmetic can be tested on recorded tuples:

  extract(path)  -> {"devices": {plane name: [(name, kind, start_ns, dur_ns)]},
                     "host": [(name, start_ns, dur_ns)]}
  Reduced(events, window) -> busy union, idle gaps named by the host span they
                     fell in, time by kind, exposed collective time

`kind` is one of "mosaic" (a Pallas/Mosaic custom call), "collective"
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all and
their start/done halves) or "xla" (everything else XLA compiled).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
HOST_SPANS = ("bench.dispatch", "bench.wait")

_COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                   "collective-permute", "all-to-all", "collective-broadcast")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'

Event = Tuple[str, str, int, int]


def opcode(text: str) -> str:
    """The HLO opcode of an operation whose trace name is its instruction
    text, `%name = shape opcode(operands), attributes`; the bare name where
    the text has no such form."""
    _, sep, rest = text.partition(" = ")
    m = _OPCODE.search(" " + rest) if sep else None
    return m.group(1) if m else text.lstrip("%").split(".")[0]


def classify(text: str) -> str:
    op = opcode(text)
    if any(op.startswith(c) for c in _COLLECTIVE_OPS):
        return "collective"
    if op == "custom-call" and MOSAIC_TARGET in text:
        return "mosaic"
    return "xla"


def label(text: str) -> str:
    """A short name for an operation: its HLO name, opcode and first output
    shape, e.g. `jvp__.12 custom-call bf16[768,512,64]`."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text[:80]
    shape = _SHAPE.search(rest)
    return " ".join(filter(None, [head.lstrip("%"), opcode(text),
                                  shape.group(0) if shape else ""]))[:80]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _stats(event) -> dict:
    try:
        return {k: v for k, v in event.stats}
    except (TypeError, ValueError):
        return {}


def extract(path: str):
    """Read device operations (the "XLA Ops" line of every TPU plane; on this
    runtime its events do not nest) and the benchmark's host spans out of one
    `.xplane.pb`. An operation is kept as (label, kind, start_ns, dur_ns)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Tuple[str, int, int]] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                events = devices.setdefault(plane.name, [])
                for ev in line.events:
                    events.append((label(ev.name), classify(ev.name),
                                   int(ev.start_ns), int(ev.duration_ns)))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)))
    for events in devices.values():
        events.sort(key=lambda e: e[2])
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


def describe(path: str, names_per_line: int = 40) -> dict:
    """What a trace holds: planes, lines, and a sample of distinct event names
    with their stats. For looking at a trace by hand before trusting
    `extract`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            seen, n = {}, 0
            for ev in line.events:
                n += 1
                if ev.name not in seen and len(seen) < names_per_line:
                    seen[ev.name] = {k: str(v)[:160] for k, v in
                                     _stats(ev).items()}
            lines.append({"line": line.name, "events": n, "sample": seen})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}


# ---------------------------------------------------------------------------
# arithmetic on the extracted tuples
# ---------------------------------------------------------------------------

def _union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def _length(intervals) -> int:
    return sum(hi - lo for lo, hi in intervals)


def _subtract(a: Sequence[Tuple[int, int]], b: Sequence[Tuple[int, int]]):
    """Parts of the (merged) intervals `a` that no interval of the (merged)
    `b` covers."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


class Reduced:
    """A traced stretch of `steps` steps, reduced. Times are seconds; per-chip
    numbers are averaged over the device planes."""

    def __init__(self, events: dict, steps: int):
        self.steps = steps
        self.devices: Dict[str, List[Event]] = {
            k: [tuple(e) for e in v] for k, v in events["devices"].items()
            if v}
        self.host = [tuple(e) for e in events["host"]]
        if not self.devices:
            raise ValueError("the trace holds no device operation")
        # the window: from the first host span's start (or first device
        # operation) to the last device operation's end
        starts = [ev[0][2] for ev in self.devices.values()]
        ends = [max(e[2] + e[3] for e in ev) for ev in self.devices.values()]
        first_host = self.host[0][1] if self.host else min(starts)
        self.t0 = min(first_host, min(starts))
        self.t1 = max(ends)
        self.window_s = (self.t1 - self.t0) * 1e-9

    # -- busy and idle ------------------------------------------------------
    def _busy(self, events) -> List[Tuple[int, int]]:
        return _union([(e[2], e[2] + e[3]) for e in events])

    @property
    def busy_s(self) -> float:
        per_chip = [_length(self._busy(ev)) for ev in self.devices.values()]
        return sum(per_chip) / len(per_chip) * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def idle_gaps(self, top: int = 5) -> List[List]:
        """The longest gaps of the first device, each named by the host span
        that covers its middle, then the total gap time by span name."""
        events = next(iter(self.devices.values()))
        busy = self._busy(events)
        gaps = _subtract([(self.t0, self.t1)], busy)

        def span_at(t: int) -> str:
            for name, start, dur in self.host:
                if start <= t < start + dur:
                    return name
            return "no_span"

        named = [(span_at((lo + hi) // 2), (hi - lo) * 1e-9)
                 for lo, hi in gaps]
        longest = sorted(named, key=lambda g: -g[1])[:top]
        totals: Dict[str, float] = {}
        for name, sec in named:
            totals[name] = totals.get(name, 0.0) + sec
        out = [[n, s] for n, s in longest]
        out += [[f"all:{n}", s] for n, s in
                sorted(totals.items(), key=lambda kv: -kv[1])]
        return out

    # -- time by kind -------------------------------------------------------
    def kind_seconds_per_step(self, kind: str) -> float:
        """Device seconds a step spends in operations of `kind`, per chip."""
        per_chip = [sum(e[3] for e in ev if e[1] == kind)
                    for ev in self.devices.values()]
        return sum(per_chip) / len(per_chip) * 1e-9 / self.steps

    def kind_calls_per_step(self, kind: str) -> float:
        per_chip = [sum(1 for e in ev if e[1] == kind)
                    for ev in self.devices.values()]
        return sum(per_chip) / len(per_chip) / self.steps

    def name_seconds_per_step(self, words: Sequence[str]) -> Optional[float]:
        """Per chip and step, the time of operations whose name holds one of
        `words`; None where no operation does."""
        per_chip, hit = [], False
        for ev in self.devices.values():
            sel = [e[3] for e in ev
                   if any(w in e[0].lower() for w in words)]
            hit = hit or bool(sel)
            per_chip.append(sum(sel))
        if not hit:
            return None
        return sum(per_chip) / len(per_chip) * 1e-9 / self.steps

    def exposed_collective_seconds_per_step(self) -> float:
        """Per chip and step, the collective time during which no other
        operation runs on that chip."""
        per_chip = []
        for ev in self.devices.values():
            coll = _union([(e[2], e[2] + e[3]) for e in ev
                           if e[1] == "collective"])
            other = _union([(e[2], e[2] + e[3]) for e in ev
                            if e[1] != "collective"])
            per_chip.append(_length(_subtract(coll, other)))
        return sum(per_chip) / len(per_chip) * 1e-9 / self.steps

    def top_ops(self, top: int = 10) -> List[List]:
        """The operations that took most device time, seconds per step on the
        first device, summed by name."""
        events = next(iter(self.devices.values()))
        total: Dict[str, int] = {}
        for name, _, _, dur in events:
            total[name] = total.get(name, 0) + dur
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        return [[n, d * 1e-9 / self.steps] for n, d in ranked]
