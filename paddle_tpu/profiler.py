"""Profiler surface.

Reference analog: ``python/paddle/fluid/profiler.py`` (profiler()
contextmanager, start/stop_profiler) over the C++ RecordEvent/DeviceTracer
CUPTI stack (platform/profiler.h:166, device_tracer.cc), exported to
chrome://tracing by tools/timeline.py.

TPU-native: jax.profiler captures an XPlane trace viewable in
TensorBoard/Perfetto (the chrome-trace analog); RecordEvent becomes
`observability.trace_span`, which is both a host span of the program's
tracer and a TraceAnnotation in the XPlane trace. What names the device
operations is not an annotation but the name scopes the lowering writes into
the compiled step's metadata (observability/scopes.py).
"""
from __future__ import annotations

import contextlib
import os
import time
from collections import defaultdict
from typing import Optional

import jax

from .observability.tracer import trace_span


_active = {}


def start_profiler(state: str = "All", tracer_option=None,
                   log_dir: str = "/tmp/paddle_tpu_profile"):
    """Begin one jax.profiler trace session. Exactly one session can be
    active per process (a jax.profiler limitation); a second start — e.g.
    a nested `profiler()` context — raises a clear error instead of
    clobbering the session state and crashing inside jax at stop time."""
    if _active.get("dir") is not None:
        raise RuntimeError(
            f"start_profiler: a profiling session is already active "
            f"(writing to {_active['dir']!r}) — nested profiler()/"
            f"start_profiler calls are not supported; stop_profiler() "
            f"first. For cheap always-on host spans inside a profiled "
            f"region use observability.trace_span instead.")
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    _active["dir"] = log_dir


def stop_profiler(sorted_key: Optional[str] = None, profile_path: Optional[str] = None):
    """End the active session and return its log dir. Raises a clear
    error when no session is active (previously this surfaced as an
    opaque failure from inside jax.profiler)."""
    if _active.get("dir") is None:
        raise RuntimeError(
            "stop_profiler without a matching start_profiler: no "
            "profiling session is active")
    log_dir = _active.pop("dir")  # cleared even if stop_trace raises,
    jax.profiler.stop_trace()     # so a new session can still start
    return log_dir


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: Optional[str] = None,
             profile_path: str = "/tmp/paddle_tpu_profile"):
    """fluid.profiler.profiler parity: wraps a training region; writes an
    XPlane trace under profile_path (open with TensorBoard)."""
    start_profiler(state, log_dir=profile_path)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


# RecordEvent RAII parity (platform/profiler.h:81) is `trace_span` itself: a
# host-side span in `observability.get_tracer()` and, under a profiler
# session, a jax.profiler.TraceAnnotation of the same name on a host line of
# the XPlane trace, so the region lines up with the device operations. It
# annotates nothing in the compiled HLO: only a name scope at trace time does.
record_event = trace_span


class _OpTimer:
    """Host-side per-op wall-time table for eager mode — the analog of the
    reference's EnableProfiler sorted per-op summary."""

    def __init__(self):
        self.times = defaultdict(float)
        self.counts = defaultdict(int)

    def summary(self, sorted_key: str = "total"):
        rows = [(k, self.counts[k], self.times[k] * 1e3,
                 self.times[k] / max(self.counts[k], 1) * 1e3)
                for k in self.times]
        rows.sort(key=lambda r: -r[2])
        lines = [f"{'op':<32}{'calls':>8}{'total_ms':>12}{'avg_ms':>10}"]
        for name, c, tot, avg in rows:
            lines.append(f"{name:<32}{c:>8}{tot:>12.3f}{avg:>10.4f}")
        return "\n".join(lines)


_op_timer: Optional[_OpTimer] = None


def export_op_profile(timer: _OpTimer) -> None:
    """Publish an eager per-op timing table to the process registry —
    ``eager/op_ms{op=}`` (cumulative host ms per op type, gauge) and
    ``eager/op_calls{op=}`` (counter) — so the summary that used to be
    print-only reaches ``/metrics``, ``/metrics.json``, flight dumps,
    and federation like every other series."""
    from .observability.registry import get_registry

    reg = get_registry()
    for op, secs in timer.times.items():
        g = reg.gauge("eager/op_ms", op=op)
        g.set(g.value + secs * 1e3)
        reg.counter("eager/op_calls", op=op).inc(timer.counts[op])


@contextlib.contextmanager
def op_profiler():
    """Eager per-op timing: patches the dygraph tracer dispatch. On exit
    the collected table is exported to the registry (export_op_profile)
    in addition to being available via ``timer.summary()``."""
    global _op_timer
    from .dygraph import tracer as tr_mod

    _op_timer = _OpTimer()
    orig = tr_mod.Tracer.trace_op

    def timed(self, op_type, inputs, attrs=None):
        t0 = time.perf_counter()
        out = orig(self, op_type, inputs, attrs)
        jax.block_until_ready(
            [v.value for vs in out.values() for v in vs])
        _op_timer.times[op_type] += time.perf_counter() - t0
        _op_timer.counts[op_type] += 1
        return out

    tr_mod.Tracer.trace_op = timed
    try:
        yield _op_timer
    finally:
        tr_mod.Tracer.trace_op = orig
        timer, _op_timer = _op_timer, None
        try:
            export_op_profile(timer)
        except Exception:
            pass


def reset_profiler():
    """Reference profiler.py reset_profiler: clear collected per-op stats."""
    global _op_timer
    if _op_timer is not None:
        _op_timer.times.clear()
        _op_timer.counts.clear()


from contextlib import contextmanager as _contextmanager


@_contextmanager
def cuda_profiler(output_file=None, output_mode=None, config=None):
    """Reference profiler.py cuda_profiler (nvprof hooks): no CUDA in the
    TPU build — use `profiler()`/jax.profiler traces instead. No-op shim."""
    yield
