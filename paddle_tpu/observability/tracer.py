"""Host-side span tracer with chrome-trace export.

Reference analog: RecordEvent + DeviceTracer (platform/profiler.h:166,
device_tracer.cc) collected host/device event streams that
``tools/timeline.py`` converted to chrome://tracing JSON. Device-side
tracing belongs to jax.profiler (XPlane); this module is the HOST side:
wall-clock spans recorded per thread with proper nesting, exported as
chrome-trace JSON that loads directly in chrome://tracing or
https://ui.perfetto.dev — and mergeable with a converted XPlane trace via
``python -m paddle_tpu.tools.timeline``.

Usage::

    from paddle_tpu.observability import trace_span, get_tracer

    with trace_span("train/step", step=i):
        ...

    @trace_span("load_batch")
    def load_batch(...): ...

    get_tracer().export_chrome_trace("host_trace.json")

Spans are recorded as B/E (begin/end) event pairs, which chrome-trace
nests by timestamp per thread — the context-manager protocol guarantees
every B gets its E even when the body raises. Overhead per span is one
``perf_counter`` call and one lock-protected append at each end; when the
tracer is disabled (``get_tracer().enabled = False``) a span records
nothing and still reads its own duration (`dur_ms`).

One clock with the device: once jax is loaded every span also opens a
``jax.profiler.TraceAnnotation`` of the same name, so that under a profiler
session the program's spans lie in the ``.xplane.pb`` on a host line beside
the device operations (with no session a TraceMe is one atomic load).
`step_span` is the root of one dispatched step and opens a
``StepTraceAnnotation``, which XProf groups by. `Tracer.spans()` gives the
completed spans back with parent and self time.

Distributed traces: when a `context.TraceContext` is active on the
thread, `trace_span` derives a child context for its duration and stamps
``trace_id``/``span_id``/``parent_id`` into the span's args — the keys
``tools/timeline.py --fleet`` uses to stitch per-process traces into one
timeline. With no active context the span records exactly as before
(zero id-generation cost on untraced hot paths). `start_trace` roots a
new trace (used by the fleet router per routed request and the PS tier
per training step); `server_span` adopts an incoming RPC ``"trace"``
header on the serving side.
"""
from __future__ import annotations

import collections
import functools
import json
import os
import sys
import threading
import time
from typing import Dict, Iterable, List, Optional

from . import context as _ctx

__all__ = ["Tracer", "get_tracer", "trace_span", "step_span", "start_trace",
           "server_span", "pair_spans"]

# one process-wide timebase so spans from every thread share a clock;
# chrome trace wants microseconds
_T0 = time.perf_counter()


def _now_us() -> float:
    return (time.perf_counter() - _T0) * 1e6


def pair_spans(events: Iterable[dict], keep_open: bool = False) -> List[dict]:
    """Pair B/E events per (pid, tid) into spans, ordered by start:
    ``{name, ts, dur, self, args, pid, tid, parent}`` in microseconds, where
    `parent` is the index in the returned list of the span this one nests in
    (None at the top of its thread) and `self` is its duration less what its
    child spans cover. An E that closes nothing (its B fell out of the ring,
    or it names another span than the innermost open one) is dropped; an
    unclosed B is dropped too, or with `keep_open` becomes a zero-duration
    span (a process that died mid-span still shows where it was). The one
    pairing: `Tracer.spans` and tools/timeline.py both use it."""
    events = sorted((ev for ev in events if ev.get("ph") in ("B", "E")),
                    key=lambda ev: ev.get("ts", 0))
    stacks: Dict[tuple, list] = {}
    spans: List[dict] = []
    for ev in events:
        stack = stacks.setdefault((ev.get("pid"), ev.get("tid")), [])
        if ev["ph"] == "B":
            span = {"name": ev.get("name", "?"), "ts": float(ev.get("ts", 0)),
                    "dur": None, "self": 0.0, "args": ev.get("args") or {},
                    "pid": ev.get("pid"), "tid": ev.get("tid"),
                    "parent": stack[-1] if stack else None}
            stack.append(span)
            spans.append(span)
        elif stack and ev.get("name") in (None, stack[-1]["name"]):
            span = stack.pop()
            span["dur"] = float(ev.get("ts", 0)) - span["ts"]
            span["self"] += span["dur"]
            if span["parent"] is not None:
                span["parent"]["self"] -= span["dur"]
    for span in spans:
        if span["dur"] is None:
            span["dur"] = span["self"] = 0.0
            span["open"] = True
    if not keep_open:
        spans = [s for s in spans if not s.get("open")]
    index = {id(s): i for i, s in enumerate(spans)}
    for span in spans:
        span.pop("open", None)
        span["parent"] = index.get(id(span["parent"]))
    return spans


class Tracer:
    """Collects span events in a ring of `max_events`: an unobserved
    long-running process cannot grow without limit, and what it keeps is the
    newest (each event pushed out at the old end is counted in `dropped`)."""

    def __init__(self, max_events: int = 200_000):
        self._lock = threading.Lock()
        self.max_events = int(max_events)
        self._events: "collections.deque" = collections.deque(
            maxlen=self.max_events)
        self._thread_names: Dict[int, str] = {}
        self.dropped = 0
        self.enabled = True
        # shows as the track title in merged fleet timelines; worker /
        # pserver entrypoints set their role here
        self.process_name = "paddle_tpu host"

    # -- recording ---------------------------------------------------------
    def _emit(self, ev: dict) -> None:
        tid = ev["tid"]
        with self._lock:
            if tid not in self._thread_names:
                self._thread_names[tid] = threading.current_thread().name
            if len(self._events) == self.max_events:
                self.dropped += 1
            self._events.append(ev)

    def begin(self, name: str, args: Optional[dict] = None,
              ts: Optional[float] = None) -> None:
        ev = {"name": name, "ph": "B", "ts": _now_us() if ts is None else ts,
              "pid": os.getpid(), "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        self._emit(ev)

    def end(self, name: str, ts: Optional[float] = None) -> None:
        self._emit({"name": name, "ph": "E",
                    "ts": _now_us() if ts is None else ts,
                    "pid": os.getpid(), "tid": threading.get_ident()})

    def instant(self, name: str, args: Optional[dict] = None) -> None:
        """One timestamped marker (chrome-trace 'i' event)."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "i", "s": "t", "ts": _now_us(),
              "pid": os.getpid(), "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        self._emit(ev)

    # -- reading back ------------------------------------------------------
    def spans(self) -> List[dict]:
        """The completed spans the ring still holds, ordered by start, each
        with its parent (an index into the returned list, None at the top of
        its thread) and its self time: its duration less what its child
        spans cover (`pair_spans`; microseconds)."""
        with self._lock:
            events = list(self._events)
        return pair_spans(events)

    # -- export ------------------------------------------------------------
    def export_chrome_trace(self, path: Optional[str] = None) -> dict:
        """Chrome-trace JSON object ({"traceEvents": [...]}); written to
        `path` when given. Loadable in chrome://tracing and Perfetto."""
        with self._lock:
            events = list(self._events)
            names = dict(self._thread_names)
        # by time: a span may be written after the spans inside it (the
        # set-up account writes jax's events when they end)
        events.sort(key=lambda ev: ev.get("ts", 0))
        pid = os.getpid()
        meta: List[dict] = [
            {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
             "args": {"name": self.process_name}}]
        for tid, tname in sorted(names.items()):
            meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                         "tid": tid, "args": {"name": tname}})
        trace = {"traceEvents": meta + events, "displayTimeUnit": "ms"}
        if path is not None:
            with open(path, "w") as f:
                json.dump(trace, f)
        return trace

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._thread_names.clear()
            self.dropped = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)


_tracer = Tracer()


def get_tracer() -> Tracer:
    """The process-wide host tracer every `trace_span` records into."""
    return _tracer


class trace_span:
    """Record one named wall-clock span: context manager AND decorator.

    ::

        with trace_span("executor/compile", sig=digest):
            ...

        @trace_span("serve")          # span per call, named "serve"
        def serve(...): ...

    Keyword arguments become chrome-trace `args` (visible on click in the
    trace viewer). Spans nest naturally per thread; the end event is
    emitted even when the body raises. After the block `dur_ms` holds the
    span's own duration, whether or not the tracer records.

    When a distributed `TraceContext` is active on the thread, the span
    becomes a child span of it: a derived context is activated for the
    span's duration and its ids are stamped into the args.
    """

    __slots__ = ("name", "args", "dur_ms", "_entered", "_ctx_token", "_t0",
                 "_annotation")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args or None
        self.dur_ms = None
        self._entered = False
        self._ctx_token = None
        self._annotation = None

    def _span_ctx(self):
        """The context this span should record under, or None. Overridden
        by the rooting/adopting subclasses."""
        parent = _ctx.current()
        return parent.child() if parent is not None else None

    def _annotate(self, profiler):
        """The profiler's annotation of this span (`profiler` is
        `jax.profiler`)."""
        return profiler.TraceAnnotation(self.name)

    def __enter__(self):
        t = _tracer
        # the same span on the profiler's clock; jax is never imported from
        # here (the pserver host loads this module and must not have it)
        jax = sys.modules.get("jax")
        if jax is not None:
            self._annotation = self._annotate(jax.profiler)
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        if t.enabled:
            ctx = self._span_ctx()
            args = self.args
            if ctx is not None:
                self._ctx_token = _ctx._activate(ctx)
                args = dict(args) if args else {}
                args.update(ctx.args())
            self._entered = True
            t.begin(self.name, args, ts=(self._t0 - _T0) * 1e6)
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.dur_ms = (t1 - self._t0) * 1e3
        if self._entered:
            self._entered = False
            _tracer.end(self.name, ts=(t1 - _T0) * 1e6)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        if self._ctx_token is not None:
            _ctx._restore(self._ctx_token)
            self._ctx_token = None
        return False

    def __call__(self, fn):
        name, args = self.name, self.args or {}
        cls = type(self)

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            with cls(name, **args):
                return fn(*a, **kw)

        return wrapper


class step_span(trace_span):
    """The root span of one dispatched step, carrying the step's ordinal:
    under a profiler session a ``StepTraceAnnotation``, which XProf groups
    the host and device work of a step by."""

    __slots__ = ("step",)

    def __init__(self, name: str, step: int, **args):
        super().__init__(name, step=step, **args)
        self.step = step

    def _annotate(self, profiler):
        return profiler.StepTraceAnnotation(self.name, step_num=self.step)


class start_trace(trace_span):
    """Root span of a new distributed trace: activates a fresh
    `TraceContext` (new trace_id, no parent) for the span's duration, so
    everything beneath it — nested spans, RPCs to pservers and fleet
    workers, their server-side spans — shares one trace_id. If a trace
    is already active this degrades to a plain child `trace_span`
    (nested roots don't fork the trace)."""

    __slots__ = ()

    def _span_ctx(self):
        parent = _ctx.current()
        return parent.child() if parent is not None else _ctx.new_trace()


class server_span(trace_span):
    """Server-side RPC span: adopts the ``"trace"`` header dict from an
    incoming frame (see `context.from_wire`), parenting this process's
    span to the client's RPC span. With no/malformed header it records
    as a plain local span.

    ::

        with server_span(f"ps/{op}", msg.get("trace"), op=op):
            out = dispatch(op, msg)
    """

    __slots__ = ("_wire",)

    def __init__(self, name: str, wire, **args):
        super().__init__(name, **args)
        self._wire = wire

    def _span_ctx(self):
        ctx = _ctx.from_wire(self._wire)
        if ctx is None:
            return super()._span_ctx()
        return ctx
