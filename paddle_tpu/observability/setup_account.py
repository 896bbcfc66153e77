"""The account of set-up: who staged which function, in which phase, how long.

Every time the process turns a function into an executable one record is
made of **which step** (the jitted function's name: ``step_<digest>``, the
scan's, a jitted helper's), **which phase**, **for whom** and **how long**.

Phases. jax reports the first four itself, for every ``jit`` in the process,
through ``jax.monitoring`` (`install` registers the listeners, once, beside
the executor's compile cache):

- ``trace``: Python to jaxpr (the walk of the Program's ops, the tape's
  backward walk, remat wrapping, nested jits);
- ``lower``: jaxpr to StableHLO, Mosaic kernels included (jax gives no hook
  that would part a kernel's lowering from the module's);
- ``backend_compile``: the backend's compile event where the persistent
  cache missed (key computation, compile and the cache's write);
- ``cache_read``: the same event where the cache hit (key computation,
  retrieval, deserialisation).

The program opens the others itself, as `phase` spans:

- ``relayout``: `_AutoLayoutStep`'s look at the accumulators' layouts (its
  second compile, where one is needed, is staged under the reason
  ``relayout``);
- ``first_run``: from the executable's being ready to the first call's
  return: the `device_put` of state leaves into the executable's entry
  formats and the enqueue with its transfers of the feed (on the plain-jit
  path: the first call less what jax staged inside it);
- ``import``: ``import paddle_tpu``, first line to last (`note_import`;
  kept in ``setup/import_seconds`` alone, not in ``setup/seconds``).

Reasons (who asked). A staging site says so with ``with staging(reason)``,
a thread-local: ``call`` (a step's first dispatch), ``executable``
(`_Step.compiled()` staging again for `compiled_step()` /
`scopes.hottest_step()`), ``cost`` (the cost ledger's ``fn.lower``),
``relayout`` (the AUTO path's second compile), ``probe`` (jits the executor
makes of its own). With no site open on the thread a function named by
`scopes.scheme_name` is ``direct`` (a step of ours staged by someone else:
today the benchmark's ``program_access.py``) and anything else ``foreign``
(the reference, ``make_weights``, user code, eager jax operations).

Each second is counted once. A jax event that starts inside another one on
the same thread (a nested jit's trace, an eager operation under a trace) is
held by the outer one and recorded nowhere: the ``step`` label names the
outermost function. A `phase` span records its duration less what was
recorded inside it.

Where it lands:

- the registry (`get_registry()`): ``setup/seconds{phase,reason}``,
  ``setup/stagings{reason}`` (traces of a step of ours in which the Program
  was walked: a trace that jax served from its own cache is none),
  ``setup/executables`` (compile events of a step of ours under ``call``),
  ``setup/cache_hits`` / ``setup/cache_misses`` (persistent cache; those of
  ``foreign`` stagings in ``..._foreign``), ``setup/import_seconds``,
  ``setup/trace_op_seconds{op}`` / ``setup/trace_op_calls{op}`` (the walk's
  exclusive time by op type: `walk`),
  ``setup/kernel_trace_seconds{kernel,reason}`` /
  ``setup/kernel_traces{kernel,reason}`` (`kernel_trace`), and
  ``setup/auto_layout_fallbacks{error}``;
- the tracer (`get_tracer()`): a span ``setup/<phase>`` with args ``step``
  and ``reason`` for each record. Inside a compiling dispatch they are
  children of ``executor/compile+run`` / ``compiled_program/compile+run``;
  outside one, of a root span ``executor/stage`` with the reason. Spans of
  jax's events are written when the event ends, with the tracer's own clock
  read at jax's start and end notifications (so they are no
  ``TraceAnnotation``s; `phase` spans are).

Nothing here reaches the lowered text or the compile cache's key. jax is
imported by `install` alone: the pserver host loads this package without it.
"""
from __future__ import annotations

import threading
import time

from . import scopes as _scopes
from .registry import get_registry
from .tracer import _T0, get_tracer, trace_span

__all__ = ["PHASES", "install", "staging", "phase", "walk", "kernel_trace",
           "note_import", "auto_layout_fallback"]

PHASES = ("trace", "lower", "backend_compile", "cache_read", "relayout",
          "first_run", "import")

_TRACE, _LOWER, _COMPILE = "trace", "lower", "compile"
_KIND_OF = {
    "/jax/core/compile/jaxpr_trace_duration": _TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": _LOWER,
    "/jax/core/compile/backend_compile_duration": _COMPILE,
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"

_OBS = get_registry()
_installed = False
_install_lock = threading.Lock()


class _Thread(threading.local):
    def __init__(self):
        self.reasons = []     # `staging` sites open on this thread
        self.events = []      # open jax events: [_Event, kind, kind, ...]
        self.phases = []      # open `phase` spans: [seconds held inside]
        self.ops = []         # the walk's open ops: [start, seconds inside]
        self.walked = {}      # op type -> [exclusive seconds, calls]


_tls = _Thread()


class _Event:
    """An outermost jax event, open."""
    __slots__ = ("kind", "step", "reason", "t0", "hit", "walked")

    def __init__(self, kind, step, reason):
        self.kind, self.step, self.reason = kind, step, reason
        self.t0 = time.perf_counter()
        self.hit = self.walked = False


def _step_of(fun_name) -> str:
    """The function's own name: jax names a module ``jit(<function>)``."""
    name = str(fun_name)
    if name.endswith(")") and "(" in name:
        name = name[name.index("(") + 1:-1]
    return name


def _reason_now(step: str = "") -> str:
    if _tls.reasons:
        return _tls.reasons[-1]
    return "direct" if _scopes.is_scheme_name(step) else "foreign"


def _asker() -> str:
    """For whom the thread stages right now: the outermost open jax event
    knows (the first of `events` is always an `_Event`), else the sites."""
    return _tls.events[0].reason if _tls.events else _reason_now()


def _add_seconds(phase_name: str, reason: str, seconds: float) -> None:
    _OBS.counter("setup/seconds", phase=phase_name,
                 reason=reason).inc(seconds)
    if _tls.phases:
        _tls.phases[-1][0] += seconds


# -- jax's events -------------------------------------------------------------

def _on_start(event, value=None, **kw):
    kind = _KIND_OF.get(event)
    if kind is None:
        return
    if _tls.events:
        _tls.events.append(kind)          # held by the outermost one
        return
    step = _step_of(kw.get("fun_name", "?"))
    _tls.events.append(_Event(kind, step, _reason_now(step)))


def _on_end(event, start=None, end=None, **kw):
    kind = _KIND_OF.get(event)
    if kind is None:
        return
    t1 = time.perf_counter()
    events = _tls.events
    if not events:
        return                            # began before the listeners did
    ev = events.pop()
    if events:                            # held by the outermost one
        if ev != kind:                    # an end without its start: resync
            del events[:]
        return
    if ev.kind != kind:
        return
    ours = _scopes.is_scheme_name(ev.step)
    name = kind
    if kind == _TRACE:
        if ours and ev.walked:
            _OBS.counter("setup/stagings", reason=ev.reason).inc()
        _flush_walk()
    elif kind == _COMPILE:
        name = "cache_read" if ev.hit else "backend_compile"
        if ours and ev.reason == "call":
            _OBS.counter("setup/executables").inc()
    _add_seconds(name, ev.reason, t1 - ev.t0)
    tracer = get_tracer()
    if tracer.enabled:
        ts0, ts1 = (ev.t0 - _T0) * 1e6, (t1 - _T0) * 1e6
        root = not _tls.reasons           # no site open: `direct`, `foreign`
        if root:
            tracer.begin("executor/stage", {"reason": ev.reason}, ts=ts0)
        tracer.begin("setup/" + name,
                     {"step": ev.step, "reason": ev.reason}, ts=ts0)
        tracer.end("setup/" + name, ts=ts1)
        if root:
            tracer.end("executor/stage", ts=ts1)


def _on_event(event, **kw):
    if event != _CACHE_HIT and event != _CACHE_MISS:
        return
    events = _tls.events
    reason = _asker()
    if event == _CACHE_HIT and len(events) == 1:
        events[0].hit = True              # the open compile event's own
    name = "setup/cache_hits" if event == _CACHE_HIT else "setup/cache_misses"
    _OBS.counter(name + "_foreign" if reason == "foreign" else name).inc()


def install() -> None:
    """Register the listeners with ``jax.monitoring``; once a process."""
    global _installed
    with _install_lock:
        if _installed:
            return
        _installed = True
    from jax import monitoring
    monitoring.register_scalar_listener(_on_start)
    monitoring.register_event_time_span_listener(_on_end)
    monitoring.register_event_listener(_on_event)
    # a reader tells a program with the account from one without by these
    for name in ("setup/cache_hits", "setup/cache_misses",
                 "setup/executables", "setup/import_seconds"):
        _OBS.counter(name)


# -- the program's own sites --------------------------------------------------

class staging:
    """``with staging(reason):`` says for whom the thread stages until the
    block ends. It opens a root span ``executor/stage``, unless told not to
    or the reason is ``call``, whose site lies inside the dispatch's own
    ``.../compile+run`` span."""

    __slots__ = ("reason", "_root", "_span")

    def __init__(self, reason: str, root: bool = True):
        self.reason = reason
        self._root = root and reason != "call"
        self._span = None

    def __enter__(self):
        if self._root:
            self._span = trace_span("executor/stage", reason=self.reason)
            self._span.__enter__()
        _tls.reasons.append(self.reason)
        return self

    def __exit__(self, *exc):
        _tls.reasons.pop()
        if self._span is not None:
            self._span.__exit__(*exc)
        return False


class phase:
    """A phase jax does not report (``relayout``, ``first_run``), as a span
    ``setup/<name>``; records its duration less what was recorded inside."""

    __slots__ = ("name", "_span", "_held", "_reason", "_muted")

    def __init__(self, name: str, step: str = "?"):
        self.name = name
        self._reason = _tls.reasons[-1] if _tls.reasons else "call"
        self._span = trace_span("setup/" + name, step=step,
                                reason=self._reason)

    def __enter__(self):
        self._muted = bool(_tls.events)   # inside a jax event: that holds it
        self._held = [0.0]
        _tls.phases.append(self._held)
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        phases = _tls.phases
        phases.pop()
        held = self._held[0]
        if phases:
            phases[-1][0] += held         # the enclosing phase holds it too
        if not self._muted:
            _add_seconds(self.name, self._reason,
                         max(0.0, self._span.dur_ms * 1e-3 - held))
        return False


class walk:
    """``with walk(op_type):`` around the lowering of one op of the walk
    (`core/executor.py` `_run_op` and its like): the op's time less that of
    the ops walked inside it goes to ``setup/trace_op_seconds{op}``, written
    when the trace ends."""

    __slots__ = ("op_type", "_frame")

    def __init__(self, op_type: str):
        self.op_type = op_type

    def __enter__(self):
        self._frame = [time.perf_counter(), 0.0]   # start, seconds inside
        _tls.ops.append(self._frame)
        return self

    def __exit__(self, *exc):
        start, inside = self._frame
        seconds = time.perf_counter() - start
        ops = _tls.ops
        ops.pop()
        if ops:
            ops[-1][1] += seconds
        slot = _tls.walked.setdefault(self.op_type, [0.0, 0])
        slot[0] += seconds - inside
        slot[1] += 1
        if _tls.events:
            _tls.events[0].walked = True
        elif not ops:
            _flush_walk()                 # an eager walk: no trace to end
        return False


def _flush_walk() -> None:
    walked = _tls.walked
    if walked:
        for op_type, (seconds, calls) in walked.items():
            _OBS.counter("setup/trace_op_seconds", op=op_type).inc(seconds)
            _OBS.counter("setup/trace_op_calls", op=op_type).inc(calls)
        walked.clear()


class kernel_trace:
    """Around one ``pl.pallas_call(...)(...)`` bind: what tracing the kernel
    costs at this call site, this time (a part of the ``trace`` phase)."""

    __slots__ = ("kernel", "_t0")

    def __init__(self, kernel: str):
        self.kernel = kernel

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self._t0
        reason = _asker()
        _OBS.counter("setup/kernel_trace_seconds", kernel=self.kernel,
                     reason=reason).inc(seconds)
        _OBS.counter("setup/kernel_traces", kernel=self.kernel,
                     reason=reason).inc()
        return False


def note_import(t0: float) -> None:
    """``import paddle_tpu`` began at `t0` (``time.perf_counter()``) and
    ends now."""
    t1 = time.perf_counter()
    _OBS.counter("setup/import_seconds").inc(t1 - t0)
    tracer = get_tracer()
    if tracer.enabled:
        tracer.begin("setup/import", ts=(t0 - _T0) * 1e6)
        tracer.end("setup/import", ts=(t1 - _T0) * 1e6)


def auto_layout_fallback(exc: BaseException, step: str, kept: str) -> None:
    """`_AutoLayoutStep` dropped what an AUTO-layout compile raised and went
    on with `kept`: counted by the exception's type, and noted in the flight
    recorder."""
    from .flight import get_flight_recorder
    error = type(exc).__name__
    _OBS.counter("setup/auto_layout_fallbacks", error=error).inc()
    get_flight_recorder().note_event(
        "warning", f"AUTO-layout compile of {step} failed ({error}: "
        f"{str(exc)[:200]}); running {kept}", step=step, error=error)
