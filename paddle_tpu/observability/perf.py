"""Perf-attribution ledger: per-(program, signature) cost accounting.

At compile time the dispatch sites (`Executor.run`, with or without a
mesh, and `Executor.run_batched`/`train_scanned`, through
`core.executor._record_dispatch`; `Predictor.run`) register what one
dispatch of the executable costs, in extraction-preference order:

1. **XLA's own numbers** — ``cost_analysis()`` (flops, bytes accessed,
   transcendentals) and ``memory_analysis()`` (per-device
   arg+temp+output−alias bytes) from the AOT ``Compiled`` object where
   one exists (the `_AutoLayoutStep` fast path), or from a trace-only
   ``Lowered`` for the lazy-jit paths (``source="xla"`` /
   ``"lowered"``).
2. **Analytic fallback** — for backends that return nothing: matmul /
   conv flops walked from the Program IR (×3 when the program carries a
   backward pass) plus a state/feed byte count (``source="analytic"``).

A rate is computed only from a time that ends at a fetch. The training
dispatch sites have no such time (their jitted call returns when the step
is enqueued), so they register costs and attribute nothing; a training
rate comes from ``benchmark/``, which times blocks that end at a fetched
loss. `Predictor.run` waits for its outputs, so it joins its wall time
with its ledger entry (`CostLedger.on_dispatch` → `attribute`) against the
published peaks of :mod:`~paddle_tpu.observability.calibrate` and sets
live per-program gauges in the process registry — visible on
``/metrics``, ``/metrics.json``, flight dumps, and federation like every
other series:

- ``perf/achieved_tflops{program,sig}``
- ``perf/achieved_gbs{program,sig}``
- ``perf/mfu{program,sig}``         (vs the chip's peak flops)
- ``perf/roofline_fraction{program,sig}`` (vs max(matmul, stream) bound)

Caveats the numbers inherit from XLA's cost model: ``bytes accessed``
counts VMEM-staged re-reads, so achieved GB/s (and hence the roofline
fraction of a memory-bound program) can legitimately exceed the
published stream rate; ``flops`` is model flops, not MXU-padded flops.
See docs/migration.md "Performance attribution".

``PDTPU_PERF_LEDGER=0`` disables registration and attribution
entirely; ``PDTPU_PERF_TRACE_COST=0`` skips the trace-only
``Lowered`` extraction on the lazy-jit paths (the one path whose
extraction is not free — it re-traces the step function once per
compile).
"""
from __future__ import annotations

import collections
import os
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

from . import calibrate
from .registry import get_registry

__all__ = ["ProgramCost", "CostLedger", "get_ledger", "attribute",
           "cost_from_executable", "analytic_cost", "enabled"]

_MAX_ENTRIES = 256


def enabled() -> bool:
    return os.environ.get("PDTPU_PERF_LEDGER", "1") != "0"


def trace_cost_enabled() -> bool:
    return enabled() and os.environ.get("PDTPU_PERF_TRACE_COST", "1") != "0"


@dataclass
class ProgramCost:
    """What ONE dispatch of an executable costs. For scan dispatches
    (`steps` > 1) the numbers cover the whole K-step scan."""

    flops: float = 0.0
    bytes_accessed: float = 0.0
    transcendentals: float = 0.0
    peak_bytes: Optional[int] = None   # per-device arg+temp+out−alias
    source: str = "none"               # "xla" | "lowered" | "analytic"
    steps: int = 1
    label: Optional[str] = None
    last: Dict[str, float] = field(default_factory=dict)  # last attribution

    def to_dict(self) -> dict:
        d = {"flops": self.flops, "bytes_accessed": self.bytes_accessed,
             "transcendentals": self.transcendentals,
             "peak_bytes": self.peak_bytes, "source": self.source,
             "steps": self.steps}
        if self.label:
            d["label"] = self.label
        if self.last:
            d["last"] = dict(self.last)
        return d


# -- extraction --------------------------------------------------------------

def cost_from_executable(executable) -> Optional[dict]:
    """flops / bytes_accessed / transcendentals from an XLA ``Compiled``
    or ``Lowered`` object, or None when the backend returns nothing (a
    runtime may raise Unimplemented)."""
    if executable is None:
        return None
    try:
        ca = executable.cost_analysis()
    except Exception:
        return None
    if not isinstance(ca, dict):
        return None
    out = {"flops": float(ca.get("flops", 0.0) or 0.0),
           "bytes_accessed": float(ca.get("bytes accessed", 0.0) or 0.0),
           "transcendentals": float(ca.get("transcendentals", 0.0) or 0.0)}
    if out["flops"] <= 0.0 and out["bytes_accessed"] <= 0.0:
        return None
    return out


def memory_from_executable(executable) -> Optional[int]:
    """Per-device live-byte estimate from ``memory_analysis()``
    (arg+temp+output−alias, the planner's formula), or None."""
    try:
        ma = executable.memory_analysis()
        est = (int(ma.argument_size_in_bytes) + int(ma.temp_size_in_bytes)
               + int(ma.output_size_in_bytes) - int(ma.alias_size_in_bytes))
        return max(est, 0)
    except Exception:
        return None


def _var_nbytes(v, batch: Optional[int]) -> int:
    import jax
    import numpy as np

    if v.shape is None:
        return 0
    shp = [int(d) if int(d) > 0 else int(batch or 1) for d in v.shape]
    try:
        itemsize = jax.dtypes.canonicalize_dtype(v.dtype).itemsize
    except Exception:
        itemsize = 4
    return int(np.prod(shp)) * int(itemsize) if shp else int(itemsize)


def analytic_cost(program, feed: Optional[Dict[str, Any]] = None) -> dict:
    """Analytic cost of one dispatch from the Program IR, for backends
    whose cost model returns nothing.

    flops: 2mnk per matmul/mul, 2·out·k²·cin per conv2d (forward),
    tripled when the program carries a backward pass (any `*_grad` op or
    `@GRAD` output). bytes: feeds + persistables (params read fwd+bwd
    and written by the update when training) + one write per op output
    whose shape is known. A deliberate lower bound — activations that
    XLA rematerializes or stages through VMEM are not modeled — and the
    entry says ``analytic`` so consumers can weigh it accordingly.
    """
    import numpy as np

    batch = None
    for a in (feed or {}).values():
        shp = getattr(a, "shape", None)
        if shp:
            batch = int(shp[0])
            break

    blk = program.global_block()

    def shape_of(name):
        v = blk._find_var_recursive(name)
        if v is None or v.shape is None:
            return None
        return [int(d) if int(d) > 0 else int(batch or 1) for d in v.shape]

    fwd_flops = 0.0
    out_bytes = 0.0
    has_bwd = False
    for b in program.blocks:
        for op in b.ops:
            t = op.type
            if t.endswith("_grad"):
                has_bwd = True
            if t in ("mul", "matmul", "matmul_v2"):
                xs = op.input("X") or op.input_names()[:1]
                ys = op.input("Y") or op.input_names()[1:2]
                sx = shape_of(xs[0]) if xs else None
                sy = shape_of(ys[0]) if ys else None
                if sx and sy and len(sy) >= 2:
                    m = int(np.prod(sx[:-1]))
                    k = sx[-1]
                    n = sy[-1]
                    fwd_flops += 2.0 * m * k * n
            elif t == "conv2d":
                outs = op.output("Output") or op.output_names()[:1]
                fils = op.input("Filter") or []
                so = shape_of(outs[0]) if outs else None
                sf = shape_of(fils[0]) if fils else None
                if so and sf and len(sf) == 4:
                    # filter [cout, cin, kh, kw]; out [b, cout, oh, ow]
                    fwd_flops += (2.0 * np.prod(so)
                                  * sf[1] * sf[2] * sf[3])
            for name in op.output_names():
                s = shape_of(name)
                if s:
                    v = blk._find_var_recursive(name)
                    out_bytes += _var_nbytes(v, batch) if v is not None \
                        else 0
            if any(n.endswith("@GRAD") for n in op.output_names()):
                has_bwd = True

    state_bytes = sum(_var_nbytes(v, batch) for v in program.list_vars()
                      if v.persistable)
    feed_bytes = sum(int(getattr(a, "nbytes", 0) or 0)
                     for a in (feed or {}).values())
    mult = 3.0 if has_bwd else 1.0
    # params: read fwd (+ read bwd + update write when training)
    bytes_accessed = (feed_bytes + state_bytes * (3.0 if has_bwd else 1.0)
                      + out_bytes)
    return {"flops": fwd_flops * mult, "bytes_accessed": bytes_accessed,
            "transcendentals": 0.0}


# -- attribution -------------------------------------------------------------

def attribute(*, flops: float = 0.0, bytes_accessed: float = 0.0,
              seconds: float, calib: Optional[calibrate.Calibration] = None
              ) -> dict:
    """Join a cost with a wall time against the chip's published peaks.
    `seconds` must end at a fetch: the time of an enqueue gives a rate
    that is too high by the step's length over the enqueue's.

    Returns achieved_tflops / achieved_gbs / mfu / roofline_fraction /
    bound. roofline_fraction is floor_time/actual_time where the floor
    is max(flops at the matmul rate, bytes at the stream rate); it is
    NOT capped at 1.0 here — XLA's bytes_accessed includes VMEM re-reads,
    so honest fractions can exceed unity (cap at presentation time if a
    bounded number is wanted).
    """
    calib = calib or calibrate.get_calibration()
    seconds = max(float(seconds), 1e-12)
    tfs = flops / seconds / 1e12
    gbs = bytes_accessed / seconds / 1e9
    mm_s = flops / (calib.matmul_tflops * 1e12)
    st_s = bytes_accessed / (calib.stream_gbs * 1e9)
    floor_s = max(mm_s, st_s)
    return {
        "achieved_tflops": tfs,
        "achieved_gbs": gbs,
        "mfu": flops / seconds / calib.peak_flops,
        "roofline_fraction": floor_s / seconds,
        "bound": "matmul" if mm_s >= st_s else "memory",
    }


# -- the ledger --------------------------------------------------------------

def _pkey(program_id) -> str:
    if isinstance(program_id, str):
        return program_id
    return f"0x{program_id:x}"


class CostLedger:
    """Bounded map (program, sig) → :class:`ProgramCost`, with the
    serving path's attribution into the registry."""

    def __init__(self, registry=None, max_entries: int = _MAX_ENTRIES):
        self._reg = registry
        self._max = int(max_entries)
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[tuple, ProgramCost]" = \
            collections.OrderedDict()
        self._pass_reports: "collections.OrderedDict[str, dict]" = \
            collections.OrderedDict()
        self._dump_registered = False
        self._pass_dump_registered = False

    def _registry(self):
        return self._reg if self._reg is not None else get_registry()

    # -- registration (compile time) ------------------------------------
    def register(self, program_id, sig: Optional[str], *,
                 executable=None, program=None,
                 feed: Optional[Dict[str, Any]] = None,
                 steps: int = 1, label: Optional[str] = None
                 ) -> Optional[ProgramCost]:
        """Record what one dispatch costs. Tries `executable`
        (``Compiled`` or ``Lowered``) first, then the analytic IR walk;
        registers nothing when both come up empty or the ledger is
        disabled. Never raises — a cost-model failure must not break a
        dispatch site."""
        if not enabled():
            return None
        try:
            cost = cost_from_executable(executable)
            if cost is not None:
                source = ("xla" if hasattr(executable, "memory_analysis")
                          else "lowered")
            elif program is not None:
                cost = analytic_cost(program, feed)
                source = "analytic"
                if steps > 1:
                    # analytic counts ONE step; a scan executable runs K
                    cost = {k: v * steps for k, v in cost.items()}
            else:
                return None
            if cost["flops"] <= 0.0 and cost["bytes_accessed"] <= 0.0:
                return None
            entry = ProgramCost(
                flops=cost["flops"], bytes_accessed=cost["bytes_accessed"],
                transcendentals=cost.get("transcendentals", 0.0),
                peak_bytes=memory_from_executable(executable),
                source=source, steps=int(steps), label=label)
            with self._lock:
                self._entries[(_pkey(program_id), sig)] = entry
                while len(self._entries) > self._max:
                    self._entries.popitem(last=False)
                if not self._dump_registered:
                    self._dump_registered = True
                    register_dump = None
                    try:
                        from .flight import register_dump_section
                        register_dump = register_dump_section
                    except Exception:
                        pass
                    if register_dump is not None:
                        register_dump("perf_ledger", self.snapshot)
            return entry
        except Exception:
            return None

    def get(self, program_id, sig: Optional[str]) -> Optional[ProgramCost]:
        with self._lock:
            return self._entries.get((_pkey(program_id), sig))

    # -- pass attribution (compile time, from ir.PassPipeline) -----------
    def record_passes(self, label: str, report: dict) -> None:
        """Record one PassPipeline run: the per-pass cost-delta report
        keyed by the program label, exported as ``ir/pass_*`` gauges and
        the ``ir_passes`` flight-dump section. Never raises."""
        if not enabled():
            return
        try:
            with self._lock:
                self._pass_reports[label] = report
                while len(self._pass_reports) > self._max:
                    self._pass_reports.popitem(last=False)
                if not self._pass_dump_registered:
                    self._pass_dump_registered = True
                    try:
                        from .flight import register_dump_section
                        register_dump_section("ir_passes", self.pass_reports)
                    except Exception:
                        pass
            reg = self._registry()
            for rec in report.get("passes", ()):
                labels = {"program": label, "ir_pass": rec["pass"]}
                reg.gauge("ir/pass_flops_delta", **labels).set(
                    rec.get("flops_delta", 0.0))
                reg.gauge("ir/pass_bytes_delta", **labels).set(
                    rec.get("bytes_delta", 0.0))
                reg.gauge("ir/pass_ops_removed", **labels).set(
                    rec.get("ops_before", 0) - rec.get("ops_after", 0))
        except Exception:
            pass

    def pass_reports(self) -> dict:
        """label → the PassPipeline report recorded for that program."""
        with self._lock:
            return {k: dict(v) for k, v in self._pass_reports.items()}

    # -- attribution (dispatch time) ------------------------------------
    def on_dispatch(self, program_id, sig: Optional[str], wall_ms: float
                    ) -> Optional[dict]:
        """Attribute one non-compile dispatch against its ledger entry;
        sets the live ``perf/*`` gauges and returns the attribution (or
        None when there is no entry). `wall_ms` must include the wait for
        the outputs (`Predictor.run`'s does)."""
        if not enabled():
            return None
        entry = self.get(program_id, sig)
        if entry is None or wall_ms <= 0.0:
            return None
        try:
            att = attribute(flops=entry.flops,
                            bytes_accessed=entry.bytes_accessed,
                            seconds=wall_ms / 1e3)
        except Exception:
            return None
        entry.last = {k: round(v, 6) for k, v in att.items()
                      if isinstance(v, float)}
        reg = self._registry()
        labels = {"program": _pkey(program_id)}
        if sig is not None:
            labels["sig"] = sig
        reg.gauge("perf/achieved_tflops", **labels).set(
            att["achieved_tflops"])
        reg.gauge("perf/achieved_gbs", **labels).set(att["achieved_gbs"])
        reg.gauge("perf/mfu", **labels).set(att["mfu"])
        reg.gauge("perf/roofline_fraction", **labels).set(
            att["roofline_fraction"])
        return att

    # -- introspection ---------------------------------------------------
    def snapshot(self) -> dict:
        """Flight-dump / debug view: every entry with its last
        attribution."""
        with self._lock:
            return {f"{p} {s or ''}".strip(): e.to_dict()
                    for (p, s), e in self._entries.items()}

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()
            self._pass_reports.clear()


_ledger = CostLedger()


def get_ledger() -> CostLedger:
    """THE process-wide cost ledger the dispatch sites register into."""
    return _ledger
