"""Pre-compile HBM budget planner.

A device OOM on TPU is a bare ``RESOURCE_EXHAUSTED`` that arrives AFTER
minutes of compilation — the most expensive possible way to learn that a
config doesn't fit. This module moves the discovery before the first real
compile: it walks a ladder of (sharding stage, remat policy, microbatch K)
candidates from cheapest-to-run to most-memory-frugal, estimates each one's
per-device footprint, and picks the first that fits a configurable budget.

Estimation prefers the compiler's own numbers: the candidate step function
is lowered and compiled against ``jax.ShapeDtypeStruct`` arguments (no
values are materialized) and XLA's ``memory_analysis()`` supplies
per-device argument/temp/output bytes — exact for the given shapes, and
cheap relative to one training step on real inputs. When the backend
exposes no cost model the planner falls back to an analytic lower bound
(shard-aware state + gradient + feed bytes) and says so in the plan.

The decision is observable: registry gauges (``planner/*``, served at
``/metrics.json``), a flight-recorder event, and a ``hbm_plan`` forensic
dump section so a later OOM post-mortem shows what the planner believed.
When nothing fits, `plan_for` raises `HbmBudgetError` naming the
best-found plan — a structured answer instead of RESOURCE_EXHAUSTED.

Reference analog: the reference framework's ``memory_optimize`` transpiler
pass reused variable memory by liveness analysis at graph-build time; here
the same "fit the device" decision is made against XLA's cost model over
whole-config candidates (sharding/remat/microbatching), which is the form
the decision actually takes on TPU.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

__all__ = [
    "Plan",
    "HbmBudgetError",
    "default_candidates",
    "estimate_plan",
    "plan_for",
    "plan_for_footprint",
    "guard",
    "last_plan",
]

# remat policy -> gauge value (gauges are numeric; the event carries the
# string)
_REMAT_GAUGE = {"none": 0, "minimal": 1, "full": 2}


@dataclass
class Plan:
    """One (sharding stage, remat policy, microbatch K) point, plus what
    the planner learned about it."""

    stage: int = 0
    remat: str = "none"
    microbatch: int = 1
    est_bytes_per_device: Optional[int] = None
    budget_bytes: Optional[int] = None
    source: str = "unevaluated"  # "measured" | "analytic" | "unconstrained"
    fits: Optional[bool] = None
    error: Optional[str] = None
    # XLA's predicted per-microbatch-step cost (cost_analysis of the
    # candidate executable the memory estimate already compiles): the
    # cost a measured step time (the benchmark's `step_ms`) divides into
    predicted_flops: Optional[float] = None
    predicted_bytes_accessed: Optional[float] = None

    def describe(self) -> str:
        est = ("?" if self.est_bytes_per_device is None
               else _fmt_bytes(self.est_bytes_per_device))
        return (f"stage{self.stage}/remat={self.remat}/K={self.microbatch}"
                f" (~{est}/device, {self.source})")

    def to_dict(self) -> dict:
        return {"stage": self.stage, "remat": self.remat,
                "microbatch": self.microbatch,
                "est_bytes_per_device": self.est_bytes_per_device,
                "budget_bytes": self.budget_bytes,
                "source": self.source, "fits": self.fits,
                "error": self.error,
                "predicted_flops": self.predicted_flops,
                "predicted_bytes_accessed": self.predicted_bytes_accessed}


class HbmBudgetError(RuntimeError):
    """No candidate fits the HBM budget (or a guarded run still OOMed).
    Carries the best plan found and every candidate's estimate, so the
    caller can print a table instead of a stack trace."""

    def __init__(self, message: str, plan: Optional[Plan] = None,
                 candidates: Sequence[Plan] = ()):
        super().__init__(message)
        self.plan = plan
        self.candidates = list(candidates)


def _fmt_bytes(n: int) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024 or unit == "TB":
            return f"{n:.2f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024.0
    return f"{n}B"


def default_candidates(batch: Optional[int] = None,
                       dp: int = 1) -> List[Plan]:
    """The escalation ladder, cheapest step first: turn on ZeRO stages
    before remat (sharding is ~free bandwidth on ICI, remat re-burns
    flops), and only then split the batch. Microbatch candidates keep the
    per-step batch divisible by both K and dp."""
    plans = [Plan(0, "none", 1), Plan(1, "none", 1), Plan(2, "none", 1),
             Plan(3, "none", 1), Plan(3, "minimal", 1), Plan(3, "full", 1)]
    for k in (2, 4, 8):
        if batch is not None and (batch % k or (batch // k) % max(dp, 1)):
            continue
        plans.append(Plan(3, "full", k))
    return plans


def resolve_budget_bytes() -> Optional[int]:
    """Budget in bytes, or None when unconstrained (CPU has no allocator
    stats). ``PDTPU_HBM_BUDGET`` (bytes) overrides; otherwise
    ``PDTPU_HBM_BUDGET_FRACTION`` (default 0.9) of the device's
    ``bytes_limit`` — the headroom covers XLA's own scratch and the
    transient double-buffering a donated update needs."""
    env = os.environ.get("PDTPU_HBM_BUDGET")
    if env:
        return int(float(env))
    from .observability.memory import device_memory_stats
    stats = device_memory_stats()
    if not stats or not stats.get("bytes_limit"):
        return None
    frac = float(os.environ.get("PDTPU_HBM_BUDGET_FRACTION", "0.9"))
    return int(stats["bytes_limit"] * frac)


def _compiled_for(program, loss_name: str, plan: Plan):
    from .core.compiler import BuildStrategy, CompiledProgram
    bs = BuildStrategy()
    bs.sharding_strategy = plan.stage
    bs.remat_policy = plan.remat
    return CompiledProgram(program).with_data_parallel(
        loss_name=loss_name, build_strategy=bs)


def _feed_with_microbatch(feed: Dict[str, np.ndarray], k: int):
    if k <= 1:
        return feed
    out = {}
    for n, a in feed.items():
        a = np.asarray(a)
        if a.ndim and a.shape[0] % k == 0:
            a = a[: a.shape[0] // k]
        out[n] = a
    return out


def _measured_bytes(cp, program, feed, loss_name: str) -> int:
    """Per-device footprint from XLA's own cost model: lower+compile the
    candidate step against shape structs (nothing is materialized) and
    read `memory_analysis()`. arg+temp+output−alias: the alias bytes are
    the donated state buffers counted on both sides."""
    import jax

    from .core.executor import _RNG_STATE, _make_key

    pads = cp._zero_pad_map()
    state_structs = {}
    for v in program.list_vars():
        if not v.persistable or v.name == _RNG_STATE:
            continue
        shp = list(v.shape)
        if v.name in pads:
            shp[0] = pads[v.name][1]
        state_structs[v.name] = jax.ShapeDtypeStruct(
            tuple(int(d) for d in shp),
            jax.dtypes.canonicalize_dtype(v.dtype),
            sharding=cp._state_sharding(v.name))
    feed_structs = {
        n: jax.ShapeDtypeStruct(np.asarray(a).shape, np.asarray(a).dtype,
                                sharding=cp._feed_sharding(np.asarray(a).ndim))
        for n, a in feed.items()}
    names = sorted(state_structs)
    fn = cp._build(sorted(feed_structs), [loss_name], names, names,
                   {n: np.asarray(a).ndim for n, a in feed.items()})
    compiled = fn.lower(state_structs, feed_structs, _make_key(0)).compile()
    ma = compiled.memory_analysis()
    est = (int(ma.argument_size_in_bytes) + int(ma.temp_size_in_bytes)
           + int(ma.output_size_in_bytes) - int(ma.alias_size_in_bytes))
    # the same compile also carries XLA's flops/bytes prediction — free
    # to read here, and the other half of predicted-vs-achieved once the
    # perf ledger attributes real dispatches
    from .observability import perf
    return max(est, 0), perf.cost_from_executable(compiled)


def _analytic_bytes(cp, program, feed) -> int:
    """Shard-aware lower bound when the backend has no cost model: state
    (params + accumulators) at their planned shardings, one gradient set
    (sharded from stage2), and the feeds. Activations are deliberately
    NOT guessed — this is a lower bound and the plan says `analytic`."""
    import jax

    dp = 1
    if cp._mesh is not None and cp._data_axis is not None:
        dp = cp._mesh.shape[cp._data_axis]
    state = 0
    grads = 0
    for v in program.list_vars():
        if not v.persistable:
            continue
        try:
            nbytes = int(np.prod([int(d) for d in v.shape]) *
                         jax.dtypes.canonicalize_dtype(v.dtype).itemsize)
        except Exception:
            continue
        factor = dp if cp._zero_plan(v) is not None else 1
        state += nbytes // factor
        if getattr(v, "trainable", False):
            gfactor = dp if cp._zero_stage() >= 2 else 1
            grads += nbytes // gfactor
    feeds = sum(np.asarray(a).nbytes // max(dp, 1) for a in feed.values())
    return state + grads + feeds


def estimate_plan(plan: Plan, program, feed, loss_name: str) -> Plan:
    """Fill in `est_bytes_per_device` + `source` for one candidate."""
    mfeed = _feed_with_microbatch(feed, plan.microbatch)
    cp = _compiled_for(program, loss_name, plan)
    try:
        plan.est_bytes_per_device, cost = _measured_bytes(cp, program, mfeed,
                                                          loss_name)
        if cost is not None:
            plan.predicted_flops = cost["flops"]
            plan.predicted_bytes_accessed = cost["bytes_accessed"]
        plan.source = "measured"
    except Exception as e:
        plan.error = f"{type(e).__name__}: {e}"[:300]
        try:
            plan.est_bytes_per_device = _analytic_bytes(cp, program, mfeed)
            plan.source = "analytic"
        except Exception as e2:
            plan.error += f"; analytic: {type(e2).__name__}: {e2}"[:200]
    return plan


_last_plan: Optional[Plan] = None
_last_candidates: List[Plan] = []


def last_plan() -> Optional[Plan]:
    return _last_plan


def _dump_section() -> object:
    return {"chosen": _last_plan.to_dict() if _last_plan else None,
            "candidates": [p.to_dict() for p in _last_candidates]}


def _record(plan: Plan, candidates: List[Plan], where: str) -> None:
    global _last_plan, _last_candidates
    _last_plan, _last_candidates = plan, list(candidates)
    from .observability.flight import (get_flight_recorder,
                                       register_dump_section)
    from .observability.registry import get_registry
    reg = get_registry()
    reg.gauge("planner/chosen_stage").set(plan.stage)
    reg.gauge("planner/chosen_remat").set(_REMAT_GAUGE.get(plan.remat, -1))
    reg.gauge("planner/chosen_microbatch").set(plan.microbatch)
    if plan.est_bytes_per_device is not None:
        reg.gauge("planner/est_bytes_per_device").set(
            plan.est_bytes_per_device)
    if plan.budget_bytes is not None:
        reg.gauge("planner/budget_bytes").set(plan.budget_bytes)
    # XLA's predicted cost of the chosen plan's step; the achieved side is
    # a measured step time, which the benchmark has and a dispatch has not
    if plan.predicted_flops is not None:
        reg.gauge("planner/predicted_flops").set(plan.predicted_flops)
    if plan.predicted_bytes_accessed is not None:
        reg.gauge("planner/predicted_bytes_accessed").set(
            plan.predicted_bytes_accessed)
    register_dump_section("hbm_plan", _dump_section)
    get_flight_recorder().note_event(
        "info", "hbm_plan", where=where, **plan.to_dict())


def plan_for(program, feed: Dict[str, np.ndarray], loss_name: str,
             budget_bytes: Optional[int] = None,
             candidates: Optional[Sequence[Plan]] = None,
             where: str = "planner") -> Plan:
    """Pick the first candidate on the ladder whose estimated per-device
    bytes fit `budget_bytes` (default: `resolve_budget_bytes()`). With no
    budget (CPU, or stats unavailable and no env override) the baseline
    candidate wins unevaluated — the planner never slows down a machine
    that cannot OOM. Raises `HbmBudgetError` naming the most frugal plan
    found when nothing fits."""
    import jax

    if budget_bytes is None:
        budget_bytes = resolve_budget_bytes()
    if candidates is None:
        batch = None
        for a in feed.values():
            a = np.asarray(a)
            if a.ndim:
                batch = a.shape[0]
                break
        candidates = default_candidates(batch, dp=len(jax.devices()))
    candidates = [Plan(p.stage, p.remat, p.microbatch) if p.fits is not None
                  else p for p in candidates]

    if budget_bytes is None:
        plan = candidates[0]
        plan.source = "unconstrained"
        plan.fits = True
        _record(plan, candidates, where)
        return plan

    evaluated: List[Plan] = []
    for plan in candidates:
        plan.budget_bytes = budget_bytes
        estimate_plan(plan, program, feed, loss_name)
        evaluated.append(plan)
        if plan.est_bytes_per_device is None:
            plan.fits = False
            continue
        plan.fits = plan.est_bytes_per_device <= budget_bytes
        if plan.fits:
            _record(plan, evaluated, where)
            return plan

    best = min((p for p in evaluated if p.est_bytes_per_device is not None),
               key=lambda p: p.est_bytes_per_device, default=None)
    _record(best or evaluated[-1], evaluated, where)
    lines = "; ".join(p.describe() for p in evaluated)
    raise HbmBudgetError(
        f"no (sharding, remat, microbatch) candidate fits the HBM budget "
        f"of {_fmt_bytes(budget_bytes)}/device — best found: "
        f"{best.describe() if best else 'none'} [{lines}]",
        plan=best, candidates=evaluated)


def plan_for_footprint(candidates: Sequence, where: str = "planner",
                       budget_bytes: Optional[int] = None) -> Plan:
    """`plan_for` for workloads that are raw jnp arrays rather than a
    Program (op microbenches, the ring-attention bench): each candidate is
    a ``(Plan, est_bytes)`` pair with a caller-computed analytic footprint
    instead of a compiled estimate. Picks the first fitting plan and
    records it through the same observability path (`planner/*` gauges,
    flight event, ``hbm_plan`` dump section), so a later `guard`-caught
    OOM names it. Raises `HbmBudgetError` when nothing fits."""
    if not candidates:
        raise ValueError("plan_for_footprint: empty candidate list")
    if budget_bytes is None:
        budget_bytes = resolve_budget_bytes()
    evaluated: List[Plan] = []
    for plan, est in candidates:
        plan.est_bytes_per_device = int(est)
        plan.budget_bytes = budget_bytes
        evaluated.append(plan)
        if budget_bytes is None:
            plan.source = "unconstrained"
            plan.fits = True
            _record(plan, evaluated, where)
            return plan
        plan.source = "analytic"
        plan.fits = plan.est_bytes_per_device <= budget_bytes
        if plan.fits:
            _record(plan, evaluated, where)
            return plan
    best = min(evaluated, key=lambda p: p.est_bytes_per_device)
    _record(best, evaluated, where)
    lines = "; ".join(p.describe() for p in evaluated)
    raise HbmBudgetError(
        f"{where}: no candidate footprint fits the HBM budget of "
        f"{_fmt_bytes(budget_bytes)}/device — best found: "
        f"{best.describe()} [{lines}]",
        plan=best, candidates=evaluated)


class guard:
    """Context manager for the dispatch that runs a planner-chosen config:
    a residual OOM (the cost model under-counted, or the budget lied) is
    re-raised as `HbmBudgetError` carrying the active plan and the
    original RESOURCE_EXHAUSTED text, after the flight recorder takes its
    post-mortem. Non-OOM errors pass through untouched."""

    def __init__(self, where: str, plan: Optional[Plan] = None):
        self.where = where
        self.plan = plan  # None -> whatever plan is active at exit time

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc is None:
            return False
        from .observability.flight import get_flight_recorder, is_oom
        if not is_oom(exc):
            return False
        plan = self.plan if self.plan is not None else _last_plan
        get_flight_recorder().record_failure(
            exc, context={"where": self.where,
                          "plan": plan.to_dict() if plan else None})
        plan_txt = plan.describe() if plan else "none recorded"
        raise HbmBudgetError(
            f"{self.where}: OOM under plan {plan_txt}; {str(exc)[:500]}",
            plan=plan, candidates=_last_candidates) from exc
