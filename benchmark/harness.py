"""One loop for every cell; the cell's files only parametrise it.

    load_cell(name)                       find the cell's files by name
    run_cell(cell, seed, seconds, trace)  set-up, check, warm-up, window,
                                          (traced stretch,) the result dict

Nothing here names a cell, a configuration, a traffic mix or a metric: a later
PR adds any of them as new files plus new entries in BENCHMARK.json.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import time
from typing import Callable, Optional

import numpy as np

from benchmark import check, loop, xtrace
from benchmark.peaks import peaks_for

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# jax.monitoring events: every backend compile (hit or miss of the persistent
# cache), and what a hit spent reading the cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class BenchmarkError(RuntimeError):
    """The run cannot give a result (exit code nonzero, no result line)."""


# ---------------------------------------------------------------------------
# finding a cell's files
# ---------------------------------------------------------------------------

def _load_module(path: str):
    """Import one of the cell's files by its path, so that a tree other than
    this checkout's (a test's copy with files added) is read from there."""
    name = "_bench_" + os.path.splitext(os.path.relpath(path, "/"))[0].replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise BenchmarkError(f"cannot import {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """The files of one cell, found by the names in BENCHMARK.json:
    <dir>/configs/<config>.json (+ .py, _reference.py),
    <dir>/traffic/<traffic>.json, <dir>/traffic/generators/<generator>.py and
    <dir>/layer_metrics/<metric>.py, where <dir> holds the configuration's
    file two levels up."""

    def __init__(self, name: str, root: str = ROOT):
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.benchmark = json.load(f)
        rows = [w for w in self.benchmark["workloads"] if w["name"] == name]
        if len(rows) != 1:
            known = [w["name"] for w in self.benchmark["workloads"]]
            raise BenchmarkError(f"no workload {name!r} in BENCHMARK.json "
                                 f"(known: {known})")
        self.workload = rows[0]
        self.name, self.chips = name, int(self.workload["chips"])
        (cfg_row,) = [c for c in self.benchmark["configs"]
                      if c["name"] == self.workload["config"]]
        cfg_path = os.path.join(root, cfg_row["file"])
        cfg_dir = os.path.dirname(cfg_path)
        bench_dir = os.path.dirname(cfg_dir)
        with open(cfg_path) as f:
            self.config = json.load(f)
        self.adapter = _load_module(
            os.path.join(cfg_dir, f"{cfg_row['name']}.py"))
        self.reference = _load_module(
            os.path.join(cfg_dir, f"{cfg_row['name']}_reference.py"))
        with open(os.path.join(bench_dir, "traffic",
                               f"{self.workload['traffic']}.json")) as f:
            self.traffic = json.load(f)
        self.generator = _load_module(os.path.join(
            bench_dir, "traffic", "generators",
            f"{self.traffic['generator']}.py"))
        self.end_to_end = [m for m in self.benchmark["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in self.benchmark["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]
        self.readers = {
            m["name"]: _load_module(os.path.join(
                bench_dir, "layer_metrics", f"{m['name']}.py")).read
            for m in self.per_layer}


def load_cell(name: str, root: str = ROOT) -> Cell:
    return Cell(name, root)


# ---------------------------------------------------------------------------
# counting compiles
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts backend compiles and the seconds they and the cache reads took,
    through jax.monitoring: every jit in the process, not only the
    executor's."""

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration
        elif event == CACHE_LOAD_EVENT:
            self.seconds += duration


# ---------------------------------------------------------------------------
# the loop
# ---------------------------------------------------------------------------

def _run_blocks(system, ring, cursor: int, steps_per_block: int,
                seconds: Optional[float], blocks: Optional[int]):
    """Blocks until `seconds` have passed (the last block is always finished
    and always counted) or for exactly `blocks` blocks. Returns readings
    (seconds a step), the wall time from first start to last end, steps
    dispatched, steps failed and the ring cursor."""
    import jax

    readings, attempted, failed = [], 0, 0
    t_begin = time.perf_counter()
    t_end = t_begin
    while True:
        if blocks is not None:
            if len(readings) >= blocks:
                break
        elif t_end - t_begin >= seconds:
            break
        t0 = time.perf_counter()
        loss = None
        ok = True
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            for _ in range(steps_per_block):
                attempted += 1
                try:
                    loss = system.step(ring[cursor % len(ring)])
                except Exception as e:        # a failed step is counted
                    print(f"bench: step raised {type(e).__name__}: "
                          f"{str(e)[:200]}", flush=True)
                    failed += 1
                    ok = False
                cursor += 1
        with jax.profiler.TraceAnnotation("bench.wait"):
            value = float(np.asarray(loss)) if loss is not None else math.nan
        t_end = time.perf_counter()
        if ok and not math.isfinite(value):
            failed += steps_per_block
        readings.append((t_end - t0) / steps_per_block)
    return readings, t_end - t_begin, attempted, failed, cursor


def first_steps(cell: Cell, system, head, seed: int, devices) -> dict:
    """Drive the started system through the followed steps by the window's
    own call and read back what the check compares: each step's loss, the
    first gradient's norm by leaf, the norm of the parameters' change by leaf
    (against the seeded weights, made again: the system's were donated)."""
    losses, grad_norms = [], None
    for i, batch in enumerate(head):
        losses.append(float(np.asarray(system.step(batch))))
        if i == 0:
            grad_norms = system.first_gradient_norms()
    initial = cell.reference.make_weights(cell.config, seed, head, devices)
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": system.update_norms(initial)}


def _traced_stretch(cell: Cell, system, ring, cursor: int, blocks: int):
    """`blocks` blocks under the profiler, reduced to operation tuples."""
    import jax

    spb = int(cell.traffic["steps_per_block"])
    trace_dir = os.path.join(ROOT, ".bench_trace", cell.name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # the spans are TraceAnnotations
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        _, _, attempted, failed, cursor = _run_blocks(
            system, ring, cursor, spb, None, blocks)
    finally:
        jax.profiler.stop_trace()
    xplane = xtrace.find_xplane(trace_dir)
    events = xtrace.extract(xplane)
    keep = os.environ.get("BENCH_KEEP_EVENTS")
    if keep:      # how tests/benchmark/data's recorded trace was made
        os.makedirs(os.path.dirname(keep) or ".", exist_ok=True)
        with open(keep, "w") as f:
            json.dump({"events": events, "steps": blocks * spb,
                       "describe": xtrace.describe(xplane)}, f)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return xtrace.Reduced(events, blocks * spb), attempted, failed


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, backend_s: float = 0.0,
             build: Optional[Callable] = None,
             device: Optional[dict] = None, say=print) -> dict:
    """The whole run of one cell. `t_start` is when the backend was up:
    `setup_s` runs from there to the first timed block, less the reference's
    time; `backend_s` is what the process took to get there (reported as a
    per-layer metric). `build` replaces the adapter's builder and `device`
    the look for a chip (tests only). Returns the result object."""
    import jax

    cfg, traffic, chips = cell.config, cell.traffic, cell.chips
    devices = jax.devices()[:chips]
    if device is None:
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": chips}
    peaks = peaks_for(device["kind"])
    compiles = CompileCounter()
    build = build or cell.adapter.build
    spb = int(traffic["steps_per_block"])
    follow_steps = int(cfg["reference"]["follow_steps"])

    marks = []

    def mark(what):
        marks.append((what, time.perf_counter()))

    # -- inputs from the seed ----------------------------------------------
    ring = cell.generator.make_ring(cfg, traffic, seed)
    if len(ring) < follow_steps:
        raise BenchmarkError("the ring is shorter than the followed steps")
    head = ring[:follow_steps]
    mark("inputs from the seed")

    # -- the plain reference, before the program's state is made; its time
    #    is not set-up and is taken out of setup_s
    t_ref = time.perf_counter()
    weights = cell.reference.make_weights(cfg, seed, head, devices)
    expected = cell.reference.follow(cfg, weights, head, devices, seed=seed)
    del weights
    reference_s = time.perf_counter() - t_ref
    compile_in_reference_s = compiles.seconds
    marks.append(("(reference)", time.perf_counter()))

    # -- the system: one object, checked on its first steps and then timed
    system = build(cfg, traffic, chips)
    mark("program built")
    system.start(cell.reference.make_weights(cfg, seed, head, devices))
    mark("startup and seeded weights")
    got = first_steps(cell, system, head, seed, devices)
    verdict = check.compare(got, expected, cfg["limits"])
    mark("first steps (compile) and their read-back")
    say(f"bench: check against the plain reference "
        f"({reference_s:.1f} s, not counted in setup_s): "
        + check.format_numbers(verdict["numbers"]), flush=True)

    # -- warm-up: the one step shape is compiled by now; the last warm-up
    #    blocks must compile nothing
    cursor = follow_steps
    before = compiles.count
    _, _, _, warm_failed, cursor = _run_blocks(
        system, ring, cursor, spb, None, max(1, int(traffic["warmup_blocks"])))
    warm_compiles = compiles.count - before
    hbm = system.hbm()
    mark("warm-up and memory analysis")
    setup_compile_s = compiles.seconds - compile_in_reference_s
    at_window = compiles.count

    # -- the window ---------------------------------------------------------
    # what set-up left on the Python heap is not collected again inside the
    # window: garbage-collector pauses would land in a few readings
    gc.collect()
    gc.freeze()
    t_window = time.perf_counter()
    setup_s = t_window - t_start - reference_s
    spans = [(what, t - (marks[i - 1][1] if i else t_start))
             for i, (what, t) in enumerate(marks)]
    say("bench: set-up " + ", ".join(
        f"{what} {sec:.1f} s" for what, sec in spans if what != "(reference)")
        + f"; {setup_compile_s:.1f} s of it in compiles and cache reads",
        flush=True)
    # a traced run keeps the profiler off for most of --seconds
    readings, wall_s, attempted, failed, cursor = _run_blocks(
        system, ring, cursor, spb, seconds * (0.8 if trace else 1.0), None)
    reduced = None
    if trace:
        reduced, t_attempted, t_failed = _traced_stretch(
            cell, system, ring, cursor, int(traffic["trace_blocks"]))
        attempted += t_attempted
        failed += t_failed
    late_compiles = compiles.count - at_window

    # -- all the work of the finished blocks over all their time -------------
    work = cell.adapter.work_per_step(cfg, traffic)
    summary = loop.reduce_window(readings, spb, wall_s, work, chips)
    say(f"bench: {summary['readings']} readings of {spb} step(s) in "
        f"{wall_s:.3f} s (median reading {summary['step_ms_median']:.3f} ms); "
        f"{attempted} steps dispatched, {failed} failed; "
        f"{late_compiles} compiles inside the window, {warm_compiles} in the "
        f"last warm-up blocks", flush=True)
    correct = bool(verdict["ok"] and late_compiles == 0 and warm_compiles == 0
                   and failed == 0 and warm_failed == 0)

    live = (hbm["argument_bytes"] + hbm["output_bytes"] - hbm["alias_bytes"]
            + hbm["temp_bytes"])
    stats_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                     for d in devices)
    device = dict(device, memory_peak_bytes=max(live, stats_peak))

    values = {
        "step_ms": summary["step_ms"],
        "step_ms_p90": summary["step_ms_p90"],
        cfg["rate_metric"]: summary["rate_per_chip"],
        "setup_s": setup_s,
    }
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if not trace:
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}
    else:
        ctx = {
            "trace": reduced, "config": cfg, "traffic": traffic,
            "chips": chips, "peaks": peaks, "hbm": hbm,
            "counts": cell.adapter.counts(cfg, traffic),
            "readings_s": readings, "wall_s": wall_s,
            "steps_per_block": spb, "values": values,
            "late_compiles": late_compiles, "compile_s": setup_compile_s,
            "backend_s": backend_s, "step_ms_median": summary["step_ms_median"],
        }
        metrics = {}
        for m in cell.per_layer:
            value = cell.readers[m["name"]](ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        result["metrics"] = metrics
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops(10),
                               "idle_gaps": reduced.idle_gaps(5)[:10]}
    result["device"] = device
    return result
