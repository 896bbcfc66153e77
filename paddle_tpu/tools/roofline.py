"""Per-kernel roofline report from a chrome/jax-profiler trace.

Given a trace (a ``.trace.json[.gz]`` file or a
``jax.profiler`` log dir), report the top-k kernels by device time with
their achieved GB/s and TFLOP/s and ``util_vs_bound`` — the kernel's
achieved fraction of whichever chip bound (stream or matmul) it sits
closer to — plus the sub-cutoff tail in aggregate. The bounds are the
device kind's published peaks (observability/calibrate.py) unless
overridden with ``--matmul-tflops/--stream-gbs``.

``--diff OTHER`` compares two traces: per-kernel ms deltas sorted by
absolute movement, plus kernels that appear in only one trace — the
"what did my change do" view the kernel campaign (ROADMAP item 4) runs
on.

Reading the numbers: GB/s uses the HLO cost model's ``bytes_accessed``
arg, which counts VMEM-staged re-reads — utilizations above 1.0 are
real and mean XLA is feeding the kernel from VMEM faster than HBM could.
``model_flops`` is algorithmic flops, so padded MXU work shows up as a
LOWER rate, as it should.

Usage::

    python -m paddle_tpu.tools.roofline TRACE [--topk 20]
        [--cutoff-ms 0.5] [--steps 1] [--json]
        [--matmul-tflops X --stream-gbs Y] [--diff OTHER]
"""
from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import sys
from typing import Optional, Tuple

__all__ = ["load_trace", "kernel_table", "capture_kernel_table",
           "diff_tables", "main"]


def load_trace(path: str) -> dict:
    """Load a chrome trace: plain ``.json``, gzipped ``.json.gz``, or a
    jax.profiler log dir (picks the newest
    ``plugins/profile/*/*.trace.json.gz``)."""
    if os.path.isdir(path):
        cands = sorted(
            glob.glob(os.path.join(path, "plugins/profile/*/*.trace.json.gz"))
            + glob.glob(os.path.join(path, "*.trace.json.gz"))
            + glob.glob(os.path.join(path, "*.trace.json")),
            key=os.path.getmtime)
        if not cands:
            raise FileNotFoundError(f"no trace files under {path!r}")
        path = cands[-1]
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def _aggregate(tr: dict) -> "collections.defaultdict":
    """name -> [us, calls, bytes, flops] over the trace's device kernel
    events. Prefers the ``XLA Ops`` thread inside device (``TPU``) pids
    — the per-kernel lane of a jax profiler export; when the trace has
    no such metadata (synthetic or foreign traces) every X event counts,
    minus the loop/step overhead spans."""
    pidname = {e["pid"]: e["args"].get("name", "") for e in tr["traceEvents"]
               if e.get("ph") == "M" and e.get("name") == "process_name"}
    tidname = {(e["pid"], e.get("tid")): e["args"].get("name", "")
               for e in tr["traceEvents"]
               if e.get("ph") == "M" and e.get("name") == "thread_name"}
    dev_pids = {p for p, nm in pidname.items() if "TPU" in nm}
    op_keys = {k for k, nm in tidname.items() if nm == "XLA Ops"
               and (not dev_pids or k[0] in dev_pids)}

    agg = collections.defaultdict(lambda: [0.0, 0, 0.0, 0.0])
    for e in tr["traceEvents"]:
        if e.get("ph") != "X":
            continue
        nm = e.get("name", "")
        if op_keys:
            if (e.get("pid"), e.get("tid")) not in op_keys:
                continue
        else:
            if dev_pids and e.get("pid") not in dev_pids:
                continue
            if nm == "while" or nm.startswith("jit_") or nm.isdigit():
                continue
        a = agg[nm]
        a[0] += e.get("dur", 0.0)
        a[1] += 1
        a[2] += float(e.get("args", {}).get("bytes_accessed", 0) or 0)
        a[3] += float(e.get("args", {}).get("model_flops", 0) or 0)
    return agg


def kernel_table(tr: dict, floors: Tuple[float, float], steps: int = 1,
                 cutoff_ms: float = 0.5, topk: Optional[int] = None) -> dict:
    """The per-kernel table of an in-memory trace: every
    kernel >= cutoff_ms per step with achieved GB/s / TFLOP/s /
    util_vs_bound, the sub-cutoff tail in aggregate, and whole-trace
    aggregate rates."""
    mm_tflops, stream_gbs = floors
    agg = _aggregate(tr)
    if not agg:
        return {"error": "no kernel events in trace"}
    total_us = sum(a[0] for a in agg.values())
    rows = []
    tail_us = tail_by = tail_fl = tail_n = 0
    for nm, (us, c, by, fl) in sorted(agg.items(), key=lambda kv: -kv[1][0]):
        ms = us / steps / 1e3
        gbs = by / (us * 1e-6) / 1e9 if us else 0.0
        tfs = fl / (us * 1e-6) / 1e12 if us else 0.0
        if ms >= cutoff_ms and (topk is None or len(rows) < topk):
            rows.append({"kernel": nm, "ms": round(ms, 3),
                         "calls": c, "gbs": round(gbs, 1),
                         "tfs": round(tfs, 1),
                         "util_vs_bound": round(
                             max(gbs / stream_gbs, tfs / mm_tflops), 3)})
        else:
            tail_us += us
            tail_by += by
            tail_fl += fl
            tail_n += 1
    return {
        "device_ms_per_step": round(total_us / steps / 1e3, 2),
        "kernels": rows,
        "tail": {"n_kernel_names": tail_n,
                 "ms": round(tail_us / steps / 1e3, 2),
                 "gbs": round(tail_by / (tail_us * 1e-6) / 1e9, 1)
                 if tail_us else 0.0,
                 "tfs": round(tail_fl / (tail_us * 1e-6) / 1e12, 1)
                 if tail_us else 0.0},
        "aggregate_gbs": round(
            sum(a[2] for a in agg.values()) / (total_us * 1e-6) / 1e9, 1),
        "aggregate_tfs": round(
            sum(a[3] for a in agg.values()) / (total_us * 1e-6) / 1e12, 1),
    }


def capture_kernel_table(run_step, floors: Tuple[float, float],
                         steps: int = 2, cutoff_ms: float = 0.5) -> dict:
    """Trace `steps` live invocations of `run_step` and build the kernel
    table (the in-vivo path bench_resnet uses)."""
    import shutil
    import tempfile

    import jax

    run_step()  # warm
    tdir = tempfile.mkdtemp(prefix="pdtpu_kernels_")
    try:
        with jax.profiler.trace(tdir):
            for _ in range(steps):
                run_step()
        try:
            tr = load_trace(tdir)
        except FileNotFoundError:
            return {"error": "no trace captured"}
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    return kernel_table(tr, floors, steps=steps, cutoff_ms=cutoff_ms)


def diff_tables(a: dict, b: dict, topk: int = 20) -> dict:
    """Per-kernel ms movement between two kernel tables (b − a): the
    biggest movers by |delta|, plus kernels present in only one trace."""
    rows_a = {r["kernel"]: r for r in a.get("kernels", [])}
    rows_b = {r["kernel"]: r for r in b.get("kernels", [])}
    moved = []
    for nm in set(rows_a) | set(rows_b):
        ra, rb = rows_a.get(nm), rows_b.get(nm)
        ms_a = ra["ms"] if ra else 0.0
        ms_b = rb["ms"] if rb else 0.0
        moved.append({"kernel": nm, "ms_a": ms_a, "ms_b": ms_b,
                      "delta_ms": round(ms_b - ms_a, 3),
                      "status": ("only_b" if ra is None
                                 else "only_a" if rb is None else "both")})
    moved.sort(key=lambda r: -abs(r["delta_ms"]))
    return {
        "device_ms_per_step_a": a.get("device_ms_per_step"),
        "device_ms_per_step_b": b.get("device_ms_per_step"),
        "delta_ms_per_step": (
            round(b["device_ms_per_step"] - a["device_ms_per_step"], 2)
            if (a.get("device_ms_per_step") is not None
                and b.get("device_ms_per_step") is not None) else None),
        "movers": moved[:topk],
        "only_in_a": sorted(set(rows_a) - set(rows_b)),
        "only_in_b": sorted(set(rows_b) - set(rows_a)),
    }


def _resolve_floors(args) -> Tuple[float, float, str]:
    if args.matmul_tflops and args.stream_gbs:
        return args.matmul_tflops, args.stream_gbs, "flags"
    from ..observability.calibrate import get_calibration
    c = get_calibration()
    return c.matmul_tflops, c.stream_gbs, c.source


def _print_table(tab: dict, floors, source: str) -> None:
    mm, st = floors
    print(f"floors: matmul {mm:.1f} TFLOP/s, stream {st:.1f} GB/s "
          f"({source})")
    if "error" in tab:
        print(f"error: {tab['error']}")
        return
    print(f"device time/step: {tab['device_ms_per_step']:.2f} ms   "
          f"aggregate: {tab['aggregate_gbs']:.1f} GB/s, "
          f"{tab['aggregate_tfs']:.1f} TFLOP/s")
    hdr = f"{'kernel':<48}{'ms':>9}{'calls':>7}{'GB/s':>8}" \
          f"{'TF/s':>8}{'util':>7}"
    print(hdr)
    for r in tab["kernels"]:
        print(f"{r['kernel'][:47]:<48}{r['ms']:>9.3f}{r['calls']:>7}"
              f"{r['gbs']:>8.1f}{r['tfs']:>8.1f}{r['util_vs_bound']:>7.3f}")
    t = tab["tail"]
    print(f"{'(tail: ' + str(t['n_kernel_names']) + ' kernels)':<48}"
          f"{t['ms']:>9.3f}{'':>7}{t['gbs']:>8.1f}{t['tfs']:>8.1f}")


def _print_diff(d: dict) -> None:
    print(f"device ms/step: {d['device_ms_per_step_a']} -> "
          f"{d['device_ms_per_step_b']} "
          f"(delta {d['delta_ms_per_step']})")
    print(f"{'kernel':<48}{'ms_a':>9}{'ms_b':>9}{'delta':>9}  status")
    for r in d["movers"]:
        print(f"{r['kernel'][:47]:<48}{r['ms_a']:>9.3f}{r['ms_b']:>9.3f}"
              f"{r['delta_ms']:>9.3f}  {r['status']}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.tools.roofline", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("trace", help="trace file (.json/.json.gz) or "
                                 "jax.profiler log dir")
    p.add_argument("--diff", metavar="OTHER",
                   help="second trace: report per-kernel deltas "
                        "(OTHER - trace)")
    p.add_argument("--save-golden", action="store_true",
                   help="persist this trace's kernel table as the "
                        "golden for this (device kind, host) — the "
                        "baseline ProfileTrigger diffs captures against")
    p.add_argument("--diff-golden", action="store_true",
                   help="diff this trace against the recorded golden "
                        "(trace - golden)")
    p.add_argument("--golden-path", default=None,
                   help="override the golden cache file "
                        "(default: under PDTPU_GOLDEN_DIR, keyed by "
                        "device kind and host)")
    p.add_argument("--topk", type=int, default=20)
    p.add_argument("--cutoff-ms", type=float, default=0.5)
    p.add_argument("--steps", type=int, default=1,
                   help="steps captured in the trace (divides times)")
    p.add_argument("--json", action="store_true", dest="as_json")
    p.add_argument("--matmul-tflops", type=float, default=None)
    p.add_argument("--stream-gbs", type=float, default=None)
    args = p.parse_args(argv)

    try:
        tr = load_trace(args.trace)
    except Exception as e:
        print(f"roofline: cannot load {args.trace!r}: {e}", file=sys.stderr)
        return 2
    mm, st, source = _resolve_floors(args)
    tab = kernel_table(tr, (mm, st), steps=args.steps,
                       cutoff_ms=args.cutoff_ms, topk=args.topk)
    if args.save_golden:
        from ..observability import profile_trigger
        if "error" in tab:
            print(f"roofline: not saving golden: {tab['error']}",
                  file=sys.stderr)
            return 2
        path = profile_trigger.save_golden(tab, path=args.golden_path,
                                           note=args.trace)
        print(f"golden saved: {path}")
        return 0
    if args.diff_golden:
        from ..observability import profile_trigger
        golden = profile_trigger.load_golden(args.golden_path)
        if golden is None:
            print("roofline: no golden recorded (run --save-golden on a "
                  "healthy trace first)", file=sys.stderr)
            return 2
        d = diff_tables(golden["table"], tab, topk=args.topk)
        if args.as_json:
            print(json.dumps({"golden": golden["table"], "trace": tab,
                              "diff": d}))
        else:
            _print_diff(d)
        return 0
    if args.diff:
        try:
            tr2 = load_trace(args.diff)
        except Exception as e:
            print(f"roofline: cannot load {args.diff!r}: {e}",
                  file=sys.stderr)
            return 2
        tab2 = kernel_table(tr2, (mm, st), steps=args.steps,
                            cutoff_ms=args.cutoff_ms, topk=args.topk)
        d = diff_tables(tab, tab2, topk=args.topk)
        if args.as_json:
            print(json.dumps({"a": tab, "b": tab2, "diff": d}))
        else:
            _print_diff(d)
        return 0
    if args.as_json:
        print(json.dumps({"floors": {"matmul_tflops": mm, "stream_gbs": st,
                                     "source": source}, **tab}))
    else:
        _print_table(tab, (mm, st), source)
    return 0


if __name__ == "__main__":
    sys.exit(main())
