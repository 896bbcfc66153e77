"""Chip floor calibration: measured matmul/stream rates, cached on disk.

Promoted out of bench.py (where `_measure_floors` ran once per bench
invocation, and before that once per *section*): the two microbenches
that anchor every roofline statement the runtime makes — a chained
8192² bf16 matmul ladder for the MXU rate and a 256 Mi-element
elementwise chain for the HBM stream rate — now live behind one shared
`get_calibration()` with an on-disk cache keyed by (device kind, host),
so a machine measures its floors once and every later process (bench
sections, subprocess children, the perf ledger, the roofline CLI) reads
the same numbers.

Measurement protocol (unchanged from bench.py — see the docstring on
`measure_floors`): both microbenches CHAIN the work inside one jit
(lax.scan / dependent matmuls) and rates are read from the xplane trace
per-kernel device durations, NOT host timers: a host timer around one
dispatch measures the enqueue and the dispatch latency, host-timed chains
carry the while-loop's per-iteration overhead, and XLA fuses unrolled
elementwise chains into one kernel.

Cache location: ``PDTPU_CALIBRATION_DIR`` (default
``~/.cache/paddle_tpu/calibration``), one JSON file per
``{device_kind}_{hostname}``. `get_calibration(recalibrate=True)` (the
``bench.py --recalibrate`` escape hatch) bypasses both the process memo
and the disk cache and rewrites the file.

Sources, in the `Calibration.source` field:

- ``measured``    — trace-derived rates from a live TPU run (a TPU run
  whose trace comes back empty raises; nothing is assumed)
- ``placeholder`` — CPU backend (tests): nominal rates so the roofline
  math stays finite and deterministic; never a device metric
- ``cache``       — loaded from disk (whatever source wrote it)

Published peaks live in `PEAKS`, keyed by jax's ``device_kind``; a kind
that is not in the table is an error, not a default.
"""
from __future__ import annotations

import json
import os
import re
import socket
import threading
import time
from dataclasses import asdict, dataclass
from typing import Optional, Tuple

__all__ = ["Calibration", "ChipPeaks", "PEAKS", "get_calibration",
           "measure_floors", "peak_flops", "cache_path", "reset"]


@dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks."""

    bf16_flops: float      # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float


# Keyed by ``jax.devices()[0].device_kind``. Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per
# chip).
PEAKS = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                             hbm_bytes=16e9),
}

# CPU placeholders, for tests only: they keep the roofline math finite on
# the CPU mesh and are never reported under a device metric's name.
_PEAK_CPU = 1e12
_PLACEHOLDER_CPU = (1.0, 10.0)     # (matmul TFLOP/s, stream GB/s)


@dataclass
class Calibration:
    """One machine's measured (or assumed) chip floors."""

    device_kind: str
    on_tpu: bool
    matmul_tflops: float
    stream_gbs: float
    peak_flops: float
    source: str            # "measured" | "placeholder" | "cache"
    measured_at: float = 0.0
    host: str = ""

    @property
    def floors(self) -> Tuple[float, float]:
        """The (matmul_tflops, stream_gbs) tuple bench.py threads around."""
        return (self.matmul_tflops, self.stream_gbs)

    def to_dict(self) -> dict:
        return asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "Calibration":
        return Calibration(
            device_kind=str(d["device_kind"]), on_tpu=bool(d["on_tpu"]),
            matmul_tflops=float(d["matmul_tflops"]),
            stream_gbs=float(d["stream_gbs"]),
            peak_flops=float(d["peak_flops"]), source=str(d["source"]),
            measured_at=float(d.get("measured_at", 0.0)),
            host=str(d.get("host", "")))


def peak_flops(device_kind: str) -> float:
    """Published bf16 peak of `device_kind` from `PEAKS`; raises on a kind
    the table does not know (the CPU gets its test placeholder)."""
    if device_kind == "cpu":
        return _PEAK_CPU
    if device_kind not in PEAKS:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}: add it "
            f"to calibrate.PEAKS with its source (known: {sorted(PEAKS)})")
    return PEAKS[device_kind].bf16_flops


def _device_kind() -> Tuple[str, bool]:
    import jax

    dev = jax.devices()[0]
    return dev.device_kind, dev.platform == "tpu"


def _cache_dir() -> str:
    return (os.environ.get("PDTPU_CALIBRATION_DIR")
            or os.path.expanduser("~/.cache/paddle_tpu/calibration"))


def cache_path(device_kind: Optional[str] = None,
               host: Optional[str] = None) -> str:
    """Cache file for this (device kind, host) — one floor set per
    machine, shared by every process on it."""
    if device_kind is None:
        device_kind, _ = _device_kind()
    host = host or socket.gethostname()
    key = re.sub(r"[^A-Za-z0-9._-]", "_", f"{device_kind}_{host}")
    return os.path.join(_cache_dir(), f"{key}.json")


def measure_floors(on_tpu: bool) -> Tuple[float, float, str]:
    """Run the two microbenches and return
    (matmul_tflops, stream_gbs, source).

    Chained work + trace-derived kernel times, per the module docstring.
    The CPU gets nominal placeholder rates without dispatching anything —
    the CPU numbers would be meaningless and slow to get. On a TPU a trace
    with no device kernels in it raises.
    """
    if not on_tpu:
        return (*_PLACEHOLDER_CPU, "placeholder")
    import glob
    import gzip
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    a = jax.random.normal(jax.random.PRNGKey(0), (8192, 8192), jnp.bfloat16)

    @jax.jit
    def mm_chain(a):
        def body(c, _):
            return c @ a, None
        y, _ = lax.scan(body, a, None, length=10)
        return y

    x = jax.random.normal(jax.random.PRNGKey(1), (256 * 1024 * 1024,),
                          jnp.bfloat16)

    @jax.jit
    def add_chain(x):
        def body(c, _):
            return c * jnp.bfloat16(1.0001) + jnp.bfloat16(1e-3), None
        y, _ = lax.scan(body, x, None, length=20)
        return y

    def leaf_kernel_us(run):
        """Trace one run; sum device-side LEAF kernel time (drop the
        `while` loop-overhead span, the jit_* parent spans, and step
        markers — only actual kernels count)."""
        tdir = tempfile.mkdtemp(prefix="pdtpu_floors_")
        with jax.profiler.trace(tdir):
            run()
        traces = glob.glob(tdir + "/plugins/profile/*/*.trace.json.gz")
        if not traces:
            raise RuntimeError(f"calibrate: profiler wrote no trace in {tdir}")
        with gzip.open(traces[0]) as f:
            tr = json.load(f)
        dev_pids = {e["pid"] for e in tr["traceEvents"]
                    if e.get("ph") == "M" and e.get("name") == "process_name"
                    and "TPU" in e["args"].get("name", "")}
        total = 0.0
        for e in tr["traceEvents"]:
            nm = e.get("name", "")
            if (e.get("ph") == "X" and e.get("pid") in dev_pids
                    and nm != "while" and not nm.startswith("jit_")
                    and not nm.isdigit()):
                total += e.get("dur", 0.0)
        return total

    for f in (lambda: mm_chain(a), lambda: add_chain(x)):  # compile
        np.asarray(jax.device_get(
            jax.tree_util.tree_leaves(f())[0].ravel()[:1]))
    mm_us = leaf_kernel_us(
        lambda: np.asarray(jax.device_get(mm_chain(a)[:1, :1])))
    add_us = leaf_kernel_us(
        lambda: np.asarray(jax.device_get(add_chain(x)[:1])))
    if not mm_us or not add_us:
        raise RuntimeError(
            f"calibrate: trace holds no TPU kernel time (matmul {mm_us} us, "
            f"stream {add_us} us) — the floors cannot be measured")
    mm_rate = 10 * 2 * 8192**3 / (mm_us * 1e-6)
    stream = 20 * 2 * x.size * 2 / (add_us * 1e-6)
    return mm_rate / 1e12, stream / 1e9, "measured"


_lock = threading.Lock()
_memo: Optional[Calibration] = None


def reset() -> None:
    """Drop the in-process memo (tests; does not touch the disk cache)."""
    global _memo
    with _lock:
        _memo = None


def get_calibration(recalibrate: bool = False) -> Calibration:
    """THE calibration for this machine: process memo → disk cache →
    fresh measurement (which also writes the cache). `recalibrate=True`
    bypasses memo and cache and rewrites the file."""
    global _memo
    with _lock:
        if _memo is not None and not recalibrate:
            return _memo
        kind, on_tpu = _device_kind()
        path = cache_path(kind)
        if not recalibrate:
            cached = _load(path, kind)
            if cached is not None:
                _memo = cached
                return _memo
        mm, stream, source = measure_floors(on_tpu)
        calib = Calibration(
            device_kind=kind, on_tpu=on_tpu, matmul_tflops=float(mm),
            stream_gbs=float(stream), peak_flops=peak_flops(kind),
            source=source, measured_at=time.time(),
            host=socket.gethostname())
        _store(path, calib)
        _memo = calib
        return _memo


def _load(path: str, device_kind: str) -> Optional[Calibration]:
    try:
        with open(path) as f:
            d = json.load(f)
        if d.get("device_kind") != device_kind:
            return None
        c = Calibration.from_dict(d)
        c.source = "cache"
        return c
    except Exception:
        return None


def _store(path: str, calib: Calibration) -> None:
    # best-effort: an unwritable cache dir must never fail a run
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(calib.to_dict(), f, indent=1)
        os.replace(tmp, path)
    except Exception:
        pass
