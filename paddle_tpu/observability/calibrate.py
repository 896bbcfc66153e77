"""Chip peaks: the published table every roofline statement of the package
divides by.

`PEAKS` is keyed by jax's ``device_kind``; a kind that is not in the table
is an error, not a default. `get_calibration()` returns the table's row for
the device this process runs on as a `Calibration`. It measures nothing,
writes nothing and reads no environment: a rate the package reports
(`perf.attribute`, the roofline CLI, `ProfileTrigger`'s kernel tables) is a
share of the published peak, as the benchmark's are
(`benchmark/peaks.py` holds the same numbers for the ledger's `mfu` and
rooflines).

Sources, in the `Calibration.source` field:

- ``published``   — `PEAKS`' row for a TPU
- ``placeholder`` — CPU backend (tests): nominal rates so the roofline
  math stays finite and deterministic; never a device metric
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

__all__ = ["Calibration", "ChipPeaks", "PEAKS", "get_calibration",
           "peak_flops"]


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


@dataclass(frozen=True)
class Calibration:
    """The chip's matmul and stream rates a roofline divides by."""

    device_kind: str
    on_tpu: bool
    matmul_tflops: float
    stream_gbs: float
    peak_flops: float
    source: str            # "published" | "placeholder"

    @property
    def floors(self) -> Tuple[float, float]:
        """(matmul_tflops, stream_gbs), as `tools.roofline` takes them."""
        return (self.matmul_tflops, self.stream_gbs)


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


def get_calibration() -> Calibration:
    """`PEAKS`' row for this process's device kind (the placeholder on the
    CPU); raises on a TPU the table does not know."""
    kind, on_tpu = _device_kind()
    if not on_tpu:
        return Calibration(kind, False, *_PLACEHOLDER_CPU, _PEAK_CPU,
                           "placeholder")
    peak = peak_flops(kind)
    return Calibration(kind, True, peak / 1e12,
                       PEAKS[kind].hbm_bytes_per_s / 1e9, peak, "published")
