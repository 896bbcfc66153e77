"""Published peaks of the chips this benchmark may run on, keyed by
`jax.devices()[0].device_kind`. A kind that is not here is an error, never a
default: a share of a peak that was guessed is worse than none."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture table):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(RuntimeError):
    """The device kind has no published peaks in this table."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device kind {device_kind!r} has no entry in benchmark/peaks.py "
            f"(known: {sorted(PEAKS)})") from None
