"""The state-space scan's share of its roofline: the least time the chip could
take for a step's scans (the larger of their required operations over the
published peak and their required bytes over the published bandwidth, both
from the shapes: `counts` of the configuration) over the measured `ssd_ms`.
The scan is XLA-compiled einsums today (no kernel): the share says how far
that form stands from the chip."""
from benchmark.layer_metrics import ssd_ms


def bound(ctx):
    c, p = ctx["counts"], ctx["peaks"]
    by_flops = c["ssd_flops_per_step"] / ctx["chips"] / p["flops_per_s"]
    by_bytes = c["ssd_bytes_per_step"] / ctx["chips"] / p["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("flops" if by_flops >= by_bytes
                                     else "bytes")


def read(ctx):
    if "ssd_flops_per_step" not in ctx["counts"]:
        return None
    measured = ssd_ms.read(ctx)
    if not measured:
        return None
    return 100.0 * bound(ctx)[0] / (measured * 1e-3)
