"""The sliding-window attention kernels' share of their roofline: the least
time the chip could take for a step's window layers (the larger of their
required operations over the published peak and their required bytes over
the published bandwidth, both from the shapes: the BAND's pairs, not the
tiles a kernel schedules; `counts` of the configuration) over the measured
`swa_ms`."""
from benchmark.layer_metrics import swa_ms


def bound(ctx):
    c, p = ctx["counts"], ctx["peaks"]
    by_flops = c["swa_flops_per_step"] / ctx["chips"] / p["flops_per_s"]
    by_bytes = c["swa_bytes_per_step"] / ctx["chips"] / p["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("flops" if by_flops >= by_bytes
                                     else "bytes")


def read(ctx):
    if "swa_flops_per_step" not in ctx["counts"]:
        return None
    measured = swa_ms.read(ctx)
    if not measured:
        return None
    return 100.0 * bound(ctx)[0] / (measured * 1e-3)
