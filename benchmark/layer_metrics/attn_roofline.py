"""The attention kernels' share of their roofline: the least time the chip
could take for a step's attention (the larger of its operations over the
published peak and its bytes over the published bandwidth, both from the
shapes, per chip) over the measured `attn_ms`. At these shapes the operations
bound it (T=512) or the bytes do (T=128); `bound_by` says which."""


def bound(ctx):
    c, p = ctx["counts"], ctx["peaks"]
    by_flops = c["attn_flops_per_step"] / ctx["chips"] / p["flops_per_s"]
    by_bytes = c["attn_bytes_per_step"] / ctx["chips"] / p["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("flops" if by_flops >= by_bytes
                                     else "bytes")


def read(ctx):
    if "attn_flops_per_step" not in ctx["counts"]:
        return None
    measured = ctx["trace"].kind_seconds_per_step("mosaic")
    if measured <= 0:
        return None
    least, _ = bound(ctx)
    return 100.0 * least / measured
