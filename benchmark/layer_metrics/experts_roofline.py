"""The routed experts' grouped products' share of their roofline: the least
time the chip could take for them (the larger of their required operations
over the published peak and their required bytes over the published
bandwidth) over the measured time in `/moe/experts`. The operations are those
of the pairs the last read step really held (`moe/pairs_held` in the
program's registry) where the program reports them, else of the pairs
expected; padding of an expert's last tile and recomputation do not count."""
from benchmark.layer_metrics import _unit_parts, pairs_held_share


def read(ctx):
    c, p = ctx["counts"], ctx["peaks"]
    if "experts_flops_per_pair" not in c:
        return None
    measured = _unit_parts.part_ms(ctx, "/moe/experts")
    if not measured:
        return None
    held = pairs_held_share.pairs(ctx)
    pairs = held[0] if held else c["experts_pairs_per_step"]
    by_flops = pairs * c["experts_flops_per_pair"] / p["flops_per_s"]
    by_bytes = c["experts_bytes_per_step"] / p["hbm_bytes_per_s"]
    return 100.0 * max(by_flops, by_bytes) / ctx["chips"] / (measured * 1e-3)
