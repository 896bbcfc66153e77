"""The delta rule's share of its roofline: the least time the chip could take
for a step's rules (the larger of their required operations over the
published peak and their required bytes over the published bandwidth, both
from the shapes and the chunk: `counts` of the configuration, the same
whatever implements the rule; the forward pass made again is not required
work) over the measured `kda_rule_ms`. The rule is XLA-compiled einsums today
(no kernel): the share says how far that form stands from the chip."""
from benchmark.layer_metrics import kda_rule_ms


def bound(ctx):
    c, p = ctx["counts"], ctx["peaks"]
    by_flops = c["kda_flops_per_step"] / ctx["chips"] / p["flops_per_s"]
    by_bytes = c["kda_bytes_per_step"] / ctx["chips"] / p["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("flops" if by_flops >= by_bytes
                                     else "bytes")


def read(ctx):
    if "kda_flops_per_step" not in ctx["counts"]:
        return None
    measured = kda_rule_ms.read(ctx)
    if not measured:
        return None
    return 100.0 * bound(ctx)[0] / (measured * 1e-3)
