"""Model FLOP/s utilization: operations per token from the configuration's
shapes x tokens per chip-second (all the untraced window's work over its
wall time) over the published peak. Recomputed operations do not count."""


def read(ctx):
    c = ctx["counts"]
    if "flops_per_token" not in c:
        return None
    rate = ctx["values"][ctx["config"]["rate_metric"]]
    return 100.0 * c["flops_per_token"] * rate / ctx["peaks"]["flops_per_s"]
