"""The slowest pass's device time over the fastest's, forward and backward
together (`loop_ms` by the pass `t` of its units `blk<i>.u<t>`): 1.0 says the
passes over the shared weights cost alike; more says where the sum of a
weight's partial gradients, its cast or a fused optimizer update landed.
Nothing where the step has no such units or the configuration no passes."""
from benchmark.layer_metrics import loop_ms


def read(ctx):
    passes = int(ctx["config"].get("total_ut_steps", 0))
    by_pass = [loop_ms.pass_ms(ctx, t) for t in range(1, passes + 1)]
    if not by_pass or any(ms is None for ms in by_pass):
        return None
    return max(by_pass) / min(by_pass)
