"""Percent of the untraced window that the median step does not explain:
stalls, slow blocks and host time between blocks, all of which `step_ms` and
the rate carry."""
from benchmark import loop


def read(ctx):
    return loop.stall_share(ctx["readings_s"], ctx["steps_per_block"],
                            ctx["wall_s"])
