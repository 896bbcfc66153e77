"""Device milliseconds a step, per chip, in the state-space scan alone
(`ssd_scan`: unit paths holding `/mamba/ssd`)."""
from benchmark.layer_metrics import _unit_parts


def read(ctx):
    return _unit_parts.part_ms(ctx, "/mamba/ssd")
