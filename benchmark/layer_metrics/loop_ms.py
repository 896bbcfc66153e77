"""Device milliseconds a step, per chip, in the looped layers: unit paths
that start with an application `blk<i>.u<t>` (`models/ouro.py`: layer i in
pass t) — norms, projections, rotation, attention kernels and the gated MLP,
forward, recomputed forward and backward alike, every pass. Nothing where the
step has no such unit."""
import re

from benchmark.layer_metrics import _unit_parts

APPLICATION = re.compile(r"blk\d+\.u(\d+)(/|$)")


def pass_ms(ctx, t=None):
    """The applications of pass `t` (all passes: None)."""
    def pick(unit):
        m = APPLICATION.match(unit)
        return m is not None and (t is None or int(m.group(1)) == t)
    return _unit_parts.leaf_ms(ctx, pick)


def read(ctx):
    return pass_ms(ctx)
