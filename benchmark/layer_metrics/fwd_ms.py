"""Device milliseconds a step, per chip, in operations of the forward pass:
those whose name scope (paddle_tpu.observability.scopes, written by the
lowering as `u.<unit>/op.<op type>`) is neither under `opt/` nor marked
`transpose(jvp(...))` or `autodiff` by the backward walk. Mosaic calls
included. Nothing from a program without the scopes.

A kernel belongs to one phase, and XLA decides what a kernel holds: forward
work fused into a backward kernel counts as `bwd`, an update fused with
anything as `mixed`. A change in what XLA fuses therefore moves time between
`fwd_ms`, `bwd_ms` and `mixed` with no change in the work done: read the
three together (`scope_coverage` gives what is left of the busy time), and
judge a change by `fwd_ms + bwd_ms + mixed`, not by one of them."""
from benchmark import scope_join


def read(ctx):
    return scope_join.phase_ms(ctx, "fwd")
