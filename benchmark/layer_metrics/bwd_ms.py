"""Device milliseconds a step, per chip, in operations of the backward pass:
what jax marks `transpose(jvp(u.<unit>/op.<op type>))` and what runs under
the `autodiff` walk (custom gradients, cotangent sums), recomputed or
deferred forward work fused into those kernels included: that is when its
time is spent. Mosaic calls included. Nothing from a program without the
scopes.

Read it together with `fwd_ms` and `mixed` (see fwd_ms.py): what XLA fuses
into which kernel moves time between the three with no change in the work
done."""
from benchmark import scope_join


def read(ctx):
    return scope_join.phase_ms(ctx, "bwd")
