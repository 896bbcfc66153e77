"""The median block reading of the untraced window, in milliseconds a step:
the steady part of `step_ms`, which a stall or a slow block does not move.
`step_ms` less this is what `stall_share` gives as a share."""


def read(ctx):
    return ctx["step_ms_median"]
