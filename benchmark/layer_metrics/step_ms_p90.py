"""The 90th percentile block reading of the untraced window, in milliseconds
a step: the slow tenth of blocks. With four ERNIE steps a block a window
holds 25 readings and fewer than three lie beyond it, so it carries no
bound; a stall moves `step_ms` by its share of the window."""


def read(ctx):
    return ctx["values"]["step_ms_p90"]
