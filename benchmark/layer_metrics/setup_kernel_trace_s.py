"""Seconds of set-up spent tracing Pallas kernels to their jaxprs, at every
call site, every time a step was traced: the sum of
`setup/kernel_trace_seconds{kernel}` (a part of `setup_trace_s`; 0 in a cell
whose step holds no kernel). Nothing from a program without the account."""
from benchmark.layer_metrics import _setup_account


def read(ctx):
    return _setup_account.total(ctx, "setup/kernel_trace_seconds")
