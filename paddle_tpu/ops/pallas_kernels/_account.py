"""`pl.pallas_call` on the set-up account's clock.

Tracing a Pallas kernel to its jaxpr is paid at every call site, every time
the step around it is traced (`_trace_kernel_to_jaxpr` has no cache). The
kernels of this package are bound through `kernel_call`, which times that
and nothing else: ``setup/kernel_trace_seconds{kernel}`` and
``setup/kernel_traces{kernel}`` (observability/setup_account.py). The
kernel's Mosaic lowering is not in it: it falls in the module's ``lower``.
"""
from jax.experimental import pallas as pl

from ...observability.setup_account import kernel_trace


def kernel_call(label: str, kernel, /, **kwargs):
    """``pl.pallas_call(kernel, **kwargs)`` whose bind is timed under
    `label` (the call's own ``name=`` goes through with the rest)."""
    call = pl.pallas_call(kernel, **kwargs)

    def bind(*args):
        with kernel_trace(label):
            return call(*args)

    return bind
