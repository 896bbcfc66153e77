"""Seconds from the process's start to the backend's being up (`import jax`
and the first `jax.devices()`): the part of a user's time to the first step
that lies before `setup_s`'s clock starts. It is the machine's and the TPU
runtime's time, 9-16 s on one chip and uneven, which is why `setup_s` leaves
it out; it is reported here so that the sum stays visible."""


def read(ctx):
    return ctx["backend_s"]
