"""Percent of the traced stretch in which no operation ran on the device:
1 - union of device-operation intervals over the window, averaged over chips."""


def read(ctx):
    return 100.0 * ctx["trace"].idle_share
