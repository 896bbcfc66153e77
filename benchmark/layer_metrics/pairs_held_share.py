"""Percent of a step's (token, expert) pairs that fell on experts held here,
summed over the expert blocks: `moe/pairs_held` over `moe/pairs_routed` in the
program's registry, as the last step read into it left them. With 8 of 128
experts held and an even router it is 6.25. Nothing where the program sets no
such gauge (`ctx["registry_series"]` hands a test's series in)."""


def series(ctx):
    if "registry_series" in ctx:
        return ctx["registry_series"]
    try:
        from paddle_tpu.observability import get_registry
    except ImportError:
        return []
    return get_registry().series()


def pairs(ctx):
    """(pairs held, pairs routed) over the blocks, or None."""
    held = [s["value"] for s in series(ctx) if s["name"] == "moe/pairs_held"]
    routed = [s["value"] for s in series(ctx)
              if s["name"] == "moe/pairs_routed"]
    if not held or not routed or not sum(routed):
        return None
    return sum(held), sum(routed)


def read(ctx):
    found = pairs(ctx)
    return 100.0 * found[0] / found[1] if found else None
