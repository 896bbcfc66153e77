"""GiB the compiled step needs on one chip by XLA's `memory_analysis()`:
arguments + temporaries + outputs - aliased. Never `memory_stats()`, which
counts live arrays only on this runtime."""


def read(ctx):
    h = ctx["hbm"]
    return (h["argument_bytes"] + h["temp_bytes"] + h["output_bytes"]
            - h["alias_bytes"]) / 2 ** 30
