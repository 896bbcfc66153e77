"""Percent of `setup_s` the program's own account covers:
`setup/import_seconds` and every phase of `setup/seconds` (`foreign` and
`executable` left out) over `setup_s`. The rest is what only the caller
sees: the seeded inputs, the model-building code, the seeded weights, the
steady steps of the check and the warm-up, the read-backs. Where the cell's
adapter imports the program before the backend is up, the import lies before
`setup_s` begins and is counted all the same. Nothing from a program without
the account."""
from benchmark.layer_metrics import _setup_account


def read(ctx):
    staged = _setup_account.seconds(ctx)
    if staged is None:
        return None
    imported = _setup_account.total(ctx, "setup/import_seconds")
    return 100.0 * (imported + staged) / ctx["values"]["setup_s"]
