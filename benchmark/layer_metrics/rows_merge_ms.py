"""Device milliseconds a step in the sort and merge of duplicate ids
(`uniq_merge` of ops/deferred_rows.py): operations whose unit path is
`rows/merge`. The gather and the scatter of the rows are `rows_ms`'s.
Nothing where the step has no such unit."""
from benchmark import scope_join


def read(ctx):
    return scope_join.unit_ms(ctx, ("rows/merge",))
