"""Device milliseconds a step spends on the packed embedding rows (DeepFM):
operations the trace names gather or scatter, and operations whose result is
an array of packed u16 rows (the table after the scatter of the touched rows,
and the rows gathered from it; nothing else in the step is u16). The sort and
merge of duplicate ids carry no such mark and are left in `xla_ms` alone.
Nothing where no operation matches."""


def read(ctx):
    sec = ctx["trace"].name_seconds_per_step(("gather", "scatter", " u16["))
    return None if sec is None else sec * 1e3
