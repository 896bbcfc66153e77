"""The most negative cumulative log-decay, in nats, that any chunk of any KDA
layer reached in the last step read into the program's registry
(`kda/decay_floor`, set by `models.kimi_linear.record_counters` where the
loss is fetched): how far the rule's sub-block scheme is from float32's range
(exp(-88) is float32's end; the scheme holds beyond it), and whether training
drives the gates to forget everything inside a chunk. Higher (nearer 0) is a
longer memory. Nothing where the program sets no such gauge."""
from benchmark.layer_metrics import pairs_held_share


def read(ctx):
    values = [s["value"] for s in pairs_held_share.series(ctx)
              if s["name"] == "kda/decay_floor"]
    return values[-1] if values else None
