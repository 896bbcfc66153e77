"""The mean entropy of the exit distribution, in nats, of the last step read
into the program's registry (`loop/exit_entropy`, set by
`models.ouro.record_loop_counters` where the loss is fetched): at most
ln(passes); a gate that collapses onto one exit reads 0. Nothing where the
program sets no such gauge."""
from benchmark.layer_metrics import pairs_held_share


def read(ctx):
    values = [s["value"] for s in pairs_held_share.series(ctx)
              if s["name"] == "loop/exit_entropy"]
    return values[-1] if values else None
