"""Percent of the scores the windowed forward kernel's scheduled tiles
compute that the window needs: `flash_attention/scores_visible` over
`flash_attention/scores_scheduled` in the program's registry, the gauges
labelled `kernel="fwd", call="window"` (set when the last windowed blocked
call was traced: static numbers of its shape and blocks). A quarter at
blocks of 1,024 under a window of 512 at T 8,192, a half at 512, two thirds
at 256. Nothing where the program sets no such gauge."""
from benchmark.layer_metrics import pairs_held_share


def read(ctx):
    found = {}
    for s in pairs_held_share.series(ctx):
        labels = s.get("labels", {})
        if (labels.get("kernel") == "fwd" and labels.get("call") == "window"
                and s["name"].startswith("flash_attention/scores_")):
            found[s["name"].split("/")[1]] = s["value"]
    if not found.get("scores_scheduled") or "scores_visible" not in found:
        return None
    return 100.0 * found["scores_visible"] / found["scores_scheduled"]
