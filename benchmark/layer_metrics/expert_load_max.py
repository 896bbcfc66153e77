"""The fullest held expert's pairs over the mean held expert's, the worst
expert block of the last step read (`moe/tokens_per_expert` in the program's
registry): 1.0 is an even load; the grouped product's time follows the sum,
its tail tiles the spread. Nothing where the program sets no such gauge."""
from benchmark.layer_metrics import pairs_held_share


def read(ctx):
    by_block = {}
    for s in pairs_held_share.series(ctx):
        if s["name"] == "moe/tokens_per_expert":
            by_block.setdefault(s["labels"].get("block"), []).append(
                s["value"])
    ratios = [max(v) * len(v) / sum(v) for v in by_block.values() if sum(v)]
    return max(ratios) if ratios else None
