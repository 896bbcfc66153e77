"""The comparison that decides `correct` for a training cell.

The program's first steps (driven through the window's own call, on the
window's own compiled step and state) are held against the plain reference
that followed the same steps from the same seeded weights and batches:

  loss_gap     worst |loss_program - loss_reference| over the followed steps
  grad_gap     worst leaf of |norm_program - norm_reference| of the first
               gradient as the optimizer got it, read back from its state
               after step one
  update_gap   worst leaf of the same gap for the norm of the parameters'
               change over the followed steps

A leaf's gap is measured against the reference's norm of that leaf or of the
median leaf, whichever is larger (some gradients are all but zero). It is the
gap between two norms, not the norm of a difference: the program never has to
hold a second copy of its state for the check."""
from __future__ import annotations

import statistics
from typing import Dict, Sequence


def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float]):
    """(gap, leaf name) of the worst leaf. Both dicts hold one norm a leaf."""
    if set(program) != set(reference):
        raise ValueError(
            f"leaves differ: only in program {sorted(set(program) - set(reference))[:4]}, "
            f"only in reference {sorted(set(reference) - set(program))[:4]}")
    floor = statistics.median(reference.values())
    worst, name = -1.0, None
    for leaf, ref in reference.items():
        gap = abs(program[leaf] - ref) / max(ref, floor, 1e-30)
        if gap != gap:            # NaN: nothing is worse
            return float("inf"), leaf
        if gap > worst:
            worst, name = gap, leaf
    return worst, name


def compare(program: dict, reference: dict, limits: Dict[str, float]) -> dict:
    """`program` / `reference`: {"losses": [...], "grad_norms": {leaf: norm},
    "update_norms": {leaf: norm}}. Returns {"ok": bool, "numbers": [...]},
    each number with its limit, for the run to print."""
    steps = min(len(program["losses"]), len(reference["losses"]))
    loss_gaps = [abs(program["losses"][i] - reference["losses"][i])
                 for i in range(steps)]
    loss_gap = max(loss_gaps) if all(g == g for g in loss_gaps) else float("inf")
    grad_gap, grad_leaf = worst_leaf_gap(program["grad_norms"],
                                         reference["grad_norms"])
    upd_gap, upd_leaf = worst_leaf_gap(program["update_norms"],
                                       reference["update_norms"])
    numbers = [
        {"name": "loss_gap", "value": loss_gap, "limit": limits["loss_gap"],
         "at": f"steps 1..{steps}"},
        {"name": "grad_gap", "value": grad_gap, "limit": limits["grad_gap"],
         "at": grad_leaf},
        {"name": "update_gap", "value": upd_gap,
         "limit": limits["update_gap"], "at": upd_leaf},
    ]
    for n in numbers:
        n["ok"] = bool(n["value"] <= n["limit"])
    return {"ok": all(n["ok"] for n in numbers), "numbers": numbers}


def format_numbers(numbers: Sequence[dict]) -> str:
    return "; ".join(
        f"{n['name']} {n['value']:.3e} (limit {n['limit']:.1e}, at {n['at']})"
        f"{'' if n['ok'] else ' FAILED'}" for n in numbers)
