"""The arithmetic of the measured loop, kept apart from the loop so that tests
can feed it recorded block times.

A *block* dispatches `steps_per_block` steps and ends by fetching the last
step's loss; a *reading* is the block's wall seconds over its steps. The
end-to-end rate and `step_ms` are all the work over all the time of the
window: the steps of the finished blocks over the measured wall time from the
first block's start to the last block's end, host time between blocks
included. Nothing is divided by the nominal `--seconds` (the last block is
always finished and counted), so a run cannot gain or lose a step at the
window's edge; a stall, a late compile or a slow host moves the rate by its
share of the window. The median reading and `stall_share` stand beside them
as the steadier diagnostics, `step_ms_p90` is the tail of the readings."""
from __future__ import annotations

import statistics
from typing import Sequence


def p90(readings: Sequence[float]) -> float:
    """The 90th percentile, interpolated between order statistics."""
    if len(readings) < 2:
        return float(readings[0])
    return statistics.quantiles(readings, n=10, method="inclusive")[-1]


def reduce_window(readings_s: Sequence[float], steps_per_block: int,
                  wall_s: float, work_per_step: float, chips: int) -> dict:
    """`readings_s`: seconds per step, one per finished block; `wall_s`: from
    the first block's start to the last block's end. Returns step_ms (wall
    over steps), the rate per chip-second (the steps' work over chips over
    wall), step_ms_p90 and the median reading."""
    if not readings_s:
        raise ValueError("no block was timed")
    steps = len(readings_s) * steps_per_block
    step_s = wall_s / steps
    return {
        "step_ms": step_s * 1e3,
        "rate_per_chip": work_per_step / chips / step_s,
        "step_ms_p90": p90(readings_s) * 1e3,
        "step_ms_median": statistics.median(readings_s) * 1e3,
        "readings": len(readings_s),
        "steps": steps,
    }


def stall_share(readings_s: Sequence[float], steps_per_block: int,
                wall_s: float) -> float:
    """Percent of the window that the median step does not explain:
    1 - (readings x median reading x steps per block) / wall. It is the
    distance between `step_ms` and the median reading, as a share."""
    med = statistics.median(readings_s)
    return 100.0 * (1.0 - len(readings_s) * med * steps_per_block / wall_s)
