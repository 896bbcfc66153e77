#!/usr/bin/env python3
"""Read, on the chip and at the cell's own size, the numbers the limits in a
configuration's file are set from: for a dozen seeds the gaps between the
program's first steps and the plain reference, and for a few seeds the gaps
between the control (the reference in the nearest lower precision) and the
reference. One process, one compiled step; no measured window.

    python3 benchmark/check_readings.py --workload <name> --seeds 12 \
        --control-seeds 3 [--first-seed 1000] [--out chiprun_out/x.json]

No benchmark run calls this; PERF.md section 2 holds what it read.
"""
import argparse
import gc
import json
import os
import sys
import time

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_000)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from benchmark import check, harness

    cell = harness.load_cell(args.workload)
    cfg, traffic = cell.config, cell.traffic
    devices = jax.devices()[:cell.chips]
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"check_readings: needs {cell.chips} TPU chip(s), found "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 4
    no_limits = {"loss_gap": float("inf"), "grad_gap": float("inf"),
                 "update_gap": float("inf")}
    steps = int(cfg["reference"]["follow_steps"])
    system = None
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        head = cell.generator.make_ring(cfg, traffic, seed)[:steps]
        t0 = time.perf_counter()
        weights = cell.reference.make_weights(cfg, seed, head, devices)
        expected = cell.reference.follow(cfg, weights, head, devices,
                                         seed=seed)
        ref_s = time.perf_counter() - t0
        row = {"seed": seed, "reference_s": ref_s}
        if i < args.control_seeds:
            t0 = time.perf_counter()
            lower = cell.reference.follow(cfg, weights, head, devices,
                                          control=True, seed=seed)
            row["control_s"] = time.perf_counter() - t0
            row["control"] = {n["name"]: [n["value"], n["at"]] for n in
                              check.compare(lower, expected,
                                            no_limits)["numbers"]}
        del weights
        # a fresh system a seed: the executor keeps the last state alive, and
        # two states do not fit beside an ERNIE step
        del system
        gc.collect()
        system = cell.adapter.build(cfg, traffic, cell.chips)
        system.start(cell.reference.make_weights(cfg, seed, head, devices))
        got = harness.first_steps(cell, system, head, seed, devices)
        row["program"] = {n["name"]: [n["value"], n["at"]] for n in
                          check.compare(got, expected, no_limits)["numbers"]}
        row["losses"] = [got["losses"], expected["losses"]]
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for name in ("loss_gap", "grad_gap", "update_gap"):
        sound = [r["program"][name][0] for r in rows]
        ctrl = [r["control"][name][0] for r in rows if "control" in r]
        summary[name] = {"program_largest": max(sound),
                         "program_median": sorted(sound)[len(sound) // 2],
                         "control_smallest": min(ctrl) if ctrl else None}
    print("check_readings summary: " + json.dumps(summary), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows,
                       "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
