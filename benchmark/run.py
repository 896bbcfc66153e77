#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Needs the TPU the cell asks for: it exits nonzero and prints no result line
where JAX finds another backend, fewer chips, or a device kind without
published peaks (benchmark/peaks.py), and where the program itself is not
there to import. The last line of stdout is the one JSON object the contract
fixes; see benchmark/README.md.
"""
import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the repo root takes the place of this file's directory on the path: the
# benchmark is the package `benchmark`, and its modules (xtrace, check, loop)
# must not shadow anything
sys.path[0] = ROOT


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    t_process = time.perf_counter()
    try:
        import jax

        from benchmark import harness
        from benchmark.peaks import UnknownDevice, peaks_for
        cell = harness.load_cell(args.workload)
    except ImportError as e:
        print(f"bench: cannot import the program or the benchmark: {e}",
              file=sys.stderr)
        return 3
    except (harness.BenchmarkError, OSError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"bench: needs a TPU: jax.devices()[0].platform is "
              f"{platform!r}; no result", file=sys.stderr)
        return 4
    try:
        peaks_for(devices[0].device_kind)
    except UnknownDevice as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 4
    if len(devices) < cell.chips:
        print(f"bench: workload {cell.name} needs {cell.chips} chips, "
              f"jax.devices() has {len(devices)}; no result", file=sys.stderr)
        return 4
    # set-up is counted from here: reaching the chip (9-16 s on the v5e
    # machines, and as uneven as that) is the machine's time, not the
    # program's, and would drown what a PR moves into set-up
    t_backend_up = time.perf_counter()
    print(f"bench: {cell.name} seed {args.seed} on {len(devices)} x "
          f"{devices[0].device_kind}, reached "
          f"{t_backend_up - t_process:.1f} s after the imports began (not "
          f"counted in setup_s); compile cache "
          f"{jax.config.jax_compilation_cache_dir}", flush=True)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t_backend_up,
                                  backend_s=t_backend_up - t_process)
    except Exception:
        traceback.print_exc()
        print("bench: the run failed; no result", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
