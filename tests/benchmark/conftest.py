"""`bench_fixtures.make_tree` (PR 23) adds the cell `ernie_base.dp4_seq512`
and its two collective metrics to its copy of BENCHMARK.json, as entries
only. PR 26 put those very entries into BENCHMARK.json itself, so the copy
would name them twice and `harness.load_cell` rightly refuses a name that
stands twice. A PR that adds to the benchmark edits none of its files: this
file, added beside them, keeps the first of each name in the copy. (A later
`benchmark` PR can take the additions out of bench_fixtures.py and this file
with them.)"""
import json
import os

import bench_fixtures

_make_tree = bench_fixtures.make_tree


def _first_of_each_name(rows):
    seen, kept = set(), []
    for row in rows:
        if row["name"] not in seen:
            seen.add(row["name"])
            kept.append(row)
    return kept


def make_tree(tmp_path, chips: int = 1) -> str:
    root = _make_tree(tmp_path, chips)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        spec[key] = _first_of_each_name(spec[key])
        for row in spec[key]:
            if "workloads" in row:
                row["workloads"] = list(dict.fromkeys(row["workloads"]))
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


bench_fixtures.make_tree = make_tree
