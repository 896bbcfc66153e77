"""Helpers for the benchmark's tests: a copy of the benchmark tree in a
temporary root, with tiny CPU-sized configurations, mixes and a layer metric
ADDED AS FILES ONLY (which is also the test that the harness finds them)."""
from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FAKE_DEVICE = {"platform": "cpu-under-test", "kind": "TPU v5 lite", "count": 1}

TINY_ERNIE = {
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "intermediate_size": 128, "vocab_size": 128,
    "max_position_embeddings": 32, "amp_dtype": None,
    # parity on the CPU is of the mathematics: no dropout, so that the system
    # and the reference follow the same steps to rounding
    "hidden_dropout_prob": 0.0, "attention_probs_dropout_prob": 0.0,
    "reference": {"tokens_per_block": 64, "follow_steps": 3},
    # float32 against float32 on the CPU: rounding only
    "limits": {"loss_gap": 1e-4, "grad_gap": 1e-3, "update_gap": 1e-2},
}


TINY_DEEPFM = {
    "table_rows": 4096, "hidden_sizes": [32, 32, 32],
    "field_cardinalities": [50, 20, 3000, 700, 30, 24, 100, 60, 3, 500, 80,
                            2000, 90, 27, 120, 900, 10, 70, 40, 4, 1500, 18,
                            15, 300, 105, 200],
    "limits": {"loss_gap": 1e-5, "grad_gap": 1e-3, "update_gap": 1e-3},
}


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def make_tree(tmp_path, chips: int = 1) -> str:
    """A root holding BENCHMARK.json and benchmark/, the real files copied
    and the tiny ones added. Returns the root."""
    root = str(tmp_path)
    bench = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(REPO, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)

    with open(os.path.join(bench, "configs", "ernie_base.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY_ERNIE, name="tiny_ernie")
    _write(os.path.join(bench, "configs", "tiny_ernie.json"), json.dumps(cfg))
    _write(os.path.join(bench, "configs", "tiny_ernie.py"),
           "from benchmark.configs.ernie_base import *  # noqa: F401,F403\n")
    _write(os.path.join(bench, "configs", "tiny_ernie_reference.py"),
           "from benchmark.configs.ernie_base_reference import *  # noqa\n")
    mix = {"name": "tiny_seq", "generator": "mlm_full",
           "generator_params": {"mlm_share": 0.15},
           "batch": 8, "seq_len": 16,
           "layout": "single" if chips == 1 else "data_parallel",
           "ring": 4, "steps_per_block": 2, "warmup_blocks": 2,
           "trace_blocks": 2}
    _write(os.path.join(bench, "traffic", "tiny_seq.json"), json.dumps(mix))
    with open(os.path.join(bench, "configs", "deepfm_criteo.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY_DEEPFM, name="tiny_deepfm")
    _write(os.path.join(bench, "configs", "tiny_deepfm.json"), json.dumps(cfg))
    _write(os.path.join(bench, "configs", "tiny_deepfm.py"),
           "from benchmark.configs.deepfm_criteo import *  # noqa: F401,F403\n")
    _write(os.path.join(bench, "configs", "tiny_deepfm_reference.py"),
           "from benchmark.configs.deepfm_criteo_reference import *  # noqa\n")
    _write(os.path.join(bench, "traffic", "tiny_fields.json"), json.dumps({
        "name": "tiny_fields", "generator": "criteo_fields",
        "generator_params": {"zipf_exponent": 1.05, "label_rate": 0.25},
        "batch": 64, "layout": "single", "ring": 8, "steps_per_block": 4,
        "warmup_blocks": 2, "trace_blocks": 2}))
    _write(os.path.join(bench, "layer_metrics", "readings_count.py"),
           "def read(ctx):\n    return len(ctx['readings_s'])\n")

    spec["configs"].append({
        "name": "tiny_ernie", "source": "test", "reduced": [],
        "file": "benchmark/configs/tiny_ernie.json", "why": "test"})
    spec["workloads"].append({
        "name": "tiny_ernie.tiny_seq", "config": "tiny_ernie",
        "traffic": "tiny_seq", "chips": chips, "why": "test"})
    spec["configs"].append({
        "name": "tiny_deepfm", "source": "test", "reduced": [],
        "file": "benchmark/configs/tiny_deepfm.json", "why": "test"})
    spec["workloads"].append({
        "name": "tiny_deepfm.tiny_fields", "config": "tiny_deepfm",
        "traffic": "tiny_fields", "chips": 1, "why": "test"})
    # a four-chip cell on a mix and with readers that are there: entries only
    spec["workloads"].append({
        "name": "ernie_base.dp4_seq512", "config": "ernie_base",
        "traffic": "dp4_seq512", "chips": 4, "why": "test"})
    for name in ("collective_ms", "collective_exposed_ms"):
        spec["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "collectives",
            "moves": "step_ms", "workloads": ["ernie_base.dp4_seq512"]})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m and "ernie_base.seq512" in m["workloads"]:
            m["workloads"].append("ernie_base.dp4_seq512")
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m and "deepfm_criteo.fields" in m["workloads"]:
            m["workloads"].append("tiny_deepfm.tiny_fields")
        if "workloads" in m and "ernie_base.seq512" in m["workloads"]:
            m["workloads"].append("tiny_ernie.tiny_seq")
    spec["per_layer"].append({
        "name": "readings_count", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "entry", "moves": "step_ms",
        "workloads": ["tiny_ernie.tiny_seq"]})
    _write(os.path.join(root, "BENCHMARK.json"), json.dumps(spec))
    return root
