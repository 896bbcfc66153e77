"""The `lfm2_24b_a2b` configuration and the cell PR 35 added: its counts
against a hand count, the cell found by name, each new reader on a hand-made
trace, and the whole cell driven on the CPU at a tiny size in float32 against
its plain reference."""
import importlib
import json
import os
import time

import numpy as np
import pytest

import bench_fixtures as bf
from benchmark import harness, peaks, xtrace
from benchmark.configs import lfm2_24b_a2b
from paddle_tpu.observability import get_registry, scopes
from paddle_tpu.parallel import moe

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(os.path.join(bf.REPO, "benchmark", "configs",
                       "lfm2_24b_a2b.json")) as f:
    CFG = json.load(f)
with open(os.path.join(bf.REPO, "benchmark", "traffic", "train8k.json")) as f:
    TRAIN8K = json.load(f)
CELL = "lfm2_24b_a2b.train8k"

TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 48,
    "layer_types": ["conv", "full_attention", "conv"],
    "num_hidden_layers": 3, "num_dense_layers": 1, "num_experts": 4,
    "num_experts_published": 8, "experts_held": [2, 4],
    "num_experts_per_tok": 2, "vocab_size": 64, "initializer_range": 0.2,
    "amp_dtype": None, "reference": {"follow_steps": 3, "head_rows": 8},
    # the followed steps inside the warm-up, the window past it
    "optimizer": {"name": "adam", "learning_rate": 1e-3, "warmup_steps": 4,
                  "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8},
    # float32 against float32 on the CPU: rounding only
    "limits": {"loss_gap": 1e-4, "grad_gap": 1e-3, "update_gap": 1e-2},
}


def _reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------

def test_operations_per_token_against_a_hand_count():
    c = lfm2_24b_a2b.counts(CFG, TRAIN8K)
    fwd = c["fwd_flops_per_token"]
    # products a token (multiply-adds), by hand from the published widths
    conv = 2048 * 6144 + 2048 * 2048                       # 16.78M
    attn = 2048 * 3072 + 2048 * 2048                       # 10.49M
    dense = 3 * 2048 * 11776                               # 72.35M
    expert = 3 * 2048 * 1536                               # 9.44M
    assert fwd["conv"] == 2 * conv + 2 * 3 * 2048 + 2 * 2048
    assert fwd["attention"] == 2 * attn + 4 * 8192 * 2048 // 2
    assert fwd["dense_mlp"] == 2 * dense
    # 4 of 64 chosen, 8 held: half an expert a token and layer, with the
    # router's 64 outputs
    assert fwd["moe"] == 2 * 2048 * 64 + 2 * expert * 4 * 8 / 64
    assert fwd["lm_head"] == 2 * 2048 * 8192
    products = (5 * conv + 2 * attn + dense + 6 * (expert / 2 + 2048 * 64)
                + 2048 * 8192)
    assert products == pytest.approx(223e6, rel=5e-3)
    whole = (5 * fwd["conv"] + 2 * fwd["attention"] + fwd["dense_mlp"]
             + 6 * fwd["moe"] + fwd["lm_head"])
    assert c["flops_per_token"] == 3 * whole
    assert c["tokens_per_step"] == 16384
    # a step: about 25 TFLOP
    assert c["flops_per_token"] * 16384 == pytest.approx(25.2e12, rel=2e-2)
    # 8,192 pairs a layer on the held experts, 1,024 an expert; three
    # products a pair, forward and twice backward
    assert c["experts_pairs_per_step"] == 6 * 8192
    assert c["experts_pairs_per_step"] / 6 / 8 == 1024
    assert c["experts_flops_per_pair"] == 3 * 2 * expert
    assert c["pairs_routed_per_step"] == 6 * 16384 * 4
    assert c["moe_blocks"] == 6
    # w3 is among the bytes: three matrices an expert, bf16 twice and the
    # float32 gradient, and a pair's row four times
    assert c["experts_bytes_per_step"] == 6 * (
        8 * expert * (2 * 2 + 4) + 8192 * 2048 * 4 * 2)
    assert c["attn_flops_per_step"] == 3 * (4 * 8192 * 2048 // 2) * 16384 * 2
    assert c["attn_bytes_per_step"] == 4 * (2048 + 512) * 16384 * 2 * 2
    # the [tokens, 6144] tensor three times, the [tokens, 2048] twice
    assert c["conv_gates_bytes_per_step"] == (
        3 * 6144 + 2 * 2048) * 2 * 16384 * 5
    assert lfm2_24b_a2b.work_per_step(CFG, TRAIN8K) == 16384


def test_the_configuration_keeps_every_published_key():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    rows = [json.loads(line) for line in open(CATALOG)]
    row = next(r for r in rows if r["name"] == "LFM2-24B-A2B")
    published = row["config"]
    differs = {k for k, v in published.items() if CFG.get(k, "absent") != v}
    assert differs == set(CFG["reduced"])
    assert CFG["source"] == row["source_url"]
    for key in CFG["reduced"]:
        assert CFG[f"{key}_published"] == published[key]
    # published layers 1 to 7: the second dense layer, then six with experts
    assert CFG["layer_types"] == published["layer_types"][1:8]
    assert CFG["num_hidden_layers"] == len(CFG["layer_types"]) == 7
    assert CFG["experts_held"] == [0, CFG["num_experts"]]
    with open(os.path.join(bf.REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "lfm2_24b_a2b")
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == row["source_url"]
    assert set(CFG["assumed"]) >= {
        "tie_word_embeddings", "router", "rotary_embedding", "initializer",
        "optimizer", "precision", "input", "weights", "conv_operator"}


def test_parameters_and_memory_of_the_cut():
    from benchmark.configs import lfm2_24b_a2b_reference as ref
    from paddle_tpu.models import lfm2
    n = sum(int(np.prod(shape)) for _, shape, _ in ref.weight_specs(CFG))
    assert n == 647_819_904
    assert lfm2.param_count(lfm2_24b_a2b.model_config(CFG)) == n
    assert 16 * n / 2 ** 30 == pytest.approx(9.65, rel=2e-3)


# ---------------------------------------------------------------------------
# the cell is found
# ---------------------------------------------------------------------------

def test_load_cell_finds_the_new_cell():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.workload["traffic"] == "train8k"
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "step_ms", "setup_s"}
    assert cell.config["rate_metric"] == "tokens_per_s"
    has = {"conv_mixer_ms", "conv_gates_ms", "qk_norm_ms", "moe_ms",
           "moe_dispatch_ms", "experts_roofline", "expert_load_max",
           "pairs_held_share", "lm_head_ms", "rope_ms", "mlp_ms", "mfu",
           "attn_ms", "attn_roofline", "scope_coverage", "step_hbm"}
    lacks = {"head_ms", "rows_ms", "mamba_ms", "ssd_ms", "loop_ms",
             "exit_ms", "collective_ms"}
    assert has <= set(cell.readers) and not lacks & set(cell.readers)
    (batch,) = cell.generator.make_ring(cell.config,
                                        dict(cell.traffic, ring=1),
                                        2 ** 31 + 77)
    ids = batch["ids"]
    assert ids.shape == (2, 8192) and 0 <= ids.min() and ids.max() < 8192
    assert 0.09 < np.mean(ids == 0) < 0.12      # 1 / H(8192) = 10.4%


def test_the_cell_is_in_the_benchmark_on_one_chip():
    # no totals: the next cell must not have to touch this test
    with open(os.path.join(bf.REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2_24b_a2b", "train8k", 1)
    assert "first 38 s" in cell["why"] and len(cell["why"]) <= 200
    assert [w["name"] for w in spec["workloads"] if w["chips"] == 4] == [
        "ernie_base.dp4_seq512"]
    (config,) = [c for c in spec["configs"] if c["name"] == "lfm2_24b_a2b"]
    assert config["file"] == "benchmark/configs/lfm2_24b_a2b.json"
    metrics = {m["name"]: m for m in spec["per_layer"]}
    for name in ("conv_mixer_ms", "conv_gates_ms", "qk_norm_ms"):
        assert CELL in metrics[name]["workloads"]
        assert metrics[name]["moves"] == "step_ms"


def test_the_learning_rate_warms_up_linearly_to_its_peak():
    from benchmark.configs import lfm2_24b_a2b_reference as ref
    opt = CFG["optimizer"]
    assert opt["warmup_steps"] == 2000 and opt["learning_rate"] == 1e-4
    assert ref.learning_rate(opt, 1) == pytest.approx(5e-8)
    assert ref.learning_rate(opt, 60) == pytest.approx(3e-6)
    assert ref.learning_rate(opt, 2000) == ref.learning_rate(opt, 5000) == 1e-4
    assert ref.learning_rate({"learning_rate": 0.5}, 1) == 0.5


def test_the_weights_are_one_draw_and_the_seed_decides_the_batches():
    from benchmark.configs import lfm2_24b_a2b_reference as ref
    assert CFG["weights_seed"] == 0
    cfg = dict(CFG, **TINY)
    a, b = ref.make_weights(cfg, 2 ** 31 + 5), ref.make_weights(cfg, 7)
    assert a.keys() == b.keys()
    assert all(np.array_equal(a[k], b[k]) for k in a)
    del cfg["weights_seed"]
    c, d = ref.make_weights(cfg, 0), ref.make_weights(cfg, 7)
    assert all(np.array_equal(a[k], c[k]) for k in a)
    assert not np.array_equal(c["blk1.moe.gate"], d["blk1.moe.gate"])


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

UNITS = ["embed", "blk0/op_norm", "blk0/conv/in_proj", "blk0/conv/gate_in",
         "blk0/conv/filter", "blk0/conv/gate_out", "blk0/conv/out_proj",
         "blk0/mlp/gate_up", "blk1/attn/qkv", "blk1/attn/qk_norm",
         "blk1/attn/rope", "blk1/moe/router", "blk1/moe/experts",
         "blk1/moe/experts/blk1/moe/experts", "lm_head", None]
MS = [2 ** i for i in range(len(UNITS))]


def _ctx(units=UNITS, opcode="fusion"):
    """A traced step whose operation i ran `MS[i]` ms in units[i]."""
    found, events, at = {}, [], 0
    for i, (unit, ms) in enumerate(zip(units, MS)):
        name = f"{opcode}.{i}"
        text = f"%{name} = f32[8,{i + 1}] {opcode}(%x)"
        found[name] = scopes.OpScope(name=name, text=text, phase="fwd",
                                     unit=unit, op_types=("mul",),
                                     has_dot=True)
        dur = int(ms * 1e6)
        events.append([xtrace.label(text), "xla", at, dur])
        at += dur
    trace = xtrace.Reduced({"devices": {"/device:TPU:0": events},
                            "host": []}, 1)
    return {"trace": trace, "op_scopes": found, "chips": 1,
            "counts": lfm2_24b_a2b.counts(CFG, TRAIN8K),
            "peaks": peaks.peaks_for("TPU v5 lite")}


@pytest.mark.parametrize("name,expected", [
    ("conv_mixer_ms", 4 + 8 + 16 + 32 + 64), ("conv_gates_ms", 8 + 16 + 32),
    ("qk_norm_ms", 512), ("rope_ms", 1024), ("mlp_ms", 128),
    ("moe_ms", 2048 + 4096 + 8192), ("lm_head_ms", 16384)])
def test_unit_readers_sum_their_units(name, expected):
    assert _reader(name)(_ctx()) == pytest.approx(expected)


@pytest.mark.parametrize("name", ["conv_mixer_ms", "conv_gates_ms",
                                  "qk_norm_ms"])
def test_the_new_readers_count_a_loop_once_and_find_nothing_elsewhere(name):
    # a `while` is left out (its body's operations are in the trace)
    assert _reader(name)(_ctx(opcode="while")) is None
    # Nemotron's units (its convolution is `.../mamba/conv`) and a program
    # that writes no scopes give nothing, and nothing raises
    other = ["blk0.M/mamba/conv", "blk0.M/mamba/in_proj", "blk5.A/attn"]
    assert _reader(name)(_ctx(other + [None] * 13)) is None
    ctx = _ctx()
    ctx["op_scopes"] = {}
    assert _reader(name)(ctx) is None


def test_experts_roofline_counts_three_products_a_held_pair():
    ctx = _ctx()
    ctx["registry_series"] = (
        [{"name": "moe/pairs_held", "labels": {"block": f"blk{i}"},
          "value": 4096} for i in range(1, 7)]
        + [{"name": "moe/pairs_routed", "labels": {"block": f"blk{i}"},
            "value": 65536} for i in range(1, 7)])
    c, p = ctx["counts"], ctx["peaks"]
    least = max(6 * 4096 * 3 * 2 * 3 * 2048 * 1536 / p["flops_per_s"],
                c["experts_bytes_per_step"] / p["hbm_bytes_per_s"])
    assert _reader("experts_roofline")(ctx) == pytest.approx(
        100 * least / ((4096 + 8192) * 1e-3))
    assert _reader("pairs_held_share")(ctx) == pytest.approx(6.25)


# ---------------------------------------------------------------------------
# the whole cell on the CPU, tiny, float32
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = bf.make_tree(tmp_path_factory.mktemp("bench_lfm2"))
    bench = os.path.join(root, "benchmark")
    cfg = dict(CFG, **TINY, name="tiny_lfm2")
    with open(os.path.join(bench, "configs", "tiny_lfm2.json"), "w") as f:
        json.dump(cfg, f)
    for suffix in ("", "_reference"):
        with open(os.path.join(bench, "configs",
                               f"tiny_lfm2{suffix}.py"), "w") as f:
            f.write(f"from benchmark.configs.lfm2_24b_a2b{suffix} "
                    f"import *  # noqa: F401,F403\n")
    with open(os.path.join(bench, "traffic", "tiny_lm8k.json"), "w") as f:
        json.dump(dict(TRAIN8K, name="tiny_lm8k", batch=2, seq_len=32, ring=4,
                       warmup_blocks=2, trace_blocks=2), f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny_lfm2", "source": "test", "reduced": [],
        "file": "benchmark/configs/tiny_lfm2.json", "why": "test"})
    spec["workloads"].append({
        "name": "tiny_lfm2.tiny_lm8k", "config": "tiny_lfm2",
        "traffic": "tiny_lm8k", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_lfm2.tiny_lm8k")
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


def _run(tree, build=None):
    cell = harness.load_cell("tiny_lfm2.tiny_lm8k", tree)
    lines = []
    result = harness.run_cell(
        cell, 2 ** 31 + 35, 0.3, False, time.perf_counter(), build=build,
        device=dict(bf.FAKE_DEVICE),
        say=lambda *a, **k: lines.append(" ".join(map(str, a))))
    return cell, result, lines


def test_the_tiny_cell_agrees_with_its_plain_reference(tree):
    cell, result, lines = _run(tree)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"tokens_per_s", "step_ms", "setup_s"}
    # the counters the step fetched with its loss are in the registry
    ctx = {}
    assert 0 < _reader("pairs_held_share")(ctx) < 100
    assert _reader("expert_load_max")(ctx) >= 1.0
    blocks = {s["labels"]["block"] for s in get_registry().series()
              if s["name"] == "moe/pairs_held"}
    assert {"blk1", "blk2"} <= blocks
    dropped = [s["value"] for s in get_registry().series()
               if s["name"] == "moe/dropped"]
    assert dropped and not any(dropped)


def test_the_program_s_rate_a_step_is_the_reference_s(tree):
    from benchmark.configs import lfm2_24b_a2b_reference as ref
    cell = harness.load_cell("tiny_lfm2.tiny_lm8k", tree)
    system = cell.adapter.build(cell.config, cell.traffic, 1)
    (batch,) = cell.generator.make_ring(cell.config,
                                        dict(cell.traffic, ring=1), 7)
    system.start(cell.reference.make_weights(cell.config, 7))
    (rate,) = [v for v in system.main.list_vars()
               if v.name.startswith("lr_warmup")]
    for t in range(1, 7):           # four steps of warm-up, then the peak
        system.step(batch)
        got = float(np.asarray(system.scope.find_var(rate.name)).reshape(()))
        assert got == pytest.approx(
            ref.learning_rate(cell.config["optimizer"], t), rel=1e-6), t


def test_the_step_names_every_part_the_unit_readers_read(tree):
    cell = harness.load_cell("tiny_lfm2.tiny_lm8k", tree)
    system = cell.adapter.build(cell.config, cell.traffic, 1)
    (batch,) = cell.generator.make_ring(cell.config,
                                        dict(cell.traffic, ring=1), 7)
    system.start(cell.reference.make_weights(cell.config, 7))
    system.step(batch)
    found = scopes.op_scopes(system.exe.compiled_step(system.main))
    units = {s.unit for s in found.values() if s.unit}
    for part in ("/conv/in_proj", "/conv/gate_in", "/conv/filter",
                 "/conv/gate_out", "/conv/out_proj", "/attn/qkv",
                 "/attn/qk_norm", "/attn/rope", "/attn/kernel", "/attn/o",
                 "/mlp/gate_up", "/mlp/down", "/moe/router", "/moe/dispatch",
                 "/moe/experts"):
        assert any(part in u for u in units), (part, sorted(units))
    assert {"lm_head", "loss", "embed", "final_norm"} <= units
    assert system.hbm()["argument_bytes"] > 0


def test_a_cell_with_w3_left_out_is_not_correct(tree, monkeypatch):
    """The timed path with the gate's second product left out of every
    expert tile (a plain expert under a gated model's name): part of the
    mathematics left out."""
    tile = moe._expert_tile

    def plain(xt, w1e, b1e, w2e, b2e, wgt, w3e, act):
        return tile(xt, w1e, b1e, w2e, b2e, wgt, None, act)

    monkeypatch.setattr(moe, "_expert_tile", plain)
    _, result, lines = _run(tree)
    assert result["correct"] is False, lines
