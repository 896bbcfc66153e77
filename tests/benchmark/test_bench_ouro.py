"""The `ouro_2_6b` configuration and the cells PR 30 added: its counts against
a hand count, both new cells found by name, each new reader on a hand-made
trace of two passes of unequal length, and the whole cell driven on the CPU
at a tiny size in float32 against its plain reference."""
import importlib
import json
import math
import os
import time

import numpy as np
import pytest

import bench_fixtures as bf
from benchmark import harness, peaks, xtrace
from benchmark.configs import ouro_2_6b
from paddle_tpu.observability import get_registry, scopes

with open(os.path.join(bf.REPO, "benchmark", "configs",
                       "ouro_2_6b.json")) as f:
    CFG = json.load(f)
with open(os.path.join(bf.REPO, "benchmark", "traffic", "train4k.json")) as f:
    TRAIN4K = json.load(f)

TINY = {
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 96, "vocab_size": 128,
    "num_hidden_layers": 2, "total_ut_steps": 3, "initializer_range": 0.1,
    "amp_dtype": None, "reference": {"follow_steps": 3, "head_rows": 16},
    # float32 against float32 on the CPU: rounding only
    "limits": {"loss_gap": 1e-4, "grad_gap": 1e-3, "update_gap": 1e-2},
}


def _reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------

def test_operations_per_token_against_a_hand_count():
    c = ouro_2_6b.counts(CFG, TRAIN4K)
    fwd = c["fwd_flops_per_token"]
    # a layer's matrices: q, k, v, o 4 x 2048^2 and gate, up, down
    # 3 x 2048 x 5632 = 51.38M weights, two operations each, 8 layers four
    # times over
    assert fwd["layers"] == 32 * 2 * (4 * 2048 ** 2 + 3 * 2048 * 5632)
    assert fwd["layers"] == pytest.approx(3.288e9, rel=1e-3)
    # QK^T and PV over the causal half of 4,096 positions, 16 heads of 128
    assert fwd["attention"] == 32 * (4 * 4096 * 2048 // 2)
    assert fwd["attention"] == pytest.approx(0.537e9, rel=1e-3)
    # the untied head once an exit
    assert fwd["lm_head"] == 4 * 2 * 2048 * 49152
    assert fwd["lm_head"] == pytest.approx(0.805e9, rel=1e-3)
    assert c["flops_per_token"] == 3 * sum(fwd.values())
    assert c["flops_per_token"] == pytest.approx(13.9e9, rel=2e-3)
    assert c["tokens_per_step"] == 8192 == ouro_2_6b.work_per_step(
        CFG, TRAIN4K)
    assert c["flops_per_token"] * 8192 == pytest.approx(113.8e12, rel=2e-3)
    assert c["applications"] == 32
    # the kernels: forward and twice that backward; Q, K, V, O and their
    # gradients once each in bf16
    assert c["attn_flops_per_step"] == 3 * 4 * 4096 * 2048 // 2 * 8192 * 32
    assert c["attn_bytes_per_step"] == 8 * 2048 * 8192 * 32 * 2
    # the head is a sixth of the operations here, a thirtieth at 48 layers
    assert fwd["lm_head"] / sum(fwd.values()) == pytest.approx(0.174, abs=2e-3)
    deployed = ouro_2_6b.counts(dict(CFG, num_hidden_layers=48), TRAIN4K)
    share = deployed["fwd_flops_per_token"]["lm_head"] / sum(
        deployed["fwd_flops_per_token"].values())
    assert share == pytest.approx(1 / 30, abs=3e-3)


def test_the_configuration_keeps_every_published_key():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    rows = [json.loads(line) for line in open(catalog)]
    published = next(r["config"] for r in rows if r["name"] == "Ouro-2.6B")
    differs = {k for k, v in published.items() if CFG.get(k, "absent") != v}
    assert differs == set(CFG["reduced"])
    assert CFG["num_hidden_layers_published"] == \
        published["num_hidden_layers"] == 48
    assert CFG["layer_types"] == \
        published["layer_types"][:CFG["num_hidden_layers"]]
    with open(os.path.join(bf.REPO, "BENCHMARK.json")) as f:
        row = next(c for c in json.load(f)["configs"]
                   if c["name"] == "ouro_2_6b")
    assert row["reduced"] == CFG["reduced"]
    assert set(CFG["assumed"]) >= {"norms", "rotary_embedding", "exit_gate",
                                   "loss", "sequence_length", "optimizer",
                                   "initializer"}


def test_parameters_and_memory_of_the_cut():
    from benchmark.configs import ouro_2_6b_reference as ref
    from paddle_tpu.models import ouro
    n = sum(int(np.prod(shape)) for _, shape, _ in ref.weight_specs(CFG))
    assert n == 8 * 51_388_416 + 2 * 49152 * 2048 + 2048 + 2049 == 612_438_017
    assert n == ouro.param_count(ouro_2_6b.model_config(CFG))
    assert 16 * n / 2 ** 30 == pytest.approx(9.13, abs=5e-3)
    whole = ouro.param_count(ouro_2_6b.model_config(
        dict(CFG, num_hidden_layers=48)))
    assert whole == pytest.approx(2.668e9, rel=1e-3)


# ---------------------------------------------------------------------------
# the cells are found
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,rate,has,lacks", [
    ("ouro_2_6b.train4k", "tokens_per_s",
     {"loop_ms", "loop_pass_ratio", "rope_ms", "mlp_ms", "exit_ms",
      "exit_entropy", "lm_head_ms", "mfu", "attn_ms", "attn_roofline",
      "scope_coverage", "step_hbm"},
     {"head_ms", "rows_ms", "mamba_ms", "moe_ms", "collective_ms"}),
    ("deepfm_criteo.uniform", "examples_per_s",
     {"rows_ms", "rows_merge_ms", "scope_coverage", "step_hbm"},
     {"attn_ms", "mfu", "loop_ms", "lm_head_ms"})])
def test_load_cell_finds_the_new_cells(name, rate, has, lacks):
    cell = harness.load_cell(name)
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {rate, "step_ms",
                                                    "setup_s"}
    assert cell.config["rate_metric"] == rate
    assert has <= set(cell.readers) and not lacks & set(cell.readers)
    ring = cell.generator.make_ring(cell.config, dict(cell.traffic, ring=2),
                                    2 ** 31 + 77)
    assert len(ring) == 2


def test_the_benchmark_has_seven_cells_and_one_on_four_chips():
    with open(os.path.join(bf.REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert len(spec["workloads"]) == 7
    assert [w["name"] for w in spec["workloads"] if w["chips"] == 4] == [
        "ernie_base.dp4_seq512"]
    assert [w["name"] for w in spec["workloads"][-2:]] == [
        "ouro_2_6b.train4k", "deepfm_criteo.uniform"]


def test_train4k_batches():
    cell = harness.load_cell("ouro_2_6b.train4k")
    (batch,) = cell.generator.make_ring(cell.config,
                                        dict(cell.traffic, ring=1),
                                        2 ** 31 + 5)
    ids, labels = batch["ids"], batch["labels"]
    assert ids.shape == (2, 4096) and labels.shape == (2, 4096, 1)
    assert 0 <= ids.min() and ids.max() < 49152
    assert np.array_equal(labels[:, :-1, 0], ids[:, 1:])
    # the whole vocabulary under a Zipf law: the last eighth is drawn too
    assert np.mean(ids >= 49152 - 6144) > 0


def test_uniform_ids_are_nearly_all_distinct():
    cell = harness.load_cell("deepfm_criteo.uniform")
    (batch,) = cell.generator.make_ring(cell.config,
                                        dict(cell.traffic, ring=1),
                                        2 ** 31 + 5)
    ids = next(v for v in batch.values()
               if np.asarray(v).shape == (4096, 26))
    assert len(np.unique(ids)) > 0.99 * ids.size


# ---------------------------------------------------------------------------
# the readers: two passes of unequal length
# ---------------------------------------------------------------------------

UNITS = ["embed",
         "blk0.u1/norm1", "blk0.u1/attn/qkv", "blk0.u1/attn/rope",
         "blk0.u1/attn/kernel", "blk0.u1/mlp/gate_up", "blk0.u1/mlp/act",
         "blk0.u1/mlp/down", "final_norm.u1",
         "blk0.u2/attn/rope", "blk0.u2/mlp/gate_up", "blk0.u2/mlp/down",
         "final_norm.u2", "exit_gate", "lm_head", "loss", None]
MS = [2 ** i for i in range(len(UNITS))]


def _ms(*units):
    return sum(ms for unit, ms in zip(UNITS, MS) if unit in units)


def _ctx(passes=2):
    """A traced step whose operation i ran MS[i] ms in UNITS[i]."""
    found, events, at = {}, [], 0
    for i, (unit, ms) in enumerate(zip(UNITS, MS)):
        name = f"fusion.{i}"
        text = f"%{name} = f32[8,{i + 1}] fusion(%x)"
        found[name] = scopes.OpScope(name=name, text=text, phase="fwd",
                                     unit=unit, op_types=("mul",),
                                     has_dot=True)
        dur = int(ms * 1e6)
        events.append([xtrace.label(text), "xla", at, dur])
        at += dur
    trace = xtrace.Reduced({"devices": {"/device:TPU:0": events},
                            "host": []}, 1)
    return {"trace": trace, "op_scopes": found, "chips": 1,
            "config": dict(CFG, total_ut_steps=passes),
            "peaks": peaks.peaks_for("TPU v5 lite")}


PASS1 = [u for u in UNITS if u and u.startswith("blk0.u1")]
PASS2 = [u for u in UNITS if u and u.startswith("blk0.u2")]


@pytest.mark.parametrize("name,expected", [
    ("loop_ms", _ms(*PASS1, *PASS2)),
    ("loop_pass_ratio", _ms(*PASS2) / _ms(*PASS1)),
    ("rope_ms", _ms("blk0.u1/attn/rope", "blk0.u2/attn/rope")),
    ("mlp_ms", _ms(*[u for u in PASS1 + PASS2 if "/mlp/" in u])),
    ("exit_ms", _ms("exit_gate", "loss")),
    ("lm_head_ms", _ms("lm_head", "loss"))])
def test_unit_readers_sum_their_units(name, expected):
    assert _reader(name)(_ctx()) == pytest.approx(expected)


def test_the_pass_ratio_needs_every_pass_of_the_configuration():
    assert _reader("loop_pass_ratio")(_ctx(passes=3)) is None
    assert _reader("loop_pass_ratio")(_ctx(passes=0)) is None


def test_the_loop_readers_count_a_while_once():
    ctx = _ctx()
    text = "%while.9 = f32[8] while(%x)"
    ctx["op_scopes"]["while.9"] = scopes.OpScope(
        name="while.9", text=text, phase="bwd", unit="blk0.u2/mlp/down",
        op_types=("mul",), has_dot=True)
    ctx["trace"].devices["/device:TPU:0"].append(
        [xtrace.label(text), "xla", 0, int(7e6)])
    assert _reader("loop_ms")(ctx) == pytest.approx(_ms(*PASS1, *PASS2))


def test_exit_entropy_reads_the_last_gauge():
    assert _reader("exit_entropy")({"registry_series": []}) is None
    series = [{"name": "loop/exit_entropy", "type": "gauge", "labels": {},
               "value": 1.25},
              {"name": "loop/passes", "type": "gauge", "labels": {},
               "value": 4}]
    assert _reader("exit_entropy")({"registry_series": series}) == 1.25


@pytest.mark.parametrize("name", ["loop_ms", "loop_pass_ratio", "rope_ms",
                                  "mlp_ms", "exit_ms", "exit_entropy"])
def test_a_program_without_the_names_gives_nothing(name):
    """What the parent tree's cells give the new readers: other units, no
    `loop/*` gauge; nothing is read and nothing raises."""
    ctx = _ctx()
    ctx["op_scopes"] = {k: v._replace(unit="bert_layer_0")
                        for k, v in ctx["op_scopes"].items()}
    ctx["registry_series"] = []
    assert _reader(name)(ctx) is None
    ctx.pop("_scope_of", None)
    ctx["op_scopes"] = {}
    ctx["config"] = {}
    assert _reader(name)(ctx) is None


# ---------------------------------------------------------------------------
# the whole cell on the CPU, tiny, float32
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = bf.make_tree(tmp_path_factory.mktemp("bench_ouro"))
    bench = os.path.join(root, "benchmark")
    cfg = dict(CFG, **TINY, name="tiny_ouro")
    cfg["layer_types"] = cfg["layer_types"][:2]
    with open(os.path.join(bench, "configs", "tiny_ouro.json"), "w") as f:
        json.dump(cfg, f)
    for suffix in ("", "_reference"):
        with open(os.path.join(bench, "configs",
                               f"tiny_ouro{suffix}.py"), "w") as f:
            f.write(f"from benchmark.configs.ouro_2_6b{suffix} "
                    f"import *  # noqa: F401,F403\n")
    with open(os.path.join(bench, "traffic", "tiny_lm4k.json"), "w") as f:
        json.dump(dict(TRAIN4K, name="tiny_lm4k", batch=2, seq_len=32, ring=4,
                       warmup_blocks=2, trace_blocks=2), f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny_ouro", "source": "test", "reduced": [],
        "file": "benchmark/configs/tiny_ouro.json", "why": "test"})
    spec["workloads"].append({
        "name": "tiny_ouro.tiny_lm4k", "config": "tiny_ouro",
        "traffic": "tiny_lm4k", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "ouro_2_6b.train4k" in m.get("workloads", []):
            m["workloads"].append("tiny_ouro.tiny_lm4k")
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


def _run(tree, build=None):
    cell = harness.load_cell("tiny_ouro.tiny_lm4k", tree)
    lines = []
    result = harness.run_cell(
        cell, 2 ** 31 + 30, 0.3, False, time.perf_counter(), build=build,
        device=dict(bf.FAKE_DEVICE),
        say=lambda *a, **k: lines.append(" ".join(map(str, a))))
    return cell, result, lines


def test_the_tiny_cell_agrees_with_its_plain_reference(tree):
    cell, result, lines = _run(tree)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"tokens_per_s", "step_ms", "setup_s"}
    # the counters the step fetched with its loss are in the registry
    gauges = {(s["name"], s["labels"].get("pass")): s["value"]
              for s in get_registry().series()
              if s["name"].startswith("loop/")}
    assert gauges[("loop/passes", None)] == 3
    shares = [gauges[("loop/exit_share", str(t))] for t in (1, 2, 3)]
    assert sum(shares) == pytest.approx(1.0, abs=1e-6)
    assert 0 < _reader("exit_entropy")({}) <= math.log(3) + 1e-6


def test_the_step_names_every_part_the_unit_readers_read(tree):
    cell = harness.load_cell("tiny_ouro.tiny_lm4k", tree)
    system = cell.adapter.build(cell.config, cell.traffic, 1)
    (batch,) = cell.generator.make_ring(cell.config,
                                        dict(cell.traffic, ring=1), 7)
    system.start(cell.reference.make_weights(cell.config, 7))
    system.step(batch)
    found = scopes.op_scopes(system.exe.compiled_step(system.main))
    units = {s.unit for s in found.values() if s.unit}
    for t in (1, 2, 3):
        for i in (0, 1):
            for part in ("/attn/qkv", "/attn/rope", "/attn/kernel", "/attn/o",
                         "/mlp/gate_up", "/mlp/act", "/mlp/down"):
                assert any(u.startswith(f"blk{i}.u{t}") and part in u
                           for u in units), (i, t, part, sorted(units))
    assert {"embed", "exit_gate", "lm_head", "loss", "final_norm.u1",
            "final_norm.u3"} <= units
    assert system.hbm()["argument_bytes"] > 0


class _TheLastPassLeftOut:
    """The timed path with the last pass's exit cut off from the loss: the
    gate's bias pushed so far up that everything leaves at the first exits —
    part of the mathematics left out."""

    def __init__(self, system):
        self._s = system

    def __getattr__(self, name):
        return getattr(self._s, name)

    def start(self, weights):
        self._s.start(dict(weights,
                           **{"exit_gate.b": weights["exit_gate.b"] + 30.0}))


def test_a_cell_with_an_exit_left_out_is_not_correct(tree):
    cell = harness.load_cell("tiny_ouro.tiny_lm4k", tree)
    _, result, lines = _run(
        tree, build=lambda *a: _TheLastPassLeftOut(cell.adapter.build(*a)))
    assert result["correct"] is False, lines
