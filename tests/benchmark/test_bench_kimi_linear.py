"""The `kimi_linear_48b_a3b` configuration and the cell PR 47 added: its file
against the catalog's row key for key, its counts against a hand count, the
cell found by name, each new reader on a hand-made trace, and the whole cell
driven on the CPU at a tiny size in float32 against its plain reference —
sound, with the delta rule's correction left out, and with the decay taken a
head and not a channel."""
import importlib
import json
import os
import time

import numpy as np
import pytest

import bench_fixtures as bf
from benchmark import harness, peaks, xtrace
from benchmark.configs import kimi_linear_48b_a3b
from paddle_tpu.observability import get_registry, scopes

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(os.path.join(bf.REPO, "benchmark", "configs",
                       "kimi_linear_48b_a3b.json")) as f:
    CFG = json.load(f)
with open(os.path.join(bf.REPO, "benchmark", "traffic", "train8k.json")) as f:
    TRAIN8K = json.load(f)
CELL = "kimi_linear_48b_a3b.train8k"
NEW_READERS = ("kda_ms", "kda_rule_ms", "kda_roofline", "kda_gates_ms",
               "kda_conv_ms", "mla_kernel_ms", "kda_decay_floor")
APPENDED_TO = ("tokens_per_s", "mfu", "moe_ms", "moe_dispatch_ms",
               "experts_roofline", "expert_load_max", "pairs_held_share",
               "lm_head_ms", "mlp_ms", "shared_expert_ms", "mla_proj_ms",
               "mla_assemble_ms")

# layers (from 1): KDA + dense MLP; KDA, latent attention, KDA with experts
TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 4,
    "linear_attn_config": {"kda_layers": [1, 2, 4], "full_attn_layers": [3],
                           "head_dim": 16, "num_heads": 2,
                           "short_conv_kernel_size": 4},
    "kda_gate_rank": 16, "kda_chunk": 16,
    "num_experts": 4, "num_experts_published": 8, "experts_held": [2, 4],
    "num_experts_per_token": 2, "vocab_size": 96, "initializer_range": 0.2,
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
# the configuration's file and its counts
# ---------------------------------------------------------------------------

def test_the_configuration_keeps_every_published_key():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    rows = [json.loads(line) for line in open(CATALOG)]
    row = next(r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    published = row["config"]
    differs = {k for k, v in published.items() if CFG.get(k, "absent") != v}
    assert differs == set(CFG["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert CFG["source"] == row["source_url"]
    for key in CFG["reduced"]:
        assert CFG[f"{key}_published"] == published[key]
    # no width is cut, and the nested group stands whole
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "num_attention_heads", "num_experts_per_token",
                "num_shared_experts", "head_dim", "routed_scaling_factor",
                "linear_attn_config"):
        assert CFG[key] == published[key], key
    assert CFG["q_lora_rank"] is None and CFG["mla_use_nope"] is True
    assert CFG["experts_held"] == [0, CFG["num_experts"]] == [0, 8]
    assert CFG["vocab_size"] * 8 == CFG["vocab_size_published"]
    assert (CFG["num_experts"] * 32, CFG["num_hidden_layers_published"]) == (
        CFG["num_experts_published"], 27)
    with open(os.path.join(bf.REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "kimi_linear_48b_a3b")
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == row["source_url"]
    assert len(entry["why"]) <= 200
    assert set(CFG["assumed"]) >= {
        "kda", "delta_rule_chunks", "latent_attention", "router",
        "feed_forward", "norms", "position", "initializer", "optimizer",
        "precision", "input", "weights"}
    assert "32 chips" in CFG["deployment"]
    assert CFG["weights_seed"] == 0 and CFG["optimizer"]["warmup_steps"] == 2000


def test_operations_per_token_against_a_hand_count():
    c = kimi_linear_48b_a3b.counts(CFG, TRAIN8K)
    fwd = c["fwd_flops_per_token"]
    d, wide = 2304, 4096
    # products a token (multiply-adds), by hand from the published widths:
    # q, k, v, the two low-rank gates, beta, the output product; the three
    # 4-tap filters
    kda = (3 * d * wide + 2 * (d * 128 + 128 * wide) + d * 32 + wide * d
           + 3 * wide * 4)
    # (the layer's 39,514,272 parameters less dt_bias, A_log and the norm)
    assert kda == 39_510_016 == 39_514_272 - (wide + 32 + 128)
    assert fwd["kda_projections"] == 2 * kda
    # the rule a token and head: its rows of A_kk, A_qk, W, U and A_qk V_new
    # over 64 positions, the state's three products, the substitution's share
    rule = 32 * (2 * 64 * (3 * 128 + 2 * 128) + 6 * 128 * 128 + 64 * 64 / 3)
    assert fwd["kda_rule"] == pytest.approx(rule, rel=1e-12)
    assert 2 * 64 * (3 * 128 + 2 * 128) + 6 * 128 * 128 == 180_224
    mla = d * 6144 + d * 576 + 512 * 8192 + 4096 * d
    assert fwd["mla_projections"] == 2 * mla
    assert fwd["attention_kernel"] == 8192 * 32 * (192 + 128) == 83_886_080
    assert fwd["dense_mlp"] == 2 * 3 * d * 9216
    expert = 3 * d * 1024
    # 8 of 256 chosen, 8 held: a quarter of an expert a token and layer,
    # beside the whole shared expert and the router's 256 outputs
    moe = 2 * (d * 256 + expert + expert * 8 * 8 / 256)
    assert fwd["moe"] == moe
    assert fwd["lm_head"] == 2 * d * 20480
    whole = (4 * (2 * kda + rule) + 2 * mla + 83_886_080 + 2 * 3 * d * 9216
             + 4 * moe + 2 * d * 20480)
    assert c["flops_per_token"] == pytest.approx(3 * whole, rel=1e-12)
    assert c["tokens_per_step"] == 16384
    assert (c["kda_layers"], c["attention_layers"], c["moe_blocks"]) == (
        4, 1, 4)
    # a step's rules: 1.14 TFLOP (5.8 ms at the v5e's peak) and 7.54 GB
    # (9.2 ms at its bandwidth): bound by bytes. q, k, v, the gate's raw
    # values and o in bf16 and beta in float32 forward; those and o's
    # cotangent in, the five cotangents out backward
    assert c["kda_flops_per_step"] == pytest.approx(1.14e12, rel=5e-3)
    forward = 5 * wide * 2 + 32 * 4                        # 41,088 a token
    backward = forward + 4 * wide * 2 + 32 * 4             # 73,984
    assert (forward, backward) == (41_088, 73_984)
    assert c["kda_bytes_per_step"] == (forward + backward) * 16384 * 4
    p = peaks.peaks_for("TPU v5 lite")
    assert (c["kda_bytes_per_step"] / p["hbm_bytes_per_s"]
            > c["kda_flops_per_step"] / p["flops_per_s"])
    assert c["kda_bytes_per_step"] / p["hbm_bytes_per_s"] == pytest.approx(
        9.2e-3, rel=1e-2)
    # 4,096 pairs a layer on the held experts, 512 an expert, four layers
    assert c["experts_pairs_per_step"] == 4 * 4096
    assert c["experts_pairs_per_step"] / 4 / 8 == 512
    assert c["experts_flops_per_pair"] == 3 * 2 * expert
    assert c["pairs_routed_per_step"] == 4 * 16384 * 8
    assert kimi_linear_48b_a3b.work_per_step(CFG, TRAIN8K) == 16384


def test_parameters_and_memory_of_the_cut():
    from benchmark.configs import kimi_linear_48b_a3b_reference as ref
    from paddle_tpu.models import kimi_linear
    specs = ref.weight_specs(CFG)
    n = sum(int(np.prod(shape)) for name, shape, _ in specs
            if not name.endswith(ref.FROZEN))
    assert n == 602_433_408
    assert kimi_linear.param_count(
        kimi_linear_48b_a3b.model_config(CFG)) == n
    assert 16 * n / 2 ** 30 == pytest.approx(8.98, abs=5e-3)
    assert sum(name.endswith(ref.FROZEN) for name, _, _ in specs) == 4
    assert [ref.is_kda(CFG, i) for i in range(5)] == [True, True, True,
                                                     False, True]


def test_the_adapter_refuses_what_the_builder_does_not_build():
    for key, value in (("moe_router_activation_func", "softmax"),
                       ("num_expert_group", 8), ("q_lora_rank", 1536),
                       ("mla_use_nope", False),
                       ("rope_scaling", {"type": "yarn"}),
                       ("num_nextn_predict_layers", 1)):
        with pytest.raises(ValueError, match="kimi_linear_48b_a3b"):
            kimi_linear_48b_a3b.model_config(dict(CFG, **{key: value}))


# ---------------------------------------------------------------------------
# the cell is found
# ---------------------------------------------------------------------------

def test_load_cell_finds_the_new_cell():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.workload["traffic"] == "train8k"
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "step_ms", "setup_s"}
    assert cell.config["rate_metric"] == "tokens_per_s"
    has = {*NEW_READERS, *APPENDED_TO[1:], "scope_coverage", "step_hbm"}
    # every Mosaic call of a step is in `attn_ms`, the experts' too; there is
    # no rotation to time
    lacks = {"attn_ms", "attn_roofline", "rope_ms", "head_ms", "rows_ms",
             "mamba_ms", "ssd_ms", "loop_ms", "mtp_ms", "swa_ms",
             "collective_ms", "conv_mixer_ms", "qk_norm_ms"}
    assert has <= set(cell.readers) and not lacks & set(cell.readers)
    (batch,) = cell.generator.make_ring(cell.config,
                                        dict(cell.traffic, ring=1),
                                        2 ** 31 + 77)
    ids = batch["ids"]
    assert ids.shape == (2, 8192) and 0 <= ids.min() and ids.max() < 20480
    np.testing.assert_array_equal(batch["labels"][:, :-1, 0], ids[:, 1:])


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    # no totals: the next cell must not have to touch this test
    with open(os.path.join(bf.REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi_linear_48b_a3b", "train8k", 1)
    assert "1/32 of deployed load" in cell["why"] and len(cell["why"]) <= 200
    assert [w["name"] for w in spec["workloads"] if w["chips"] == 4] == [
        "ernie_base.dp4_seq512"]
    names = [m["name"] for m in spec["per_layer"]]
    metrics = {m["name"]: m for m in spec["per_layer"] + spec["end_to_end"]}
    # the seven follow what PR 41 ended the list with
    at = names.index(NEW_READERS[0])
    assert names[at - 1] == "attn_proj_ms"
    assert names[at:at + 7] == list(NEW_READERS)
    for name in NEW_READERS:
        # first on its list, not alone: a later cell may be appended
        assert metrics[name]["workloads"][0] == CELL
        assert metrics[name]["moves"] == "step_ms"
        assert metrics[name]["source"] == (
            "program_counter" if name == "kda_decay_floor"
            else "device_trace")
    assert metrics["kda_roofline"]["unit"] == "%"
    assert metrics["kda_decay_floor"]["better"] == "higher"
    assert len({metrics[name]["layer"] for name in NEW_READERS}) == 1
    for name in APPENDED_TO:
        assert CELL in metrics[name]["workloads"], name
    for name in ("attn_ms", "attn_roofline", "rope_ms"):
        assert CELL not in metrics[name]["workloads"], name


def test_joyai_s_cell_and_metrics_stand_as_written_with_this_cell_appended():
    """What tests/benchmark/test_bench_joyai.py's
    `test_the_cell_is_in_the_benchmark_on_one_chip` held, less its one
    assertion that PR 39's four metrics list JoyAI's cell alone
    (tests/conftest.py says why that one went): the cell's entry, the one
    cell on four chips, each metric's `moves`, `source` and `layer`, JoyAI's
    cell first on each list, alone on `mtp_ms`, with this cell after it on
    the other three."""
    joyai = "joyai_llm_flash.train8k"
    with open(os.path.join(bf.REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (cell,) = [w for w in spec["workloads"] if w["name"] == joyai]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "joyai_llm_flash", "train8k", 1)
    assert "1/16 of deployed load" in cell["why"] and len(cell["why"]) <= 200
    assert [w["name"] for w in spec["workloads"] if w["chips"] == 4] == [
        "ernie_base.dp4_seq512"]
    metrics = {m["name"]: m for m in spec["per_layer"]}
    four = ("mla_proj_ms", "mla_assemble_ms", "mtp_ms", "shared_expert_ms")
    for name in four:
        assert metrics[name]["workloads"] == (
            [joyai] if name == "mtp_ms" else [joyai, CELL])
        assert metrics[name]["moves"] == "step_ms"
        assert metrics[name]["source"] == "device_trace"
    assert len({metrics[name]["layer"] for name in four}) == 1


def test_the_weights_are_one_draw_and_the_seed_decides_the_batches():
    from benchmark.configs import kimi_linear_48b_a3b_reference as ref
    cfg = dict(CFG, **TINY)
    a, b = ref.make_weights(cfg, 2 ** 31 + 5), ref.make_weights(cfg, 7)
    assert a.keys() == b.keys()
    assert all(np.array_equal(a[k], b[k]) for k in a)
    del cfg["weights_seed"]
    c, d = ref.make_weights(cfg, 0), ref.make_weights(cfg, 7)
    assert all(np.array_equal(a[k], c[k]) for k in a)
    assert not np.array_equal(c["blk1.moe.gate"], d["blk1.moe.gate"])
    # the decays' draws lie in the stated ranges: A in [1, 16] a head, the
    # step in [1e-3, 1e-1] a channel
    rate = np.exp(np.asarray(a["blk0.A_log"]))
    step = np.log1p(np.exp(np.asarray(a["blk0.dt_bias"], np.float64)))
    assert 1.0 <= rate.min() and rate.max() <= 16.0
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 1e-1 * 1.001


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

UNITS = ["embed", "blk0/kda/q", "blk0/kda/conv", "blk0/kda/decay",
         "blk0/kda/beta", "blk0/kda/rule", "blk0/kda/rule/blk0/kda/rule",
         "blk0/kda/out_gate", "blk0/kda/out_norm", "blk0/kda/o",
         "blk0/mlp/gate_up", "blk3/attn/q_b", "blk3/attn/assemble",
         "blk3/attn/kernel", "blk3/moe/experts", "blk3/moe/shared/down",
         "lm_head", None]
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
            "counts": kimi_linear_48b_a3b.counts(CFG, TRAIN8K),
            "peaks": peaks.peaks_for("TPU v5 lite"), "registry_series": []}


@pytest.mark.parametrize("name,expected", [
    ("kda_ms", sum(MS[1:10])),
    ("kda_rule_ms", 32 + 64),
    ("kda_gates_ms", 8 + 16 + 128 + 256),
    ("kda_conv_ms", 4),
    ("mla_kernel_ms", MS[13]),
    ("mla_proj_ms", MS[11]), ("mla_assemble_ms", MS[12]),
    ("mlp_ms", MS[10]), ("moe_ms", MS[14] + MS[15]),
    ("shared_expert_ms", MS[15]), ("lm_head_ms", MS[16])])
def test_unit_readers_sum_their_units(name, expected):
    assert _reader(name)(_ctx()) == pytest.approx(expected)


def test_kda_roofline_is_the_bytes_bound_over_the_rule_s_time():
    """7.54 GB a step over 819 GB/s, 9.2 ms, against the 96 ms this trace
    gives the rule: 9.6%; nothing where the counts know no rule."""
    ctx = _ctx()
    c, p = ctx["counts"], ctx["peaks"]
    least = c["kda_bytes_per_step"] / p["hbm_bytes_per_s"]
    assert _reader("kda_roofline")(ctx) == pytest.approx(
        100 * least / 96e-3)
    assert 0 < _reader("kda_roofline")(ctx) < 105
    ctx["counts"] = {"tokens_per_step": 1}
    assert _reader("kda_roofline")(ctx) is None


def test_the_decay_floor_is_read_from_the_program_s_gauge():
    ctx = _ctx()
    assert _reader("kda_decay_floor")(ctx) is None
    ctx["registry_series"] = [
        {"name": "moe/pairs_held", "labels": {"block": "blk1"}, "value": 3},
        {"name": "kda/decay_floor", "labels": {}, "value": -41.5}]
    assert _reader("kda_decay_floor")(ctx) == -41.5


@pytest.mark.parametrize("name", [n for n in NEW_READERS
                                  if n != "kda_decay_floor"])
def test_the_new_readers_count_a_loop_once_and_find_nothing_elsewhere(name):
    # a `while` is left out (its body's operations are in the trace)
    assert _reader(name)(_ctx(opcode="while")) is None
    # JoyAI's, LFM2's and Nemotron's units and a program that writes no
    # scopes (the parent's, for a cell it cannot build) give nothing, but
    # for latent attention's kernel, which JoyAI has; nothing raises
    other = ["blk1/attn/qkv", "blk1/attn/qk_norm", "blk0.M/mamba/in_proj",
             "blk5.A/attn", "blk1/moe/experts", "lm_head", "blk0/attn/rope"]
    assert _reader(name)(_ctx(other + [None] * 11)) is None
    ctx = _ctx()
    ctx["op_scopes"] = {}
    assert _reader(name)(ctx) is None


# ---------------------------------------------------------------------------
# the whole cell on the CPU, tiny, float32
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = bf.make_tree(tmp_path_factory.mktemp("bench_kimi"))
    bench = os.path.join(root, "benchmark")
    cfg = dict(CFG, **TINY, name="tiny_kimi")
    with open(os.path.join(bench, "configs", "tiny_kimi.json"), "w") as f:
        json.dump(cfg, f)
    for suffix in ("", "_reference"):
        with open(os.path.join(bench, "configs",
                               f"tiny_kimi{suffix}.py"), "w") as f:
            f.write(f"from benchmark.configs.kimi_linear_48b_a3b{suffix} "
                    f"import *  # noqa: F401,F403\n")
    with open(os.path.join(bench, "traffic", "tiny_lm8k.json"), "w") as f:
        json.dump(dict(TRAIN8K, name="tiny_lm8k", batch=2, seq_len=32, ring=4,
                       warmup_blocks=2, trace_blocks=2), f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny_kimi", "source": "test", "reduced": [],
        "file": "benchmark/configs/tiny_kimi.json", "why": "test"})
    spec["workloads"].append({
        "name": "tiny_kimi.tiny_lm8k", "config": "tiny_kimi",
        "traffic": "tiny_lm8k", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_kimi.tiny_lm8k")
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


def _run(tree, build=None):
    cell = harness.load_cell("tiny_kimi.tiny_lm8k", tree)
    lines = []
    result = harness.run_cell(
        cell, 2 ** 31 + 47, 0.3, False, time.perf_counter(), build=build,
        device=dict(bf.FAKE_DEVICE),
        say=lambda *a, **k: lines.append(" ".join(map(str, a))))
    return cell, result, lines


def test_the_tiny_cell_agrees_with_its_plain_reference(tree):
    cell, result, lines = _run(tree)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"tokens_per_s", "step_ms", "setup_s"}
    # the counters the step fetched with its loss are in the registry: the
    # three expert layers' and the KDA layers' decay floor
    ctx = {}
    assert 0 < _reader("pairs_held_share")(ctx) < 100
    assert _reader("expert_load_max")(ctx) >= 1.0
    assert -200 < _reader("kda_decay_floor")(ctx) < 0
    series = get_registry().series()
    blocks = {s["labels"]["block"] for s in series
              if s["name"] == "moe/pairs_held"}
    assert {"blk1", "blk2", "blk3"} <= blocks
    dropped = [s["value"] for s in series if s["name"] == "moe/dropped"]
    assert dropped and not any(dropped)


def test_the_step_names_every_part_the_unit_readers_read(tree):
    cell = harness.load_cell("tiny_kimi.tiny_lm8k", tree)
    system = cell.adapter.build(cell.config, cell.traffic, 1)
    (batch,) = cell.generator.make_ring(cell.config,
                                        dict(cell.traffic, ring=1), 7)
    system.start(cell.reference.make_weights(cell.config, 7))
    np.asarray(system.step(batch))
    found = scopes.op_scopes(system.exe.compiled_step(system.main))
    units = {s.unit for s in found.values() if s.unit}
    for part in ("/kda/q", "/kda/k", "/kda/v", "/kda/conv", "/kda/decay",
                 "/kda/beta", "/kda/rule", "/kda/out_gate", "/kda/out_norm",
                 "/kda/o", "/attn/q_b", "/attn/kv_a", "/attn/kv_b",
                 "/attn/assemble", "/attn/kernel", "/attn/o", "/mlp/gate_up",
                 "/mlp/down", "/moe/router", "/moe/dispatch", "/moe/experts",
                 "/moe/shared"):
        assert any(part in u for u in units), (part, sorted(units))
    assert not [u for u in units if "/rope" in u or "/attn/q_a" in u]
    assert {"lm_head", "loss", "embed", "final_norm"} <= units
    assert system.hbm()["argument_bytes"] > 0


def test_a_cell_without_the_rule_s_correction_is_not_correct(
        tree, monkeypatch):
    """The timed path with beta k k^T dropped from the state's update (a
    decayed sum of beta k v^T: Mamba-2's scan with a decay a channel): other
    outputs, other gradients."""
    from paddle_tpu.ops import linear_attn_ops as la
    terms = la._chunk_terms

    def uncorrected(q, k, v, g, beta, gate, scale, l2_eps):
        (w, u, *rest), floor = terms(q, k, v, g, beta, gate, scale, l2_eps)
        return (0 * w, beta * v.astype(u.dtype), *rest), floor

    monkeypatch.setattr(la, "_chunk_terms", uncorrected)
    _, result, lines = _run(tree)
    assert result["correct"] is False, lines
    assert any("FAILED" in line for line in lines)


def test_a_cell_whose_decay_is_one_value_a_head_is_not_correct(
        tree, monkeypatch):
    """The timed path with each head's log-decay averaged over its channels
    (a gated DeltaNet's scalar decay): the model decays channel by
    channel."""
    from paddle_tpu.ops import linear_attn_ops as la
    prepared = la._prepared

    def a_head(q, k, g, gate, l2_eps):
        q, k, g = prepared(q, k, g, gate, l2_eps)
        return q, k, g.mean(axis=-1, keepdims=True) + 0 * g

    monkeypatch.setattr(la, "_prepared", a_head)
    _, result, lines = _run(tree)
    assert result["correct"] is False, lines
