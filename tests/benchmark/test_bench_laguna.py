"""The `laguna_xs2` configuration and the cell PR 41 added: its counts against
a hand count, the cell found by name, each new reader on a hand-made trace,
and the whole cell driven on the CPU at a tiny size in float32 against its
plain reference — sound, and with the window, the partial rotation or the
output gate left out of the reference."""
import importlib
import json
import os
import time

import numpy as np
import pytest

import bench_fixtures as bf
from benchmark import harness, peaks, xtrace
from benchmark.configs import laguna_xs2
from benchmark.configs import laguna_xs2_reference as ref
from paddle_tpu.observability import get_registry, scopes

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(os.path.join(bf.REPO, "benchmark", "configs",
                       "laguna_xs2.json")) as f:
    CFG = json.load(f)
with open(os.path.join(bf.REPO, "benchmark", "traffic", "train8k.json")) as f:
    TRAIN8K = json.load(f)
CELL = "laguna_xs2.train8k"
NEW_READERS = ("swa_ms", "swa_roofline", "swa_tile_fill", "attn_gate_ms",
               "attn_proj_ms")
FULL, SLIDING = "full_attention", "sliding_attention"

TINY = {
    "hidden_size": 64, "num_key_value_heads": 2, "head_dim": 16,
    "num_attention_heads": 6,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
    "layer_types": [FULL, SLIDING, SLIDING, SLIDING, FULL],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "sliding_window": 8, "intermediate_size": 96,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "num_hidden_layers": 5, "num_experts": 4, "num_experts_published": 16,
    "experts_held": [4, 4], "num_experts_per_tok": 4, "vocab_size": 96,
    "rope_parameters": {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 4,
               "original_max_position_embeddings": 16, "beta_slow": 1,
               "beta_fast": 2, "attention_factor": 1.1386294361119891,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1}},
    "initializer_range": 0.2, "amp_dtype": None,
    "reference": {"follow_steps": 3, "head_rows": 8},
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
    c = laguna_xs2.counts(CFG, TRAIN8K)
    fwd = c["fwd_flops_per_token"]
    # the pairs a head of one sequence scores: the causal half, and the band
    assert laguna_xs2.visible_pairs(8192) == 8192 * 8193 // 2 == 33_558_528
    assert laguna_xs2.visible_pairs(8192, 512) == (
        512 * 513 // 2 + (8192 - 512) * 512) == 4_063_488
    assert laguna_xs2.visible_pairs(256, 512) == 256 * 257 // 2
    # products a token (multiply-adds), by hand from the published widths:
    # q | k | v, the gate and the output product at 48 and at 64 heads
    full = 2048 * (48 + 16) * 128 + 2048 * 48 + 48 * 128 * 2048
    window = 2048 * (64 + 16) * 128 + 2048 * 64 + 64 * 128 * 2048
    assert (full, window) == (29_458_432, 37_879_808)  # = their parameters
    assert fwd["attention_projections"] == 2 * (2 * full + 3 * window)
    # QK^T and PV, 128 channels each, a visible pair a query head
    kernel = (2 * 4 * 128 * 48 * 33_558_528
              + 3 * 4 * 128 * 64 * 4_063_488) / 8192
    assert fwd["attention_kernel"] == pytest.approx(kernel, rel=1e-12)
    assert fwd["dense_mlp"] == 2 * 3 * 2048 * 8192
    # 8 of 256 chosen, 32 held: one expert a token and layer, beside the
    # whole shared expert and the router's 256 outputs
    expert = 3 * 2048 * 512
    assert fwd["moe"] == 4 * 2 * (2048 * 256 + expert + expert)
    assert fwd["lm_head"] == 2 * 2048 * 12544
    whole = sum(fwd.values())
    assert whole == pytest.approx(801.8e6, rel=1e-3)
    assert c["flops_per_token"] == pytest.approx(3 * whole, rel=1e-12)
    assert c["tokens_per_step"] == 16384
    step = c["flops_per_token"] * 16384
    assert step == pytest.approx(39.4e12, rel=2e-3)
    # the kernels: 31% of the step, the three window layers a fifth of that
    full_step = 3 * 2 * 2 * 4 * 128 * 48 * 33_558_528
    swa_step = 3 * 3 * 2 * 4 * 128 * 64 * 4_063_488
    assert c["attn_flops_per_step"] == full_step + swa_step
    assert c["swa_flops_per_step"] == swa_step
    assert c["attn_flops_per_step"] / step == pytest.approx(0.31, abs=5e-3)
    assert swa_step / c["attn_flops_per_step"] == pytest.approx(0.195,
                                                                abs=5e-3)
    assert swa_step == pytest.approx(2.4e12, rel=1e-2)
    assert c["attn_flops_per_step"] == pytest.approx(12.3e12, rel=1e-2)
    # q, out and their cotangents at n_l x 128, k, v and theirs at 8 x 128
    assert c["swa_bytes_per_step"] == 3 * 4 * (8192 + 1024) * 16384 * 2
    assert c["attn_bytes_per_step"] == c["swa_bytes_per_step"] + (
        2 * 4 * (6144 + 1024) * 16384 * 2)
    # 16,384 pairs a layer on the held experts, 512 an expert, four layers
    assert c["moe_blocks"] == 4
    assert c["experts_pairs_per_step"] == 4 * 16384
    assert c["experts_pairs_per_step"] / 4 / 32 == 512
    assert c["experts_flops_per_pair"] == 3 * 2 * expert
    assert c["pairs_routed_per_step"] == 4 * 16384 * 8
    assert laguna_xs2.work_per_step(CFG, TRAIN8K) == 16384


def test_the_configuration_keeps_every_published_key():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    rows = [json.loads(line) for line in open(CATALOG)]
    row = next(r for r in rows if r["name"] == "Laguna-XS.2")
    published = row["config"]
    differs = {k for k, v in published.items() if CFG.get(k, "absent") != v}
    assert differs == set(CFG["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert CFG["source"] == row["source_url"]
    for key in CFG["reduced"]:
        assert CFG[f"{key}_published"] == published[key]
    # no width is cut, and the lists by layer stand whole
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "shared_expert_intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "sliding_window", "rope_parameters",
                "layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer", "partial_rotary_factor"):
        assert CFG[key] == published[key], key
    assert CFG["experts_held"] == [0, CFG["num_experts"]] == [0, 32]
    assert CFG["vocab_size"] * 8 == CFG["vocab_size_published"]
    assert CFG["vocab_size"] == 98 * 128
    with open(os.path.join(bf.REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "laguna_xs2")
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == row["source_url"]
    assert len(entry["why"]) <= 200
    assert set(CFG["assumed"]) >= {
        "attention", "output_gate", "rotary_embedding", "router",
        "feed_forward", "norms", "initializer", "optimizer", "precision",
        "input", "weights"}
    assert "8 chips" in CFG["deployment"]
    for key in ("loss_gap", "grad_gap", "update_gap", "reason"):
        assert key in CFG["limits"]


def test_parameters_and_memory_of_the_cut():
    from paddle_tpu.models import laguna
    specs = ref.weight_specs(CFG)
    n = sum(int(np.prod(shape)) for _, shape, _ in specs)
    assert n == 691_623_936
    mcfg = laguna_xs2.model_config(CFG)
    assert laguna.param_count(mcfg) == n
    assert 16 * n / 2 ** 30 == pytest.approx(10.31, rel=2e-3)
    # the layers the cut keeps: 48 heads and a dense MLP, three window
    # layers at 64, a full layer at 48 with experts
    assert mcfg.layer_types == [FULL, SLIDING, SLIDING, SLIDING, FULL]
    assert mcfg.num_attention_heads_per_layer == [48, 64, 64, 64, 48]
    assert mcfg.mlp_layer_types == ["dense"] + ["sparse"] * 4
    assert mcfg.num_experts == 256 and mcfg.held() == (0, 32)
    assert mcfg.sliding_window == 512
    assert laguna.rope_arguments(mcfg, FULL) == {
        "theta": 500000.0, "rotary_dim": 64,
        "yarn": {"factor": 64, "original_max_position_embeddings": 4096,
                 "beta_fast": 64, "beta_slow": 1},
        "attention_factor": 1.4158883083359672}
    assert laguna.rope_arguments(mcfg, SLIDING) == {"theta": 10000.0}


def test_the_adapter_refuses_what_the_builder_does_not_build():
    for key, value in (("attention_bias", True),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match="laguna_xs2"):
            laguna_xs2.model_config(dict(CFG, **{key: value}))
    with pytest.raises(ValueError, match="output gate is built in"):
        laguna_xs2.model_config(dict(CFG, gating=False)).check()


# ---------------------------------------------------------------------------
# the cell is found
# ---------------------------------------------------------------------------

def test_load_cell_finds_the_new_cell():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.workload["traffic"] == "train8k"
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "step_ms", "setup_s"}
    assert cell.config["rate_metric"] == "tokens_per_s"
    has = {*NEW_READERS, "moe_ms", "moe_dispatch_ms", "experts_roofline",
           "expert_load_max", "pairs_held_share", "lm_head_ms", "rope_ms",
           "mlp_ms", "mfu", "attn_ms", "attn_roofline", "scope_coverage",
           "step_hbm"}
    lacks = {"head_ms", "rows_ms", "mamba_ms", "ssd_ms", "loop_ms",
             "exit_ms", "collective_ms", "conv_mixer_ms", "qk_norm_ms",
             "mla_proj_ms", "mla_assemble_ms", "mtp_ms", "shared_expert_ms"}
    assert has <= set(cell.readers) and not lacks & set(cell.readers)
    (batch,) = cell.generator.make_ring(cell.config,
                                        dict(cell.traffic, ring=1),
                                        2 ** 31 + 77)
    ids = batch["ids"]
    assert ids.shape == (2, 8192) and 0 <= ids.min() and ids.max() < 12544
    np.testing.assert_array_equal(batch["labels"][:, :-1, 0], ids[:, 1:])


def test_the_cell_is_in_the_benchmark_on_one_chip():
    # no totals: the next cell must not have to touch this test
    with open(os.path.join(bf.REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna_xs2", "train8k", 1)
    assert "1/8 of deployed load" in cell["why"] and len(cell["why"]) <= 200
    metrics = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_READERS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "step_ms"
    assert metrics["swa_tile_fill"]["source"] == "program_counter"
    assert metrics["swa_roofline"]["unit"] == "%"
    assert metrics["swa_roofline"]["layer"] == metrics["attn_ms"]["layer"]
    names = [m["name"] for m in spec["per_layer"]]
    at = names.index("shared_expert_ms")
    assert names[at + 1:at + 6] == list(NEW_READERS)
    for name in ("mfu", "attn_ms", "attn_roofline", "moe_ms",
                 "moe_dispatch_ms", "experts_roofline", "lm_head_ms",
                 "expert_load_max", "pairs_held_share", "rope_ms", "mlp_ms"):
        assert CELL in metrics[name]["workloads"], name
    rates = {m["name"]: m for m in spec["end_to_end"]}
    assert CELL in rates["tokens_per_s"]["workloads"]


def test_the_weights_are_one_draw_and_the_seed_decides_the_batches():
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

# (unit, kind) of a traced step's operations; operation i ran 2^i ms
OPS = [("embed", "xla"), ("blk0/attn/qkv", "xla"), ("blk0/attn/gate", "xla"),
       ("blk0/attn/rope", "xla"), ("blk0/attn/kernel", "mosaic"),
       ("blk0/attn/o", "xla"), ("blk0/mlp/gate_up", "xla"),
       ("blk1/attn/qkv", "xla"), ("blk1/attn/swa", "mosaic"),
       ("blk1/attn/swa", "xla"), ("blk1/attn/gate/blk1/attn/gate", "xla"),
       ("blk1/moe/shared/gate_up", "xla"), ("blk1/moe/experts", "xla"),
       ("blk2/attn/swa/blk2/attn/swa", "mosaic"), ("lm_head", "xla"),
       (None, "mosaic")]
MS = [2 ** i for i in range(len(OPS))]


def _ctx(ops=OPS, opcode="fusion", series=None):
    found, events, at = {}, [], 0
    for i, ((unit, kind), ms) in enumerate(zip(ops, MS)):
        if kind == "mosaic":
            name = f"custom-call.{i}"
            text = (f"%{name} = bf16[8,{i + 1}] custom-call(%x), "
                    f"custom_call_target=\"tpu_custom_call\"")
        else:
            name = f"{opcode}.{i}"
            text = f"%{name} = f32[8,{i + 1}] {opcode}(%x)"
        found[name] = scopes.OpScope(name=name, text=text, phase="fwd",
                                     unit=unit, op_types=("mul",),
                                     has_dot=True)
        dur = int(ms * 1e6)
        events.append([xtrace.label(text), xtrace.classify(text), at, dur])
        at += dur
    trace = xtrace.Reduced({"devices": {"/device:TPU:0": events},
                            "host": []}, 1)
    ctx = {"trace": trace, "op_scopes": found, "chips": 1,
           "counts": laguna_xs2.counts(CFG, TRAIN8K),
           "peaks": peaks.peaks_for("TPU v5 lite")}
    if series is not None:
        ctx["registry_series"] = series
    return ctx


SWA_MS = MS[8] + MS[13]


@pytest.mark.parametrize("name,expected", [
    # the Mosaic calls under /attn/swa alone: not the unit's XLA operation,
    # not the full layer's kernel, not a call of no unit
    ("swa_ms", SWA_MS),
    ("attn_gate_ms", MS[2] + MS[10]),
    ("attn_proj_ms", MS[1] + MS[5] + MS[7]),
    # the accepted readers on the same step
    ("attn_ms", MS[4] + MS[8] + MS[13] + MS[15]),
    ("rope_ms", MS[3]), ("mlp_ms", MS[6]),
    ("moe_ms", MS[11] + MS[12]), ("lm_head_ms", MS[14])])
def test_unit_readers_sum_their_units(name, expected):
    assert _reader(name)(_ctx()) == pytest.approx(expected)


def test_swa_roofline_counts_the_band_s_pairs():
    """The window layers' 2.4 TFLOP of required work (12.2 ms at the v5e's
    peak: the operations bound it, not the 1.8 ms of bytes) over the time in
    their kernels; a reader that counted the tiles of a 512-block schedule
    would say twice that."""
    ctx = _ctx()
    c, p = ctx["counts"], ctx["peaks"]
    least = c["swa_flops_per_step"] / p["flops_per_s"]
    assert least > c["swa_bytes_per_step"] / p["hbm_bytes_per_s"]
    assert least == pytest.approx(12.2e-3, rel=2e-2)
    assert _reader("swa_roofline")(ctx) == pytest.approx(
        100 * least / (SWA_MS * 1e-3))
    # and the accepted share over every Mosaic call
    whole = c["attn_flops_per_step"] / p["flops_per_s"]
    assert _reader("attn_roofline")(ctx) == pytest.approx(
        100 * whole / ((MS[4] + SWA_MS + MS[15]) * 1e-3))
    del ctx["counts"]["swa_flops_per_step"]
    assert _reader("swa_roofline")(ctx) is None


def test_swa_tile_fill_reads_the_windowed_forward_kernel_s_gauges():
    def gauge(name, value, **labels):
        return {"name": f"flash_attention/{name}", "labels": labels,
                "value": value}
    series = [gauge("scores_visible", 4_063_488, kernel="fwd", call="window"),
              gauge("scores_scheduled", 31 * 512 * 512, kernel="fwd",
                    call="window"),
              gauge("scores_visible", 1, kernel="bwd", call="window"),
              gauge("scores_scheduled", 7, kernel="bwd", call="window"),
              gauge("scores_visible", 33_558_528, kernel="fwd",
                    call="causal"),
              gauge("scores_scheduled", 36 * 1024 * 1024, kernel="fwd",
                    call="causal")]
    assert _reader("swa_tile_fill")(_ctx(series=series)) == pytest.approx(
        100 * 4_063_488 / (31 * 512 * 512))
    # a program with causal calls only, or without the gauges: nothing
    assert _reader("swa_tile_fill")(_ctx(series=series[4:])) is None
    assert _reader("swa_tile_fill")(_ctx(series=[])) is None
    # by the kernel's own numbers: 15 tiles of 1,024 a quarter, 93 of 256
    # two thirds
    fa = importlib.import_module(
        "paddle_tpu.ops.pallas_kernels.flash_attention")
    for block, fill in ((1024, 0.258), (512, 0.5), (256, 0.667)):
        n = 8192 // block
        tiles = len(fa._tile_schedule(n, n, block, block, True,
                                      window=512)[0])
        assert 4_063_488 / (tiles * block * block) == pytest.approx(
            fill, abs=2e-3)


@pytest.mark.parametrize("name", [n for n in NEW_READERS
                                  if n != "swa_tile_fill"])
def test_the_new_readers_find_nothing_elsewhere(name):
    # JoyAI's, LFM2's and Nemotron's units and a program that writes no
    # scopes give nothing, and nothing raises
    other = [("blk1/attn/q_a", "xla"), ("blk1/attn/kernel", "mosaic"),
             ("blk0.M/mamba/in_proj", "xla"), ("blk5.A/attn", "mosaic"),
             ("blk1/attn/qk_norm", "xla"), ("blk1/moe/experts", "xla"),
             ("lm_head", "xla")]
    assert _reader(name)(_ctx(other)) is None
    ctx = _ctx()
    ctx["op_scopes"] = {}
    assert _reader(name)(ctx) is None
    if name in ("attn_gate_ms", "attn_proj_ms"):
        # a `while` is left out (its body's operations are in the trace)
        only = [(u, k) for u, k in OPS if k == "xla"]
        assert _reader(name)(_ctx(only, opcode="while")) is None


# ---------------------------------------------------------------------------
# the whole cell on the CPU, tiny, float32
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = bf.make_tree(tmp_path_factory.mktemp("bench_laguna"))
    bench = os.path.join(root, "benchmark")
    cfg = dict(CFG, **TINY, name="tiny_laguna")
    with open(os.path.join(bench, "configs", "tiny_laguna.json"), "w") as f:
        json.dump(cfg, f)
    for suffix in ("", "_reference"):
        with open(os.path.join(bench, "configs",
                               f"tiny_laguna{suffix}.py"), "w") as f:
            f.write(f"from benchmark.configs.laguna_xs2{suffix} "
                    f"import *  # noqa: F401,F403\n")
    with open(os.path.join(bench, "traffic", "tiny_lm8k.json"), "w") as f:
        json.dump(dict(TRAIN8K, name="tiny_lm8k", batch=2, seq_len=32, ring=4,
                       warmup_blocks=2, trace_blocks=2), f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny_laguna", "source": "test", "reduced": [],
        "file": "benchmark/configs/tiny_laguna.json", "why": "test"})
    spec["workloads"].append({
        "name": "tiny_laguna.tiny_lm8k", "config": "tiny_laguna",
        "traffic": "tiny_lm8k", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_laguna.tiny_lm8k")
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


def _run(tree, build=None):
    cell = harness.load_cell("tiny_laguna.tiny_lm8k", tree)
    lines = []
    result = harness.run_cell(
        cell, 2 ** 31 + 41, 0.3, False, time.perf_counter(), build=build,
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
    series = get_registry().series()
    blocks = {s["labels"]["block"] for s in series
              if s["name"] == "moe/pairs_held"}
    assert {"blk1", "blk2", "blk3", "blk4"} <= blocks
    dropped = [s["value"] for s in series if s["name"] == "moe/dropped"]
    assert dropped and not any(dropped)


def test_the_step_names_every_part_the_unit_readers_read(tree):
    cell = harness.load_cell("tiny_laguna.tiny_lm8k", tree)
    system = cell.adapter.build(cell.config, cell.traffic, 1)
    (batch,) = cell.generator.make_ring(cell.config,
                                        dict(cell.traffic, ring=1), 7)
    system.start(cell.reference.make_weights(cell.config, 7))
    np.asarray(system.step(batch))
    found = scopes.op_scopes(system.exe.compiled_step(system.main))
    units = {s.unit for s in found.values() if s.unit}
    for part in ("/attn/qkv", "/attn/gate", "/attn/rope", "/attn/kernel",
                 "/attn/swa", "/attn/o", "/mlp/gate_up", "/mlp/down",
                 "/moe/router", "/moe/dispatch", "/moe/experts",
                 "/moe/shared"):
        assert any(part in u for u in units), (part, sorted(units))
    assert {"lm_head", "loss", "embed", "final_norm"} <= units
    assert system.hbm()["argument_bytes"] > 0


def _reference_with(monkeypatch, **change):
    """The reference's attention reading another configuration."""
    attention = ref.attention

    def changed(x, params, p, i, cfg, mm=ref._mm):
        cfg = dict(cfg, **{k: v(cfg) if callable(v) else v
                           for k, v in change.items()})
        return attention(x, params, p, i, cfg, mm=mm)

    monkeypatch.setattr(ref, "attention", changed)


def test_a_reference_without_the_window_is_not_correct(tree, monkeypatch):
    """The window layers of the reference seeing every earlier key: from
    position 8 on, other scores in three layers of five."""
    _reference_with(monkeypatch, sliding_window=None)
    _, result, lines = _run(tree)
    assert result["correct"] is False, lines
    assert any("FAILED" in line for line in lines)


def test_a_reference_turning_whole_heads_is_not_correct(tree, monkeypatch):
    """The full layers' rotation on all 16 channels of a head where the
    model turns the first 8 and passes the rest."""
    def whole(cfg):
        rules = {k: dict(v) for k, v in cfg["rope_parameters"].items()}
        rules[FULL]["partial_rotary_factor"] = 1
        return rules

    _reference_with(monkeypatch, rope_parameters=whole)
    _, result, lines = _run(tree)
    assert result["correct"] is False, lines
    assert any("FAILED" in line for line in lines)


def test_a_reference_without_the_gate_is_not_correct(tree, monkeypatch):
    """The kernel's result going to W_o as it is: about twice the gated
    one, in every layer."""
    _reference_with(monkeypatch, gating=False)
    _, result, lines = _run(tree)
    assert result["correct"] is False, lines
    assert any("FAILED" in line for line in lines)
