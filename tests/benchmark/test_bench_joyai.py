"""The `joyai_llm_flash` configuration and the cell PR 39 added: its counts
against a hand count, the cell found by name, each new reader on a hand-made
trace, and the whole cell driven on the CPU at a tiny size in float32 against
its plain reference — sound, with the prediction module's term left out, and
with the rotation by halves."""
import importlib
import json
import os
import time

import numpy as np
import pytest

import bench_fixtures as bf
from benchmark import harness, peaks, xtrace
from benchmark.configs import joyai_llm_flash
from paddle_tpu.observability import get_registry, scopes

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(os.path.join(bf.REPO, "benchmark", "configs",
                       "joyai_llm_flash.json")) as f:
    CFG = json.load(f)
with open(os.path.join(bf.REPO, "benchmark", "traffic", "train8k.json")) as f:
    TRAIN8K = json.load(f)
CELL = "joyai_llm_flash.train8k"
NEW_READERS = ("mla_proj_ms", "mla_assemble_ms", "mtp_ms", "shared_expert_ms")

TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "qk_head_dim": 24, "v_head_dim": 16, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "n_routed_experts": 8, "n_routed_experts_published": 16,
    "experts_held": [4, 8], "num_experts_per_tok": 4, "vocab_size": 96,
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
    c = joyai_llm_flash.counts(CFG, TRAIN8K)
    fwd = c["fwd_flops_per_token"]
    # products a token (multiply-adds), by hand from the published widths
    mla = (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
           + 32 * 128 * 2048)                                # 26.35M
    assert mla == 26_345_472
    dense = 3 * 2048 * 7168                                  # 44.04M
    expert = 3 * 2048 * 768                                  # 4.72M
    assert fwd["mla_projections"] == 2 * mla
    # QK^T over 192 channels and PV over 128, the causal half of 8,192 keys
    assert fwd["attention_kernel"] == 8192 * 32 * (192 + 128) == 83_886_080
    assert fwd["dense_mlp"] == 2 * dense
    # 8 of 256 chosen, 16 held: half an expert a token and layer, beside
    # the whole shared expert and the router's 256 outputs
    moe = 2 * (2048 * 256 + expert + expert * 8 * 16 / 256)
    assert fwd["moe"] == moe
    assert fwd["lm_head"] == 2 * 2048 * 16160
    # the module: eh_proj, a layer like the trunk's expert layers, and the
    # head again over 8,191 of a sequence's 8,192 positions
    mtp = (2 * 4096 * 2048 + 2 * mla + 83_886_080 + moe
           + 2 * 2048 * 16160 * 8191 / 8192)
    assert fwd["mtp"] == pytest.approx(mtp, rel=1e-12)
    whole = (5 * (2 * mla + 83_886_080) + 2 * dense + 4 * moe
             + 2 * 2048 * 16160 + mtp)
    assert c["flops_per_token"] == pytest.approx(3 * whole, rel=1e-12)
    assert whole == pytest.approx(1133e6, rel=2e-3)
    assert c["tokens_per_step"] == 16384
    # a step: 55.7 TFLOP, 44% of it in the attention kernels, a fifth in
    # the module
    step = c["flops_per_token"] * 16384
    assert step == pytest.approx(55.7e12, rel=2e-3)
    assert c["attention_layers"] == 6
    assert c["attn_flops_per_step"] == 3 * 83_886_080 * 16384 * 6
    assert c["attn_flops_per_step"] / step == pytest.approx(0.444, abs=2e-3)
    assert 3 * mtp * 16384 / step == pytest.approx(0.21, abs=1e-2)
    # q, k and their gradients at 6,144 a token, v, out and theirs at 4,096
    assert c["attn_bytes_per_step"] == (4 * 6144 + 4 * 4096) * 16384 * 6 * 2
    assert c["mla_assemble_bytes_per_step"] == 4 * 6144 * 16384 * 6 * 2
    # 8,192 pairs a layer on the held experts, 512 an expert, five layers;
    # three products a pair, forward and twice backward
    assert c["moe_blocks"] == 5
    assert c["experts_pairs_per_step"] == 5 * 8192
    assert c["experts_pairs_per_step"] / 5 / 16 == 512
    assert c["experts_flops_per_pair"] == 3 * 2 * expert
    assert c["pairs_routed_per_step"] == 5 * 16384 * 8
    assert joyai_llm_flash.work_per_step(CFG, TRAIN8K) == 16384


def test_the_configuration_keeps_every_published_key():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    rows = [json.loads(line) for line in open(CATALOG)]
    row = next(r for r in rows if r["name"] == "JoyAI-LLM-Flash")
    published = row["config"]
    differs = {k for k, v in published.items() if CFG.get(k, "absent") != v}
    assert differs == set(CFG["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert CFG["source"] == row["source_url"]
    for key in CFG["reduced"]:
        assert CFG[f"{key}_published"] == published[key]
    # no width is cut
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "num_attention_heads",
                "num_experts_per_tok", "n_shared_experts"):
        assert CFG[key] == published[key], key
    assert CFG["experts_held"] == [0, CFG["n_routed_experts"]] == [0, 16]
    assert CFG["vocab_size"] * 8 == CFG["vocab_size_published"]
    with open(os.path.join(bf.REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "joyai_llm_flash")
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == row["source_url"]
    assert len(entry["why"]) <= 200
    assert set(CFG["assumed"]) >= {
        "latent_attention", "rotary_embedding", "router",
        "multi_token_prediction", "feed_forward", "norms", "initializer",
        "optimizer", "precision", "input", "weights"}
    assert "16 chips" in CFG["deployment"]


def test_parameters_and_memory_of_the_cut():
    from benchmark.configs import joyai_llm_flash_reference as ref
    from paddle_tpu.models import joyai_flash
    specs = ref.weight_specs(CFG)
    n = sum(int(np.prod(shape)) for name, shape, _ in specs
            if not name.endswith(ref.FROZEN))
    assert n == 680_439_808
    assert joyai_flash.param_count(joyai_llm_flash.model_config(CFG)) == n
    assert 16 * n / 2 ** 30 == pytest.approx(10.14, rel=2e-3)
    assert len(specs) == 98 and sum(
        name.endswith(ref.FROZEN) for name, _, _ in specs) == 5


def test_the_adapter_refuses_what_the_builder_does_not_build():
    for key, value in (("scoring_func", "softmax"), ("n_group", 8),
                       ("rope_scaling", {"type": "yarn"}),
                       ("topk_method", "greedy")):
        with pytest.raises(ValueError, match="joyai_llm_flash"):
            joyai_llm_flash.model_config(dict(CFG, **{key: value}))


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
             "exit_ms", "collective_ms", "conv_mixer_ms", "qk_norm_ms"}
    assert has <= set(cell.readers) and not lacks & set(cell.readers)
    (batch,) = cell.generator.make_ring(cell.config,
                                        dict(cell.traffic, ring=1),
                                        2 ** 31 + 77)
    ids = batch["ids"]
    assert ids.shape == (2, 8192) and 0 <= ids.min() and ids.max() < 16160
    np.testing.assert_array_equal(batch["labels"][:, :-1, 0], ids[:, 1:])


def test_the_cell_is_in_the_benchmark_on_one_chip():
    # no totals: the next cell must not have to touch this test
    with open(os.path.join(bf.REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "joyai_llm_flash", "train8k", 1)
    assert "1/16 of deployed load" in cell["why"] and len(cell["why"]) <= 200
    assert [w["name"] for w in spec["workloads"] if w["chips"] == 4] == [
        "ernie_base.dp4_seq512"]
    metrics = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_READERS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "step_ms"
        assert metrics[name]["source"] == "device_trace"
    assert len({metrics[name]["layer"] for name in NEW_READERS}) == 1


def test_the_new_metrics_follow_the_set_up_metrics_which_stand_as_written():
    """PR 37's eight entries as it wrote them, in their order, after
    everything the benchmark had before them, and this PR's four straight
    after them. (PR 37's own test asks besides that nothing follow the eight;
    the driver takes new entries at the end of a list only, so no PR that adds
    a metric can keep that: tests/conftest.py says where the rule stands and
    takes that one test out of the collection.)"""
    import test_bench_setup_account as setup
    with open(os.path.join(bf.REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    names = [m["name"] for m in spec["per_layer"]]
    rows = {m["name"]: m for m in spec["per_layer"]}
    eight = ["setup_trace_s", "setup_lower_s", "setup_kernel_trace_s",
             "setup_restage_s", "stagings_per_step", "setup_cache_misses",
             "setup_first_run_s", "setup_coverage"]
    assert sorted(eight) == sorted(setup.READERS)
    at = names.index(eight[0])
    # after what the benchmark had when PR 37 added them (PR 35's last)
    assert names[at - 1] == "qk_norm_ms" and not set(names[:at]) & (
        set(eight) | set(NEW_READERS))
    assert names[at:at + 8] == eight
    assert names[at + 8:at + 12] == list(NEW_READERS)
    for name, (unit, better, _) in setup.READERS.items():
        assert rows[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "program_counter", "layer": setup.LAYER,
            "moves": "setup_s"}


def test_the_weights_are_one_draw_and_the_seed_decides_the_batches():
    from benchmark.configs import joyai_llm_flash_reference as ref
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

UNITS = ["embed", "blk0/attn/q_a", "blk0/attn/q_norm", "blk0/attn/kv_b",
         "blk0/attn/rope", "blk0/attn/assemble", "blk0/attn/kernel",
         "blk0/attn/o", "blk0/mlp/gate_up", "blk1/moe/shared/gate_up",
         "blk1/moe/experts", "lm_head", "mtp/eh_proj",
         "mtp/blk/attn/q_b", "mtp/blk/attn/assemble",
         "mtp/blk/moe/shared/down", "mtp/head", "mtp/head/mtp/head", None]
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
            "counts": joyai_llm_flash.counts(CFG, TRAIN8K),
            "peaks": peaks.peaks_for("TPU v5 lite")}


MTP_MS = sum(MS[12:18])


@pytest.mark.parametrize("name,expected", [
    ("mla_proj_ms", 2 + 4 + 8 + 128 + MS[13]),
    ("mla_assemble_ms", 32 + MS[14]),
    ("mtp_ms", MTP_MS),
    ("shared_expert_ms", 512 + MS[15]),
    ("rope_ms", 16), ("mlp_ms", 256),
    ("moe_ms", 512 + 1024 + MS[15]),
    # the trunk's head alone: the module's is under `mtp/head`
    ("lm_head_ms", 2048)])
def test_unit_readers_sum_their_units(name, expected):
    assert _reader(name)(_ctx()) == pytest.approx(expected)


@pytest.mark.parametrize("name", NEW_READERS)
def test_the_new_readers_count_a_loop_once_and_find_nothing_elsewhere(name):
    # a `while` is left out (its body's operations are in the trace)
    assert _reader(name)(_ctx(opcode="while")) is None
    # LFM2's and Nemotron's units and a program that writes no scopes give
    # nothing (but for the shared expert, which Nemotron has), and nothing
    # raises
    other = ["blk1/attn/qkv", "blk1/attn/qk_norm", "blk0.M/mamba/in_proj",
             "blk5.A/attn", "blk1/moe/experts", "lm_head"]
    assert _reader(name)(_ctx(other + [None] * 13)) is None
    ctx = _ctx()
    ctx["op_scopes"] = {}
    assert _reader(name)(ctx) is None


def test_the_module_s_prefix_is_a_path_and_not_a_substring():
    units = ["blk1/mtp_like", "smtp/head", "mtp", "mtp/loss"] + [None] * 15
    assert _reader("mtp_ms")(_ctx(units)) == pytest.approx(4 + 8)


def test_attn_roofline_counts_the_published_head_sizes():
    """All Mosaic time against QK^T over 192 and PV over 128 on the causal
    half, six layers: 24.7 TFLOP, 125.6 ms at the v5e's peak."""
    ctx = _ctx()
    label = xtrace.label("%custom-call.1 = bf16[64,8192,128] "
                         "custom-call(%q), custom_call_target="
                         "\"tpu_custom_call\"")
    ctx["trace"] = xtrace.Reduced(
        {"devices": {"/device:TPU:0": [[label, "mosaic", 0,
                                        int(400e9)]]}, "host": []}, 1)
    c, p = ctx["counts"], ctx["peaks"]
    assert c["attn_flops_per_step"] == pytest.approx(24.74e12, rel=1e-3)
    least = c["attn_flops_per_step"] / p["flops_per_s"]
    assert least > c["attn_bytes_per_step"] / p["hbm_bytes_per_s"]
    assert _reader("attn_roofline")(ctx) == pytest.approx(
        100 * least / 400.0)


# ---------------------------------------------------------------------------
# the whole cell on the CPU, tiny, float32
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = bf.make_tree(tmp_path_factory.mktemp("bench_joyai"))
    bench = os.path.join(root, "benchmark")
    cfg = dict(CFG, **TINY, name="tiny_joyai")
    with open(os.path.join(bench, "configs", "tiny_joyai.json"), "w") as f:
        json.dump(cfg, f)
    for suffix in ("", "_reference"):
        with open(os.path.join(bench, "configs",
                               f"tiny_joyai{suffix}.py"), "w") as f:
            f.write(f"from benchmark.configs.joyai_llm_flash{suffix} "
                    f"import *  # noqa: F401,F403\n")
    with open(os.path.join(bench, "traffic", "tiny_lm8k.json"), "w") as f:
        json.dump(dict(TRAIN8K, name="tiny_lm8k", batch=2, seq_len=32, ring=4,
                       warmup_blocks=2, trace_blocks=2), f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny_joyai", "source": "test", "reduced": [],
        "file": "benchmark/configs/tiny_joyai.json", "why": "test"})
    spec["workloads"].append({
        "name": "tiny_joyai.tiny_lm8k", "config": "tiny_joyai",
        "traffic": "tiny_lm8k", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_joyai.tiny_lm8k")
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


def _run(tree, build=None):
    cell = harness.load_cell("tiny_joyai.tiny_lm8k", tree)
    lines = []
    result = harness.run_cell(
        cell, 2 ** 31 + 39, 0.3, False, time.perf_counter(), build=build,
        device=dict(bf.FAKE_DEVICE),
        say=lambda *a, **k: lines.append(" ".join(map(str, a))))
    return cell, result, lines


def test_the_learning_rate_warms_up_linearly_to_its_peak(tree):
    """The configuration's rate (PR 39: at a constant 1e-4 the routers moved
    within a window and six seeds spread 1%), in the reference and, step by
    step, in the program."""
    from benchmark.configs import joyai_llm_flash_reference as ref
    opt = CFG["optimizer"]
    assert opt["warmup_steps"] == 2000 and opt["learning_rate"] == 1e-4
    assert ref.learning_rate(opt, 1) == pytest.approx(5e-8)
    assert ref.learning_rate(opt, 60) == pytest.approx(3e-6)
    assert ref.learning_rate(opt, 2000) == ref.learning_rate(opt, 5000) == 1e-4
    assert ref.learning_rate({"learning_rate": 0.5}, 1) == 0.5
    cell = harness.load_cell("tiny_joyai.tiny_lm8k", tree)
    system = cell.adapter.build(cell.config, cell.traffic, 1)
    (batch,) = cell.generator.make_ring(cell.config,
                                        dict(cell.traffic, ring=1), 7)
    system.start(cell.reference.make_weights(cell.config, 7))
    (rate,) = [v for v in system.main.list_vars()
               if v.name.startswith("lr_warmup")]
    for t in range(1, 7):           # four steps of warm-up, then the peak
        np.asarray(system.step(batch))
        got = float(np.asarray(system.scope.find_var(rate.name)).reshape(()))
        assert got == pytest.approx(
            ref.learning_rate(cell.config["optimizer"], t), rel=1e-6), t


def test_the_tiny_cell_agrees_with_its_plain_reference(tree):
    cell, result, lines = _run(tree)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"tokens_per_s", "step_ms", "setup_s"}
    # the counters the step fetched with its loss are in the registry: the
    # trunk's two expert layers and the module's, and the module's loss term
    ctx = {}
    assert 0 < _reader("pairs_held_share")(ctx) < 100
    assert _reader("expert_load_max")(ctx) >= 1.0
    series = get_registry().series()
    blocks = {s["labels"]["block"] for s in series
              if s["name"] == "moe/pairs_held"}
    assert {"blk1", "blk2", "blk_mtp"} <= blocks
    dropped = [s["value"] for s in series if s["name"] == "moe/dropped"]
    assert dropped and not any(dropped)
    (mtp,) = [s["value"] for s in series if s["name"] == "mtp/loss"]
    assert 1.0 < mtp < 7.0


def test_the_step_names_every_part_the_unit_readers_read(tree):
    cell = harness.load_cell("tiny_joyai.tiny_lm8k", tree)
    system = cell.adapter.build(cell.config, cell.traffic, 1)
    (batch,) = cell.generator.make_ring(cell.config,
                                        dict(cell.traffic, ring=1), 7)
    system.start(cell.reference.make_weights(cell.config, 7))
    np.asarray(system.step(batch))
    found = scopes.op_scopes(system.exe.compiled_step(system.main))
    units = {s.unit for s in found.values() if s.unit}
    for part in ("/attn/q_a", "/attn/q_b", "/attn/kv_a", "/attn/kv_b",
                 "/attn/rope", "/attn/assemble", "/attn/kernel", "/attn/o",
                 "/mlp/gate_up", "/mlp/down", "/moe/router", "/moe/dispatch",
                 "/moe/experts", "/moe/shared"):
        assert any(part in u for u in units), (part, sorted(units))
        assert any(part in u and u.startswith("mtp/blk") for u in units) or (
            part.startswith("/mlp/")), part
    assert {"lm_head", "loss", "embed", "final_norm", "mtp/eh_proj",
            "mtp/head"} <= units
    assert system.hbm()["argument_bytes"] > 0


def test_a_cell_whose_module_s_term_is_left_out_is_not_correct(
        tree, monkeypatch):
    """The timed path minimising L_main alone (weight 0 on the module's
    term): a trunk that trains, a module that does not, a lower loss."""
    from paddle_tpu.models import joyai_flash
    build = joyai_flash.build_pretrain_program

    def without(cfg, *args, **kwargs):
        cfg.mtp_loss_weight = 0.0
        return build(cfg, *args, **kwargs)

    monkeypatch.setattr(joyai_flash, "build_pretrain_program", without)
    _, result, lines = _run(tree)
    assert result["correct"] is False, lines
    assert any("FAILED" in line for line in lines)


def test_a_cell_whose_rotation_is_by_halves_is_not_correct(
        tree, monkeypatch):
    """The timed path pairing channels (j, j + 4) where the model pairs
    (2j, 2j + 1): other scores, other gradients."""
    from paddle_tpu.models import joyai_flash
    build = joyai_flash.build_pretrain_program

    def by_halves(cfg, *args, **kwargs):
        cfg.rope_interleave = False
        return build(cfg, *args, **kwargs)

    monkeypatch.setattr(joyai_flash, "build_pretrain_program", by_halves)
    _, result, lines = _run(tree)
    assert result["correct"] is False, lines
