"""The `nemotron3_nano` configuration and the cells PR 26 added: its counts
against a hand count, both new cells found by name, each new reader on a
hand-made scope map or registry series, and the whole cell driven on the CPU
at a tiny size in float32 against its plain reference."""
import importlib
import json
import os
import time

import numpy as np
import pytest

import bench_fixtures as bf
from benchmark import harness, peaks, scope_join, xtrace
from benchmark.configs import nemotron3_nano
from paddle_tpu.observability import scopes

CFG_PATH = os.path.join(bf.REPO, "benchmark", "configs",
                        "nemotron3_nano.json")
with open(CFG_PATH) as f:
    CFG = json.load(f)
with open(os.path.join(bf.REPO, "benchmark", "traffic", "train8k.json")) as f:
    TRAIN8K = json.load(f)

TINY = {
    "hidden_size": 32, "hybrid_override_pattern": "ME*", "head_dim": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "ssm_state_size": 16,
    "n_groups": 2, "chunk_size": 8, "n_routed_experts": 4,
    "n_routed_experts_published": 8, "experts_held": [2, 4],
    "num_experts_per_tok": 2, "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 48, "vocab_size": 64,
    "num_hidden_layers": 3, "initializer_range": 0.2, "amp_dtype": None,
    "reference": {"follow_steps": 3, "head_rows": 8},
    # float32 against float32 on the CPU: rounding only
    "limits": {"loss_gap": 1e-4, "grad_gap": 1e-3, "update_gap": 1e-2},
}


def _reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------

def test_operations_per_token_against_a_hand_count():
    c = nemotron3_nano.counts(CFG, TRAIN8K)
    fwd = c["fwd_flops_per_token"]
    # Mamba-2: in_proj 2688 x (4096 + 6144 + 64), out_proj 4096 x 2688, the
    # 4-tap convolution over 6144 channels, the scan (half a 128-chunk of
    # C B^T over 8 groups x 128 and of the [128, 128] x [128, 64] product
    # over 64 heads; the state in and out, 64 heads x 64 x 128 each)
    scan = 128 * 128 * 8 + 128 * 64 * 64 + 2 * 2 * 64 * 128 * 64
    assert fwd["mamba"] == (2 * 2688 * 10304 + 2 * 4096 * 2688
                            + 2 * 4 * 6144 + scan)
    assert fwd["mamba"] == pytest.approx(80.2e6, rel=2e-3)
    # attention at T 8,192, causal: q, k, v 2688 x 4608, o 4096 x 2688
    assert fwd["attention"] == (2 * 2688 * 4608 + 2 * 4096 * 2688
                                + 4 * 8192 * 4096 // 2)
    assert fwd["attention"] == pytest.approx(113.9e6, rel=1e-3)
    # experts: router 2688 x 128, shared 2 x 2688 x 3712, and 6 x 8/128 of a
    # routed expert (2 x 2688 x 1856) a token
    routed = 6 * 8 / 128 * 2 * 2 * 2688 * 1856
    assert routed == pytest.approx(7.48e6, rel=1e-3)
    assert fwd["moe"] == pytest.approx(
        2 * 2688 * 128 + 2 * 2 * 2688 * 3712 + routed)
    assert fwd["moe"] == pytest.approx(48.1e6, rel=1e-3)
    assert fwd["lm_head"] == 2 * 2688 * 16384
    total = 4 * fwd["mamba"] + fwd["attention"] + 4 * fwd["moe"] + \
        fwd["lm_head"]
    assert c["flops_per_token"] == pytest.approx(3 * total)
    assert total == pytest.approx(715e6, rel=2e-3)
    assert c["tokens_per_step"] == 16384
    assert nemotron3_nano.work_per_step(CFG, TRAIN8K) == 16384
    # a held expert sees about 768 pairs a step
    assert c["experts_pairs_per_step"] / 4 / 8 == 768
    assert c["pairs_routed_per_step"] == 4 * 16384 * 6
    assert c["experts_flops_per_step"] == pytest.approx(
        4 * 6144 * 3 * 2 * 2 * 2688 * 1856)
    assert c["ssd_flops_per_step"] == 3 * scan * 16384 * 4
    # the attention kernels: QK^T and PV over the causal half of 8,192 x
    # 8,192 for 32 heads of 128, forward and twice that backward, one block;
    # Q, O, dQ, dO at 4,096 wide and K, V, dK, dV at 256, bf16
    assert c["attn_flops_per_step"] == 3 * 2 * 2 * 8192 * 4096 // 2 * 16384
    assert c["attn_flops_per_step"] == pytest.approx(3.30e12, rel=2e-3)
    assert c["attn_bytes_per_step"] == 4 * (4096 + 256) * 16384 * 2
    # x, B, C, dt in and y out, bf16, three passes, four layers
    assert c["ssd_bytes_per_step"] == 3 * 2 * (4096 + 2048 + 64 + 4096) \
        * 16384 * 4


def test_the_configuration_keeps_every_published_width():
    rows = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] \
        if os.path.exists("/opt/skills/guides/model-configs/"
                          "architectures.jsonl") else []
    published = next((r["config"] for r in rows
                      if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"),
                     None)
    if published is None:
        pytest.skip("the catalog is not on this machine")
    differs = {k for k, v in published.items() if CFG.get(k, "absent") != v}
    assert differs == set(CFG["reduced"])
    assert CFG["n_routed_experts_published"] == published["n_routed_experts"]
    assert CFG["vocab_size_published"] == published["vocab_size"]
    assert CFG["hybrid_override_pattern"] == \
        published["hybrid_override_pattern"][:CFG["num_hidden_layers"]]
    with open(os.path.join(bf.REPO, "BENCHMARK.json")) as f:
        row = next(c for c in json.load(f)["configs"]
                   if c["name"] == "nemotron3_nano")
    assert row["reduced"] == CFG["reduced"]


def test_parameters_and_memory_of_the_cut():
    from benchmark.configs import nemotron3_nano_reference as ref
    n = sum(int(np.prod(shape)) for _, shape, _ in ref.weight_specs(CFG))
    assert n == pytest.approx(667.0e6, rel=1e-3)
    assert 16 * n / 2 ** 30 == pytest.approx(9.94, rel=2e-3)


# ---------------------------------------------------------------------------
# the cells are found
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,chips,has,lacks", [
    ("nemotron3_nano.train8k", 1,
     {"mamba_ms", "ssd_ms", "ssd_roofline", "moe_ms", "moe_dispatch_ms",
      "experts_roofline", "lm_head_ms", "expert_load_max",
      "pairs_held_share", "mfu", "scope_coverage", "step_hbm", "attn_ms",
      "attn_roofline"},
     {"head_ms", "collective_ms", "rows_ms"}),
    ("ernie_base.dp4_seq512", 4,
     {"collective_ms", "collective_exposed_ms", "attn_ms", "attn_roofline",
      "head_ms", "mfu"}, {"mamba_ms", "rows_ms"})])
def test_load_cell_finds_the_new_cells(name, chips, has, lacks):
    cell = harness.load_cell(name)
    assert cell.chips == chips
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "step_ms", "setup_s"}
    assert has <= set(cell.readers) and not lacks & set(cell.readers)
    ring = cell.generator.make_ring(cell.config, dict(cell.traffic, ring=2),
                                    2 ** 31 + 77)
    assert len(ring) == 2


def test_lm_zipf_batches():
    cell = harness.load_cell("nemotron3_nano.train8k")
    make = cell.generator.make_ring
    a = make(cell.config, dict(cell.traffic, ring=2), 2 ** 31 + 5)
    b = make(cell.config, dict(cell.traffic, ring=2), 2 ** 31 + 5)
    c = make(cell.config, dict(cell.traffic, ring=2), 2 ** 31 + 6)
    assert all(np.array_equal(x["ids"], y["ids"]) for x, y in zip(a, b))
    assert not np.array_equal(a[0]["ids"], c[0]["ids"])
    assert not np.array_equal(a[0]["ids"], a[1]["ids"])
    ids, labels = a[0]["ids"], a[0]["labels"]
    assert ids.shape == (2, 8192) and labels.shape == (2, 8192, 1)
    assert ids.dtype == labels.dtype == np.int32
    assert 0 <= ids.min() and ids.max() < 16384
    # every position's label is the next position's id
    assert np.array_equal(labels[:, :-1, 0], ids[:, 1:])
    # rank = id: id 0 is about 1 / H(16384) = 9.7% of the draws, and the
    # first eighth of the ids as many again as the last eighth many times
    assert 0.08 < np.mean(ids == 0) < 0.12
    assert np.mean(ids < 2048) > 20 * np.mean(ids >= 16384 - 2048)
    assert cell.traffic["generator_params"] == {"zipf_exponent": 1.0}


def test_the_weights_are_one_draw_and_the_seed_decides_the_batches():
    """The pairs a chip holds follow the routers' draw, and the step's time
    the pairs: the cell's weights are `weights_seed`'s whatever `--seed`
    is; a configuration without the key draws them from the seed."""
    from benchmark.configs import nemotron3_nano_reference as ref
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

UNITS = ["embed", "blk0.M/norm", "blk0.M/mamba/in_proj", "blk0.M/mamba/ssd",
         "blk0.M/mamba/ssd/blk0.M/mamba/ssd", "blk0.M/mamba/out_proj",
         "blk1.E/moe/router", "blk1.E/moe/dispatch", "blk1.E/moe/experts",
         "blk1.E/moe/shared", "blk1.E/moe/combine", "blk2.A/attn",
         "lm_head", "loss", None]


def _ctx(ms_by_unit):
    """A traced step whose operation i ran `ms_by_unit[i]` ms in UNITS[i]."""
    found, events, at = {}, [], 0
    for i, (unit, ms) in enumerate(zip(UNITS, ms_by_unit)):
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
            "counts": nemotron3_nano.counts(CFG, TRAIN8K),
            "peaks": peaks.peaks_for("TPU v5 lite")}


MS = [1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384]


@pytest.mark.parametrize("name,expected", [
    ("mamba_ms", 4 + 8 + 16 + 32), ("ssd_ms", 8 + 16),
    ("moe_ms", 64 + 128 + 256 + 512 + 1024),
    ("moe_dispatch_ms", 64 + 128 + 1024), ("lm_head_ms", 4096 + 8192)])
def test_unit_readers_sum_their_units(name, expected):
    assert _reader(name)(_ctx(MS)) == pytest.approx(expected)


def test_unit_readers_count_a_loop_once():
    """The trace holds a `while` and, inside its span, its body's
    operations; the new readers sum the body and leave the loop out (the
    readers that were there, `scope_join.device_ms`, sum both). A fusion
    with a zero-length custom call inside its span is no loop."""
    def op(name, opcode, unit):
        text = f"%{name} = f32[8] {opcode}(%x)"
        return xtrace.label(text), scopes.OpScope(
            name=name, text=text, phase="bwd", unit=unit, op_types=("mul",),
            has_dot=True)
    ops = [op("while.3", "while", "lm_head"), op("fusion.1", "fusion",
                                                  "lm_head"),
           op("fusion.2", "fusion", "lm_head"),
           op("while.4", "while", "blk1.E/moe/experts"),
           op("fusion.5", "fusion", "blk1.E/moe/experts"),
           op("fusion.6", "fusion", "loss"),
           op("custom-call.7", "custom-call", "loss")]
    ms = 1_000_000
    spans = [(0, 10), (0, 4), (4, 6), (10, 5), (11, 3), (15, 2), (16, 0)]
    events = [[label, "xla", lo * ms, dur * ms]
              for (label, _), (lo, dur) in zip(ops, spans)]
    ctx = {"trace": xtrace.Reduced({"devices": {"/device:TPU:0": events},
                                    "host": []}, 1),
           "op_scopes": {s.name: s for _, s in ops}}
    assert _reader("lm_head_ms")(ctx) == pytest.approx(4 + 6 + 2)
    assert _reader("moe_ms")(ctx) == pytest.approx(3)
    assert scope_join.unit_ms(ctx, ("lm_head", "loss")) == pytest.approx(22)


def test_rooflines_are_least_time_over_measured():
    ctx = _ctx(MS)
    c, p = ctx["counts"], ctx["peaks"]
    ssd_least = max(c["ssd_flops_per_step"] / p["flops_per_s"],
                    c["ssd_bytes_per_step"] / p["hbm_bytes_per_s"])
    assert ssd_least == c["ssd_bytes_per_step"] / p["hbm_bytes_per_s"]
    assert _reader("ssd_roofline")(ctx) == pytest.approx(
        100 * ssd_least / 24e-3)
    ctx["registry_series"] = []                # the pairs expected
    least = c["experts_flops_per_step"] / p["flops_per_s"]
    assert least > c["experts_bytes_per_step"] / p["hbm_bytes_per_s"]
    assert _reader("experts_roofline")(ctx) == pytest.approx(
        100 * least / 256e-3)
    # the pairs really held, where the program reports them
    ctx["registry_series"] = _series([[900, 700, 800, 672] * 2] * 4)
    assert _reader("experts_roofline")(ctx) == pytest.approx(
        100 * least * (4 * 6144 / c["experts_pairs_per_step"]) / 256e-3)


def _series(tokens_by_block, routed=16384 * 6):
    out = []
    for i, tokens in enumerate(tokens_by_block):
        block = f"blk{2 * i + 1}"
        out += [{"name": "moe/tokens_per_expert", "type": "gauge",
                 "labels": {"block": block, "expert": str(e)}, "value": n}
                for e, n in enumerate(tokens)]
        out += [{"name": "moe/pairs_held", "type": "gauge",
                 "labels": {"block": block}, "value": sum(tokens)},
                {"name": "moe/pairs_routed", "type": "gauge",
                 "labels": {"block": block}, "value": routed}]
    return out


def test_counter_readers():
    even = [768] * 8
    skewed = [1536] + [658] * 6 + [660]
    ctx = {"registry_series": _series([even, skewed, even, even])}
    assert _reader("pairs_held_share")(ctx) == pytest.approx(
        100 * 4 * 6144 / (4 * 16384 * 6))
    assert _reader("pairs_held_share")(ctx) == pytest.approx(6.25)
    assert _reader("expert_load_max")(ctx) == pytest.approx(1536 / 768)


@pytest.mark.parametrize("name", [
    "mamba_ms", "ssd_ms", "ssd_roofline", "moe_ms", "moe_dispatch_ms",
    "experts_roofline", "lm_head_ms", "expert_load_max", "pairs_held_share"])
def test_a_program_without_the_names_gives_nothing(name):
    ctx = _ctx(MS)
    ctx["op_scopes"] = {k: v._replace(unit="bert_layer_0")
                        for k, v in ctx["op_scopes"].items()}
    ctx["registry_series"] = []
    assert _reader(name)(ctx) is None
    ctx.pop("_scope_of", None)
    ctx["op_scopes"] = {}
    assert _reader(name)(ctx) is None


# ---------------------------------------------------------------------------
# the whole cell on the CPU, tiny, float32
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = bf.make_tree(tmp_path_factory.mktemp("bench_nemotron"))
    bench = os.path.join(root, "benchmark")
    cfg = dict(CFG, **TINY, name="tiny_nemotron")
    with open(os.path.join(bench, "configs", "tiny_nemotron.json"), "w") as f:
        json.dump(cfg, f)
    for suffix in ("", "_reference"):
        with open(os.path.join(bench, "configs",
                               f"tiny_nemotron{suffix}.py"), "w") as f:
            f.write(f"from benchmark.configs.nemotron3_nano{suffix} "
                    f"import *  # noqa: F401,F403\n")
    with open(os.path.join(bench, "traffic", "tiny_lm.json"), "w") as f:
        json.dump(dict(TRAIN8K, name="tiny_lm", batch=2, seq_len=32, ring=4,
                       warmup_blocks=2, trace_blocks=2), f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny_nemotron", "source": "test", "reduced": [],
        "file": "benchmark/configs/tiny_nemotron.json", "why": "test"})
    spec["workloads"].append({
        "name": "tiny_nemotron.tiny_lm", "config": "tiny_nemotron",
        "traffic": "tiny_lm", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "nemotron3_nano.train8k" in m.get("workloads", []):
            m["workloads"].append("tiny_nemotron.tiny_lm")
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


def _run(tree, build=None):
    cell = harness.load_cell("tiny_nemotron.tiny_lm", tree)
    lines = []
    result = harness.run_cell(
        cell, 2 ** 31 + 26, 0.3, False, time.perf_counter(), build=build,
        device=dict(bf.FAKE_DEVICE),
        say=lambda *a, **k: lines.append(" ".join(map(str, a))))
    return cell, result, lines


def test_the_tiny_cell_agrees_with_its_plain_reference(tree):
    from paddle_tpu.observability import get_registry
    cell, result, lines = _run(tree)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"tokens_per_s", "step_ms", "setup_s"}
    # the counters the step fetched with its loss are in the registry
    ctx = {}
    share = _reader("pairs_held_share")(ctx)
    assert 0 < share < 100
    assert _reader("expert_load_max")(ctx) >= 1.0
    dropped = [s["value"] for s in get_registry().series()
               if s["name"] == "moe/dropped"]
    assert dropped and not any(dropped)


def test_the_step_names_every_part_the_unit_readers_read(tree):
    cell = harness.load_cell("tiny_nemotron.tiny_lm", tree)
    system = cell.adapter.build(cell.config, cell.traffic, 1)
    (batch,) = cell.generator.make_ring(cell.config,
                                        dict(cell.traffic, ring=1), 7)
    system.start(cell.reference.make_weights(cell.config, 7))
    system.step(batch)
    found = scopes.op_scopes(system.exe.compiled_step(system.main))
    units = {s.unit for s in found.values() if s.unit}
    for part in ("/mamba/in_proj", "/mamba/conv", "/mamba/ssd",
                 "/mamba/norm", "/mamba/out_proj", "/moe/router",
                 "/moe/dispatch", "/moe/experts", "/moe/shared",
                 "/moe/combine", "/attn"):
        assert any(part in u for u in units), (part, sorted(units))
    assert {"lm_head", "loss", "embed"} <= units
    assert system.hbm()["argument_bytes"] > 0


class _HalfTheExpertsSilent:
    """The timed path with the second half of the held experts' output
    matrices zeroed: part of the mathematics left out."""

    def __init__(self, system):
        self._s = system

    def __getattr__(self, name):
        return getattr(self._s, name)

    def start(self, weights):
        weights = {k: (v.at[v.shape[0] // 2:].set(0.0)
                       if k.endswith(".moe.w2") else v)
                   for k, v in weights.items()}
        self._s.start(weights)


def test_a_cell_with_experts_left_out_is_not_correct(tree):
    cell = harness.load_cell("tiny_nemotron.tiny_lm", tree)
    _, result, lines = _run(
        tree, build=lambda *a: _HalfTheExpertsSilent(cell.adapter.build(*a)))
    assert result["correct"] is False, lines
