"""Operation and byte counts against the numbers in ISSUE 23, the peaks table,
and the mechanical rules BENCHMARK.json has to keep."""
import json
import os
import re

import pytest

from benchmark import peaks
from benchmark.configs import ernie_base
from benchmark.layer_metrics import attn_roofline, mfu

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _json(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


CFG = _json("benchmark", "configs", "ernie_base.json")


@pytest.mark.parametrize("mix,per_token,attn_tflop", [
    ("seq512", 706.9e6, 1.855), ("seq128", 664.4e6, 0.4638),
    ("dp4_seq512", 706.9e6, 4 * 1.855)])
def test_ernie_operations_per_token(mix, per_token, attn_tflop):
    traffic = _json("benchmark", "traffic", f"{mix}.json")
    c = ernie_base.counts(CFG, traffic)
    t = traffic["seq_len"]
    assert c["flops_per_token"] == 6 * (
        12 * (4 * 768 ** 2 + 2 * 768 * 3072) + 768 * 30522) + 110592 * t
    assert c["flops_per_token"] == pytest.approx(per_token, rel=1e-3)
    assert c["attn_flops_per_step"] == pytest.approx(attn_tflop * 1e12,
                                                     rel=1e-3)
    # Q, K, V, O and their gradients, once each, bf16, 12 layers
    assert c["attn_bytes_per_step"] == 12 * 8 * c["tokens_per_step"] * 768 * 2
    assert c["attn_calls_per_step"] == 24
    assert ernie_base.work_per_step(CFG, traffic) == traffic["batch"] * t


def test_mfu_reproduces_pr22_from_its_rate():
    # (ledger, PR 22): 106,720 tokens/s/chip at T=128 read as mfu 35.992
    ctx = {"counts": ernie_base.counts(CFG, _json("benchmark", "traffic",
                                                  "seq128.json")),
           "values": {"tokens_per_s": 106720.0}, "config": CFG,
           "peaks": peaks.peaks_for("TPU v5 lite")}
    assert mfu.read(ctx) == pytest.approx(35.992, rel=1e-3)


@pytest.mark.parametrize("mix,which", [("seq512", "flops"),
                                       ("seq128", "bytes")])
def test_attention_roofline_bound(mix, which):
    traffic = _json("benchmark", "traffic", f"{mix}.json")
    ctx = {"counts": ernie_base.counts(CFG, traffic), "chips": 1,
           "peaks": peaks.peaks_for("TPU v5 lite")}
    least, bound = attn_roofline.bound(ctx)
    assert bound == which
    assert least == pytest.approx(
        max(ctx["counts"]["attn_flops_per_step"] / 197e12,
            ctx["counts"]["attn_bytes_per_step"] / 819e9))


def test_peaks_table_refuses_an_unknown_kind():
    assert peaks.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert "source" in peaks.peaks_for("TPU v5 lite")
    with pytest.raises(peaks.UnknownDevice, match="cpu"):
        peaks.peaks_for("cpu")


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_keeps_the_contract_s_mechanical_rules():
    spec = _json("BENCHMARK.json")
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    cells = {w["name"] for w in spec["workloads"]}
    configs = {c["name"] for c in spec["configs"]}
    assert len(cells) == len(spec["workloads"])
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
        assert os.path.exists(os.path.join(REPO, c["file"]))
        assert _json(c["file"])["reduced"] == c["reduced"]
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(
        1, len(spec["workloads"]) // 4)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "layer_metrics", m["name"] + ".py"))
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    # every cell reports setup_s, another end-to-end metric and a layer metric
    for cell in cells:
        mine = [m for m in spec["end_to_end"]
                if cell in m.get("workloads", [cell])]
        assert len(mine) >= 2
