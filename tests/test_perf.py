"""Perf-attribution ledger, calibration cache, roofline CLI, bench gate.

The observability tentpole's acceptance surface on the CPU backend:
XLA cost extraction (the CPU cost model returns real flops/bytes) and
the analytic IR fallback, attribute() math against a crafted
calibration, the compile-time ledger hookup in all three dispatch sites
(perf/* gauges appear for any compiled program; step records gain
achieved_tflops), the disk calibration cache (miss → write, hit →
source "cache", --recalibrate bypass), the roofline CLI on a canned
chrome trace (+ diff mode), and perf_gate pass/fail/exit-2 on
synthetically perturbed bench docs in every accepted wrapper format.
"""
import gzip
import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.observability import calibrate, perf
from paddle_tpu.observability.registry import get_registry
from paddle_tpu.observability.steps import get_step_profiler
from paddle_tpu.tools import perf_gate, roofline


@pytest.fixture(autouse=True)
def _fresh_ledger():
    perf.get_ledger().reset()
    yield
    perf.get_ledger().reset()


def _tiny_train_program(width=8):
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        x = layers.data("x", [width], dtype="float32")
        y = layers.fc(x, size=4)
        loss = layers.reduce_mean(y * y)
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    return main_p, startup, loss


# -- extraction -----------------------------------------------------------

def test_cost_from_executable_cpu_matmul():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a, b: a @ b)
    lowered = f.lower(jnp.ones((64, 32)), jnp.ones((32, 16)))
    compiled = lowered.compile()
    for exe in (lowered, compiled):
        cost = perf.cost_from_executable(exe)
        assert cost is not None
        assert cost["flops"] == pytest.approx(2 * 64 * 32 * 16)
        assert cost["bytes_accessed"] > 0
    # memory_analysis: args + out − alias (nothing donated here)
    mem = perf.memory_from_executable(compiled)
    assert mem == (64 * 32 + 32 * 16 + 64 * 16) * 4


def test_cost_from_executable_normalizes_keys_and_rejects_empty():
    class DictExe:
        def cost_analysis(self):
            return {"flops": 5.0, "bytes accessed": 7.0}

    class RaisingExe:
        def cost_analysis(self):
            raise NotImplementedError("Unimplemented on this backend")

    class ZeroExe:
        def cost_analysis(self):
            return {"flops": 0.0, "bytes accessed": 0.0}

    assert perf.cost_from_executable(DictExe()) == {
        "flops": 5.0, "bytes_accessed": 7.0, "transcendentals": 0.0}
    assert perf.cost_from_executable(RaisingExe()) is None
    assert perf.cost_from_executable(ZeroExe()) is None
    assert perf.cost_from_executable(None) is None


def test_analytic_cost_counts_matmul_flops_and_backward():
    main_p, _, _ = _tiny_train_program(width=8)
    feed = {"x": np.ones((4, 8), dtype=np.float32)}
    cost = perf.analytic_cost(main_p, feed)
    # fc is one mul [4,8]x[8,4]; minimize adds a backward pass → ×3
    assert cost["flops"] == pytest.approx(3 * 2 * 4 * 8 * 4)
    assert cost["bytes_accessed"] > 0

    # forward-only program: no ×3
    fwd_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(fwd_p, startup):
        x = layers.data("x", [8], dtype="float32")
        layers.fc(x, size=4)
    fwd = perf.analytic_cost(fwd_p, feed)
    assert fwd["flops"] == pytest.approx(2 * 4 * 8 * 4)


# -- attribute() math -----------------------------------------------------

def _calib(mm=100.0, stream=1000.0, peak=200e12):
    return calibrate.Calibration(
        device_kind="test", on_tpu=True, matmul_tflops=mm,
        stream_gbs=stream, peak_flops=peak, source="measured")


def test_attribute_known_numbers():
    att = perf.attribute(flops=1e12, bytes_accessed=1e9, seconds=0.5,
                         calib=_calib())
    assert att["achieved_tflops"] == pytest.approx(2.0)
    assert att["achieved_gbs"] == pytest.approx(2.0)
    assert att["mfu"] == pytest.approx(1e12 / 0.5 / 200e12)
    # floor = max(1e12/100e12 s, 1e9/1000e9 s) = max(0.01, 0.001)
    assert att["roofline_fraction"] == pytest.approx(0.01 / 0.5)
    assert att["bound"] == "matmul"


def test_attribute_memory_bound_and_uncapped_fraction():
    att = perf.attribute(bytes_accessed=4e9, seconds=0.002, calib=_calib())
    assert att["bound"] == "memory"
    # floor 4e9/1000e9 = 4 ms against a 2 ms wall: fraction above 1.0
    # stays uncapped (VMEM re-read semantics — see docs/migration.md)
    assert att["roofline_fraction"] == pytest.approx(2.0)


# -- ledger + dispatch sites ----------------------------------------------

def test_executor_run_registers_and_sets_gauges():
    main_p, startup, loss = _tiny_train_program()
    feed = {"x": np.ones((2, 8), dtype=np.float32)}
    exe = fluid.Executor(fluid.TPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(3):
            exe.run(main_p, feed=feed, fetch_list=[loss])
    key = f"0x{id(main_p):x}"
    snap = perf.get_ledger().snapshot()
    mine = {k: v for k, v in snap.items() if k.startswith(key)}
    assert mine, f"no ledger entry for {key} in {list(snap)}"
    entry = next(iter(mine.values()))
    assert entry["source"] in ("xla", "lowered", "analytic")
    assert entry["flops"] > 0
    # live gauges for THIS program reached the shared registry
    series = get_registry().snapshot()
    for g in ("perf/mfu", "perf/roofline_fraction", "perf/achieved_tflops",
              "perf/achieved_gbs"):
        assert any(k.startswith(g + "{") and key in k for k in series), \
            f"{g} gauge missing for {key}"


def test_step_records_carry_achieved_tflops():
    main_p, startup, loss = _tiny_train_program()
    feed = {"x": np.ones((2, 8), dtype=np.float32)}
    exe = fluid.Executor(fluid.TPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(3):
            exe.run(main_p, feed=feed, fetch_list=[loss])
    key = f"0x{id(main_p):x}"
    recs = [r for r in get_step_profiler().records()
            if r.get("program") == key and not r.get("compile")]
    assert recs
    assert any("achieved_tflops" in r for r in recs)


def test_scan_driver_registers_whole_scan_cost():
    main_p, startup, loss = _tiny_train_program()
    feed = {"x": np.ones((2, 8), dtype=np.float32)}
    exe = fluid.Executor(fluid.TPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.train_scanned(main_p, reader=lambda: iter([feed] * 8),
                          scan_steps=4, fetch_list=[loss])
    entries = [v for k, v in perf.get_ledger().snapshot().items()
               if k.startswith(f"0x{id(main_p):x}") and v["steps"] == 4]
    assert entries, "no steps=4 scan entry registered"


def test_ledger_disabled_by_env(monkeypatch):
    monkeypatch.setenv("PDTPU_PERF_LEDGER", "0")
    assert not perf.enabled()
    main_p, _, _ = _tiny_train_program()
    out = perf.get_ledger().register("0xdead", "sig", program=main_p,
                                     feed={"x": np.ones((2, 8), "f4")})
    assert out is None
    assert perf.get_ledger().snapshot() == {}


def test_planner_estimate_plan_predicts_flops_and_bytes():
    from paddle_tpu import planner

    main_p, startup, loss = _tiny_train_program()
    # batch divisible by the conftest's 8-device mesh, so the measured
    # (compile-backed) path runs rather than the analytic fallback
    feed = {"x": np.ones((8, 8), dtype=np.float32)}
    exe = fluid.Executor(fluid.TPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        plan = planner.estimate_plan(
            planner.Plan(0, "none", 1), main_p, feed, loss.name)
    assert plan.source == "measured"
    assert plan.predicted_flops and plan.predicted_flops > 0
    assert plan.predicted_bytes_accessed and plan.predicted_bytes_accessed > 0
    assert plan.to_dict()["predicted_flops"] == plan.predicted_flops


# -- calibration cache ----------------------------------------------------

def test_calibration_cache_miss_write_hit_and_recalibrate(
        tmp_path, monkeypatch):
    monkeypatch.setenv("PDTPU_CALIBRATION_DIR", str(tmp_path))
    calibrate.reset()
    try:
        c1 = calibrate.get_calibration()
        # CPU backend: placeholder rates, measured without dispatching
        assert c1.source == "placeholder"
        assert c1.floors == (1.0, 10.0)
        assert c1.peak_flops == 1e12
        path = calibrate.cache_path()
        assert os.path.exists(path)
        assert str(tmp_path) in path

        # process memo: same object, no re-read
        assert calibrate.get_calibration() is c1

        # fresh process simulation: memo dropped → disk hit
        calibrate.reset()
        c2 = calibrate.get_calibration()
        assert c2.source == "cache"
        assert c2.floors == c1.floors

        # tampered cache proves the hit really reads the file
        doc = json.load(open(path))
        doc["matmul_tflops"] = 42.5
        json.dump(doc, open(path, "w"))
        calibrate.reset()
        assert calibrate.get_calibration().matmul_tflops == 42.5

        # --recalibrate: bypasses the tampered cache and rewrites it
        c3 = calibrate.get_calibration(recalibrate=True)
        assert c3.source == "placeholder"
        assert c3.matmul_tflops == 1.0
        assert json.load(open(path))["matmul_tflops"] == 1.0

        # a cache for another device kind is ignored
        os.replace(path, calibrate.cache_path(device_kind="other-chip"))
        calibrate.reset()
        assert calibrate.get_calibration().source == "placeholder"
    finally:
        calibrate.reset()


# -- eager op profile export ----------------------------------------------

def test_export_op_profile_reaches_registry():
    from paddle_tpu import profiler as prof

    timer = prof._OpTimer()
    timer.times["op_perf_test_a"] = 0.25
    timer.counts["op_perf_test_a"] = 3
    timer.times["op_perf_test_b"] = 0.5
    timer.counts["op_perf_test_b"] = 1
    prof.export_op_profile(timer)
    reg = get_registry()
    assert reg.gauge("eager/op_ms", op="op_perf_test_a").value == \
        pytest.approx(250.0)
    assert reg.counter("eager/op_calls", op="op_perf_test_a").value == 3
    assert reg.counter("eager/op_calls", op="op_perf_test_b").value == 1
    # cumulative: a second export adds, not overwrites
    prof.export_op_profile(timer)
    assert reg.gauge("eager/op_ms", op="op_perf_test_a").value == \
        pytest.approx(500.0)


# -- roofline CLI ---------------------------------------------------------

def _canned_trace(kernels):
    """Chrome trace with TPU process metadata and an 'XLA Ops' thread;
    kernels = [(name, dur_us, bytes, flops), ...]."""
    ev = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 1, "tid": 2, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 9, "name": "process_name",
         "args": {"name": "python host"}},
        # host-side event that must NOT be counted
        {"ph": "X", "pid": 9, "tid": 1, "name": "hostwork", "dur": 99999.0},
    ]
    ts = 0.0
    for name, dur, by, fl in kernels:
        ev.append({"ph": "X", "pid": 1, "tid": 2, "name": name, "ts": ts,
                   "dur": dur, "args": {"bytes_accessed": by,
                                        "model_flops": fl}})
        ts += dur
    return {"traceEvents": ev}


def test_kernel_table_math_and_tail():
    tr = _canned_trace([
        ("fusion.1", 1000.0, 1e9, 5e8),    # 1 ms, 1000 GB/s, 0.5 TF/s
        ("fusion.2", 2000.0, 1e9, 0.0),    # 2 ms, 500 GB/s
        ("tiny.3", 10.0, 1e6, 0.0),        # below cutoff → tail
    ])
    tab = roofline.kernel_table(tr, floors=(100.0, 500.0), cutoff_ms=0.5)
    assert tab["device_ms_per_step"] == pytest.approx(3.01)
    assert [r["kernel"] for r in tab["kernels"]] == ["fusion.2", "fusion.1"]
    top = {r["kernel"]: r for r in tab["kernels"]}
    assert top["fusion.1"]["gbs"] == pytest.approx(1000.0)
    assert top["fusion.1"]["tfs"] == pytest.approx(0.5)
    # util vs bound: max(1000/500, 0.5/100) = 2.0 — above 1.0 is legal
    assert top["fusion.1"]["util_vs_bound"] == pytest.approx(2.0)
    assert top["fusion.2"]["util_vs_bound"] == pytest.approx(1.0)
    assert tab["tail"]["n_kernel_names"] == 1
    assert tab["aggregate_gbs"] > 0


def test_roofline_cli_json_and_diff(tmp_path, capsys):
    a = tmp_path / "a.trace.json.gz"
    with gzip.open(a, "wt") as f:
        json.dump(_canned_trace([("fusion.1", 1000.0, 1e9, 0.0),
                                 ("fusion.2", 500.0, 5e8, 0.0)]), f)
    b = tmp_path / "b.trace.json"   # plain json also accepted
    b.write_text(json.dumps(_canned_trace(
        [("fusion.1", 2000.0, 1e9, 0.0), ("fusion.9", 100.0, 1e8, 0.0)])))

    rc = roofline.main([str(a), "--json", "--matmul-tflops", "100",
                        "--stream-gbs", "500", "--cutoff-ms", "0.2"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["floors"]["source"] == "flags"
    assert {r["kernel"] for r in doc["kernels"]} == {"fusion.1", "fusion.2"}

    rc = roofline.main([str(a), "--diff", str(b), "--json",
                        "--matmul-tflops", "100", "--stream-gbs", "500",
                        "--cutoff-ms", "0.05"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    movers = {m["kernel"]: m for m in doc["diff"]["movers"]}
    assert movers["fusion.1"]["delta_ms"] == pytest.approx(1.0)
    assert movers["fusion.1"]["status"] == "both"
    assert "fusion.2" in doc["diff"]["only_in_a"]
    assert "fusion.9" in doc["diff"]["only_in_b"]

    assert roofline.main([str(tmp_path / "missing.json")]) == 2


# -- perf gate ------------------------------------------------------------

def _bench_doc(**over):
    doc = {"metric": "m", "value": 100.0, "unit": "u", "vs_baseline": 1.0,
           "extra": {"mfu": 0.40, "deepfm_rate": 200000.0,
                     "nmt_big_rate": 50000.0, "nmt_big_mfu": 0.36,
                     "resnet50_imgs_per_sec_per_chip": 2400.0,
                     "resnet50_mfu": 0.15, "resnet50_roofline_frac": 0.67,
                     "ps_embedding": {"prefetch_speedup": 1.5,
                                      "staleness0_bitwise_equal": True,
                                      "push_depth1_bitwise_equal": True,
                                      "hot_cache_bitwise_equal": True},
                     "dispatch_overhead": {
                         "scan_overhead_pct_of_run": 4.0}}}
    for path, v in over.items():
        cur = doc
        parts = path.split(".")
        for p in parts[:-1]:
            cur = cur[p]
        cur[parts[-1]] = v
    return doc


def test_gate_clean_rerun_within_margins_passes(tmp_path):
    base = tmp_path / "base.json"
    fresh = tmp_path / "fresh.json"
    base.write_text(json.dumps(_bench_doc()))
    # 5% dips everywhere: inside every margin
    fresh.write_text(json.dumps(_bench_doc(**{
        "value": 95.0, "extra.mfu": 0.38, "extra.deepfm_rate": 190000.0,
        "extra.dispatch_overhead.scan_overhead_pct_of_run": 4.2})))
    assert perf_gate.main([str(fresh), str(base)]) == 0


def test_gate_fails_on_injected_regression(tmp_path, capsys):
    base = tmp_path / "base.json"
    base.write_text(json.dumps(_bench_doc()))
    for path, bad in [("value", 80.0),                   # −20% rate
                      ("extra.deepfm_rate", 100000.0),   # −50%
                      ("extra.dispatch_overhead.scan_overhead_pct_of_run",
                       9.0),                             # overhead doubled
                      ("extra.ps_embedding.hot_cache_bitwise_equal",
                       False)]:                          # invariant flip
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps(_bench_doc(**{path: bad})))
        assert perf_gate.main([str(fresh), str(base)]) == 1, path
        assert "FAIL" in capsys.readouterr().out


def test_gate_lost_metric_is_regression_but_null_both_sides_skips(tmp_path):
    base = tmp_path / "base.json"
    fresh = tmp_path / "fresh.json"
    base.write_text(json.dumps(_bench_doc()))
    fresh.write_text(json.dumps(_bench_doc(**{"extra.nmt_big_rate": None})))
    assert perf_gate.main([str(fresh), str(base)]) == 1

    # CPU-smoke tolerance: absent on BOTH sides → skipped
    base.write_text(json.dumps(_bench_doc(**{"extra.nmt_big_rate": None,
                                             "extra.nmt_big_mfu": None})))
    assert perf_gate.main([str(fresh), str(base)]) == 0


def test_gate_margin_scale(tmp_path):
    base = tmp_path / "base.json"
    fresh = tmp_path / "fresh.json"
    base.write_text(json.dumps(_bench_doc()))
    fresh.write_text(json.dumps(_bench_doc(value=85.0)))  # −15% vs 10% margin
    assert perf_gate.main([str(fresh), str(base)]) == 1
    assert perf_gate.main([str(fresh), str(base),
                           "--margin-scale", "2.0"]) == 0


def test_gate_accepts_wrapper_formats(tmp_path):
    doc = _bench_doc()
    base = tmp_path / "base.json"
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(doc))

    # driver wrapper with parsed
    base.write_text(json.dumps({"n": 5, "cmd": "python bench.py", "rc": 0,
                                "tail": "", "parsed": doc}))
    assert perf_gate.main([str(fresh), str(base)]) == 0

    # wrapper with parsed=null but an intact JSON line in the tail
    base.write_text(json.dumps({"n": 5, "cmd": "c", "rc": 0,
                                "parsed": None,
                                "tail": "noise\n" + json.dumps(doc) + "\n"}))
    assert perf_gate.main([str(fresh), str(base)]) == 0

    # truncated-tail recovery (the BENCH_r05.json shape): line cut at the
    # START, flat metrics regex-recovered
    cut = json.dumps(doc)[30:]
    base.write_text(json.dumps({"n": 5, "cmd": "c", "rc": 0,
                                "parsed": None, "tail": cut}))
    rec = perf_gate.load_doc(str(base))
    assert rec["_recovered_from_tail"]
    assert rec["extra"]["deepfm_rate"] == 200000.0
    assert perf_gate.main([str(fresh), str(base)]) == 0

    # nothing recoverable → exit 2
    base.write_text(json.dumps({"n": 5, "cmd": "c", "rc": 1,
                                "parsed": None, "tail": "OOM\n"}))
    assert perf_gate.main([str(fresh), str(base)]) == 2


def test_gate_reads_real_bench_r05_baseline():
    """The repo's own truncated baseline must stay loadable — the gate's
    entire value is gating against BENCH_r05.json."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_r05.json")
    doc = perf_gate.load_doc(path)
    assert doc["extra"]["deepfm_rate"] == pytest.approx(268244.1)
    # the context fields the rate is gated under survive truncation too
    assert doc["extra"]["deepfm_roofline"]["vocab"] == 33554432


def test_gate_context_mismatch_skips_raw_rates_not_normalized(tmp_path):
    """A TPU-recorded throughput baseline vs a CPU smoke run of the toy
    config: raw hardware rates are skipped with the mismatch named, but
    self-normalized metrics (MFU) still gate."""
    base, fresh = tmp_path / "base.json", tmp_path / "fresh.json"
    bdoc = _bench_doc()
    bdoc["extra"]["device"] = "TPU v5 lite0"
    bdoc["extra"]["deepfm_roofline"] = {"vocab": 33554432}
    base.write_text(json.dumps(bdoc))

    fdoc = _bench_doc(**{"extra.deepfm_rate": 13000.0})  # 15x "drop"
    fdoc["extra"]["device"] = "TFRT_CPU_0"
    fdoc["extra"]["deepfm_roofline"] = {"vocab": 10000}
    fresh.write_text(json.dumps(fdoc))
    assert perf_gate.main([str(fresh), str(base)]) == 0
    rep = perf_gate.compare(fdoc, bdoc)
    reasons = {e["path"]: e["reason"] for e in rep["skipped"]}
    assert "context mismatch" in reasons["extra.deepfm_rate"]

    # same drop with MATCHING context is a real regression
    fdoc["extra"]["device"] = "TPU v5 lite0"
    fdoc["extra"]["deepfm_roofline"] = {"vocab": 33554432}
    fresh.write_text(json.dumps(fdoc))
    assert perf_gate.main([str(fresh), str(base)]) == 1

    # a context-mismatched run can't dodge self-normalized metrics
    fdoc["extra"]["device"] = "TFRT_CPU_0"
    fdoc["extra"]["mfu"] = 0.10  # vs 0.40 baseline
    fresh.write_text(json.dumps(fdoc))
    assert perf_gate.main([str(fresh), str(base)]) == 1
