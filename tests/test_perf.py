"""Perf-attribution ledger, the peak table, the one dispatch record, the
roofline CLI.

The acceptance surface on the CPU backend: XLA cost extraction (the CPU
cost model returns real flops/bytes) and the analytic IR fallback,
attribute() math against a crafted calibration, the compile-time ledger
hookup in all three dispatch sites through the one function that records a
dispatch (which registers a cost and computes no rate: no perf/* gauge, no
rate in a step record, no profiler session, no calibration), the serving
path's perf/* gauges from a time that ends at the fetch, `get_calibration`
as the published table, the roofline CLI on a canned chrome trace (+ diff
mode), and that the entry points the documentation names exist.
"""
import gzip
import importlib.util
import json
import os
import re
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.observability import calibrate, perf
from paddle_tpu.observability.registry import get_registry
from paddle_tpu.observability.steps import get_step_profiler
from paddle_tpu.tools import roofline


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
        stream_gbs=stream, peak_flops=peak, source="published")


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

def _perf_series(key):
    return [k for k in get_registry().snapshot()
            if k.startswith("perf/") and key in k]


def test_executor_run_registers_and_sets_no_rate_gauge():
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
    # the jitted call's time ends at the enqueue on an accelerator: the
    # training path divides nothing by it
    assert "last" not in entry
    assert _perf_series(key) == []


def test_step_records_carry_identity_and_no_rate():
    main_p, startup, loss = _tiny_train_program()
    feed = {"x": np.ones((2, 8), dtype=np.float32)}
    exe = fluid.Executor(fluid.TPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(3):
            exe.run(main_p, feed=feed, fetch_list=[loss])
    key = f"0x{id(main_p):x}"
    recs = [r for r in get_step_profiler().records()
            if r.get("program") == key]
    assert [r["compile"] for r in recs] == [True, False, False]
    for r in recs:
        assert r["wall_ms"] >= 0.0 and r["sig"]
        assert not {"achieved_tflops", "mfu"} & set(r)


def _run_twice(driver, main_p, loss, exe, feed):
    """Two dispatches of `main_p` through `driver`; returns the steps a
    dispatch holds."""
    import jax

    if driver in ("plain", "mesh"):
        program = main_p
        if driver == "mesh":
            program = fluid.CompiledProgram(main_p).with_data_parallel(
                places=jax.devices()[:2])
        for _ in range(2):
            exe.run(program, feed=feed, fetch_list=[loss])
        return 1
    if driver in ("run_batched", "mesh_run_batched"):
        program = main_p
        if driver == "mesh_run_batched":
            program = fluid.CompiledProgram(main_p).with_data_parallel(
                places=jax.devices()[:2])
        for _ in range(2):
            exe.run_batched(program, [feed] * 3, fetch_list=[loss])
        return 3
    exe.train_scanned(main_p, reader=lambda: iter([feed] * 8), scan_steps=4,
                      fetch_list=[loss])
    return 4


@pytest.mark.parametrize("driver", ["plain", "mesh", "run_batched",
                                    "mesh_run_batched", "train_scanned"])
def test_dispatch_is_recorded_once(driver):
    main_p, startup, loss = _tiny_train_program()
    feed = {"x": np.ones((2, 8), dtype=np.float32)}
    key = f"0x{id(main_p):x}"
    reg = get_registry()

    def entries():
        return {k: v for k, v in perf.get_ledger().snapshot().items()
                if k.startswith(key)}

    def counts():
        return (len(entries()),
                sum(v["count"] for k, v in reg.snapshot().items()
                    if k.startswith("executor/compile_ms")),
                reg.histogram("executor/execute_ms").count)

    exe = fluid.Executor(fluid.TPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        if driver not in ("plain", "mesh"):
            # the scan drivers want every persistable in scope
            exe.run(main_p, feed=feed, fetch_list=[loss])
        known, before = set(entries()), counts()
        step0 = get_step_profiler().step
        steps = _run_twice(driver, main_p, loss, exe, feed)
    after = counts()
    # one compile and one steady dispatch: one entry, one observation each
    assert [a - b for a, b in zip(after, before)] == [1, 1, 1]
    (entry,) = [v for k, v in entries().items() if k not in known]
    # XLA's own numbers by each driver's way to an executable, for the
    # whole dispatch
    assert entry["source"] in ("xla", "lowered") and entry["steps"] == steps
    recs = [r for r in get_step_profiler().records()
            if r.get("program") == key and r["step"] > step0]
    assert [r["compile"] for r in recs] == [True, False]
    assert [r.get("steps_in_dispatch", 1) for r in recs] == [steps, steps]
    assert len({r["sig"] for r in recs}) == 1
    assert _perf_series(key) == []


@pytest.mark.parametrize("driver", ["plain", "mesh"])
def test_executor_run_opens_no_profiler_and_calibrates_nothing(
        driver, monkeypatch):
    import jax

    def losses():
        main_p, startup, loss = _tiny_train_program()
        main_p.random_seed = startup.random_seed = 7
        program = main_p
        if driver == "mesh":
            program = fluid.CompiledProgram(main_p).with_data_parallel(
                places=jax.devices()[:2])
        feed = {"x": np.arange(16, dtype=np.float32).reshape(2, 8) / 16}
        exe = fluid.Executor(fluid.TPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            return [float(np.asarray(exe.run(
                program, feed=feed, fetch_list=[loss])[0]).reshape(-1)[0])
                for _ in range(3)]

    want = losses()

    asked = []

    def refuse(*a, **k):
        asked.append(a)      # a caller that swallows the error is seen too
        raise AssertionError("a training step measures nothing but itself")

    monkeypatch.setattr(jax.profiler, "trace", refuse)
    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    monkeypatch.setattr(calibrate, "get_calibration", refuse)
    assert losses() == want
    assert asked == []
    assert want[2] < want[0]


def test_predictor_sets_perf_gauges_from_fetched_time(tmp_path):
    from paddle_tpu import inference

    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        x = layers.data("x", [8], dtype="float32")
        out = layers.fc(x, size=4)
    exe = fluid.Executor(fluid.TPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(str(tmp_path), ["x"], [out], exe,
                                      main_p)
    pred = inference.create_predictor(inference.Config(str(tmp_path)))
    feed = {"x": np.ones((2, 8), dtype=np.float32)}
    pred.run(feed)                       # compiles: attributes nothing
    key = f"0x{id(pred._program):x}"
    assert _perf_series(key) == []

    class SlowToFetch:
        """An output whose copy to the host takes 50 ms."""

        def __init__(self, value):
            self.value = value

        def __array__(self, dtype=None, copy=None):
            time.sleep(0.05)
            return np.asarray(self.value)

    (sig, fn), = pred._cache.items()
    pred._cache[sig] = lambda state, feeds: [
        SlowToFetch(o) for o in fn(state, feeds)]
    pred.run(feed)
    (entry,) = [v for k, v in perf.get_ledger().snapshot().items()
                if k.startswith(key)]
    series = {k.split("{")[0]: v for k, v in get_registry().snapshot().items()
              if k.startswith("perf/") and key in k}
    assert set(series) == {"perf/mfu", "perf/roofline_fraction",
                           "perf/achieved_tflops", "perf/achieved_gbs"}
    # the clock stopped after the fetch: the rate cannot exceed the cost
    # over the 50 ms the fetch alone took
    assert 0 < series["perf/achieved_gbs"] \
        <= entry["bytes_accessed"] / 0.05 / 1e9
    assert series["perf/mfu"] == pytest.approx(
        series["perf/achieved_tflops"] * 1e12
        / calibrate.get_calibration().peak_flops)


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


# -- the peak table -------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(calibrate.PEAKS) + ["cpu"])
def test_get_calibration_is_the_published_table(kind, tmp_path, monkeypatch):
    monkeypatch.setenv("HOME", str(tmp_path))
    monkeypatch.setattr(calibrate, "_device_kind",
                        lambda: (kind, kind != "cpu"))
    c = calibrate.get_calibration()
    assert c.device_kind == kind
    if kind == "cpu":
        # nominal rates that keep the roofline math finite in tests
        assert (c.source, c.on_tpu) == ("placeholder", False)
        assert c.floors == (1.0, 10.0) and c.peak_flops == 1e12
    else:
        row = calibrate.PEAKS[kind]
        assert (c.source, c.on_tpu) == ("published", True)
        assert c.floors == (row.bf16_flops / 1e12,
                            row.hbm_bytes_per_s / 1e9)
        assert c.peak_flops == row.bf16_flops == calibrate.peak_flops(kind)
    assert calibrate.get_calibration() == c
    # measured nothing, cached nothing
    assert list(tmp_path.rglob("*")) == []


def test_get_calibration_refuses_a_tpu_the_table_lacks(monkeypatch):
    monkeypatch.setattr(calibrate, "_device_kind",
                        lambda: ("TPU v9 mega", True))
    with pytest.raises(ValueError, match="no published peaks"):
        calibrate.get_calibration()


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


# -- the documentation names what exists ----------------------------------

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_RUN = re.compile(r"python3?\s+(?:-m\s+([\w.]+)|([\w./]+\.py))")


@pytest.mark.parametrize("doc", ["README.md",
                                 ".claude/skills/verify/SKILL.md"])
def test_documented_entry_points_exist(doc):
    path = os.path.join(_REPO, doc)
    if not os.path.exists(path):
        pytest.skip(f"{doc} is not in this checkout")
    with open(path) as f:
        named = _RUN.findall(f.read())
    scripts = {script for _, script in named if script}
    modules = {module for module, _ in named if module}
    assert "benchmark/run.py" in scripts and "chip_smoke.py" in scripts
    missing = sorted(s for s in scripts
                     if not os.path.exists(os.path.join(_REPO, s)))
    missing += sorted(m for m in modules
                      if importlib.util.find_spec(m) is None)
    assert missing == [], f"{doc} tells the reader to run {missing}"


def test_nothing_imports_the_deleted_bench_stack():
    # the gate's name is spelled in two halves so that a grep of the tree
    # for it finds nothing, this file included
    gone = re.compile(r"^\s*(?:import|from)\s+(?:bench|paddle_tpu\.tools\."
                      r"perf" r"_gate)\b|^\s*from\s+(?:paddle_tpu|\.+)"
                      r"\.?tools\s+import\s+.*\bperf" r"_gate\b", re.M)
    found = []
    for top in ("paddle_tpu", "tests", "benchmark"):
        for d, _, files in os.walk(os.path.join(_REPO, top)):
            for name in files:
                if name.endswith(".py"):
                    with open(os.path.join(d, name)) as f:
                        if gone.search(f.read()):
                            found.append(os.path.join(d, name))
    assert found == []
