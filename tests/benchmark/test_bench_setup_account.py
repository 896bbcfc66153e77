"""The eight readers of the program's set-up account (PR 37): each on a
registry with known counters, on one without the account (the parent), and
on the counters a small program leaves; their entries in BENCHMARK.json; and
`scope_join.steady_steps` on a compiling step whose span now has children."""
import importlib
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import scope_join
from benchmark.layer_metrics import _setup_account
from paddle_tpu.observability import Registry, get_tracer

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LAYER = "program to step: executor and compiler"
READERS = {
    # name: (unit, better, what it reads of `_known`)
    "setup_trace_s": ("s", "lower", 4.0 + 0.5 + 0.25),
    "setup_lower_s": ("s", "lower", 2.0 + 1.0),
    "setup_kernel_trace_s": ("s", "lower", 0.75 + 0.125),
    "setup_restage_s": ("s", "lower", 0.5 + 0.25 + 1.0 + 0.0625),
    "stagings_per_step": ("ratio", "lower", (2 + 1) / 2),
    "setup_cache_misses": ("count", "lower", 3),
    "setup_first_run_s": ("s", "lower", 0.03125 + 1.5),
    "setup_coverage": ("%", "higher", 100 * (
        0.5 + 4.0 + 0.5 + 0.25 + 2.0 + 1.0 + 0.0625 + 6.0 + 0.03125 + 1.5)
        / 32.0),
}


def _reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read


def _known() -> Registry:
    reg = Registry()
    for phase, reason, seconds in [
            ("trace", "call", 4.0), ("trace", "cost", 0.5),
            ("trace", "direct", 0.25), ("trace", "foreign", 8.0),
            ("trace", "executable", 16.0),
            ("lower", "call", 2.0), ("lower", "direct", 1.0),
            ("lower", "foreign", 8.0), ("lower", "executable", 16.0),
            ("backend_compile", "relayout", 0.0625),
            ("cache_read", "call", 6.0), ("cache_read", "foreign", 8.0),
            ("relayout", "call", 0.03125), ("first_run", "call", 1.5)]:
        reg.counter("setup/seconds", phase=phase, reason=reason).inc(seconds)
    reg.counter("setup/stagings", reason="call").inc(2)
    reg.counter("setup/stagings", reason="cost").inc(1)
    reg.counter("setup/stagings", reason="executable").inc(1)
    reg.counter("setup/executables").inc(2)
    reg.counter("setup/cache_misses").inc(3)
    reg.counter("setup/cache_misses_foreign").inc(5)
    reg.counter("setup/cache_hits").inc(1)
    reg.counter("setup/import_seconds").inc(0.5)
    reg.counter("setup/kernel_trace_seconds", kernel="flash_fwd",
                reason="call").inc(0.75)
    reg.counter("setup/kernel_trace_seconds", kernel="flash_bwd_dq",
                reason="call").inc(0.125)
    reg.counter("setup/kernel_trace_seconds", kernel="flash_fwd",
                reason="executable").inc(16.0)
    reg.counter("executor/cache_misses").inc(7)
    return reg


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_on_known_counters(name):
    ctx = {"registry": _known(), "values": {"setup_s": 32.0}}
    assert _reader(name)(ctx) == pytest.approx(READERS[name][2], rel=1e-12)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_on_a_program_without_the_account_reads_nothing(name):
    parent = Registry()           # what the parent's registry holds of set-up
    parent.counter("executor/cache_misses").inc(2)
    parent.histogram("executor/compile_ms", sig="ab12").observe(900.0)
    assert _reader(name)({"registry": parent,
                          "values": {"setup_s": 32.0}}) is None


def test_a_cell_without_kernels_or_restagings_reads_zero_not_nothing():
    reg = Registry()
    reg.counter("setup/cache_misses")
    reg.counter("setup/executables").inc(1)
    reg.counter("setup/stagings", reason="call").inc(1)
    ctx = {"registry": reg, "values": {"setup_s": 10.0}}
    assert _reader("setup_kernel_trace_s")(ctx) == 0.0
    assert _reader("setup_restage_s")(ctx) == 0.0
    assert _reader("setup_cache_misses")(ctx) == 0.0
    assert _reader("stagings_per_step")(ctx) == 1.0
    reg.counter("setup/executables").inc(-1)      # no step compiled at all
    assert _reader("stagings_per_step")(
        {"registry": reg, "values": {"setup_s": 10.0}}) is None


def test_the_eight_entries_are_in_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    rows = {m["name"]: m for m in spec["per_layer"]}
    for name, (unit, better, _) in READERS.items():
        assert rows[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "program_counter", "layer": LAYER, "moves": "setup_s"}
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", f"{name}.py"))
    # added at the end, after what the benchmark had
    assert [m["name"] for m in spec["per_layer"]][-8:] == [
        "setup_trace_s", "setup_lower_s", "setup_kernel_trace_s",
        "setup_restage_s", "stagings_per_step", "setup_cache_misses",
        "setup_first_run_s", "setup_coverage"]
    assert rows["compile_s"]["moves"] == rows["backend_s"]["moves"] == (
        "setup_s")


def test_the_readers_on_a_program_the_executor_ran():
    import paddle_tpu as fluid

    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", [6])
            loss = fluid.layers.mean(fluid.layers.fc(x, 5, act="tanh"))
            fluid.optimizer.SGD(0.1).minimize(loss)
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    spans_before = len(get_tracer().spans())
    for _ in range(3):
        exe.run(main, feed={"x": np.ones((2, 6), "float32")},
                fetch_list=[loss], scope=scope)
    ctx = {"values": {"setup_s": 3600.0}}
    got = {name: _reader(name)(ctx) for name in READERS}
    assert all(v is not None for v in got.values()), got
    assert got["setup_trace_s"] > 0 and got["setup_lower_s"] > 0
    assert got["setup_first_run_s"] > 0
    # over the whole process: other tests' steps are in it (one whose AUTO
    # compile was refused reaches two executables from one trace)
    assert got["stagings_per_step"] > 0
    assert 0 < got["setup_coverage"] < 100
    assert _setup_account.seconds(ctx) >= (
        got["setup_trace_s"] + got["setup_lower_s"]
        + got["setup_first_run_s"])
    # the compiling step's span has the phases as children now, and is still
    # left out of the steady steps by its name
    spans = get_tracer().spans()
    compiling = [i for i, s in enumerate(spans)
                 if s["name"] == "executor/compile+run" and i >= spans_before]
    assert len(compiling) == 1
    inside = {s["name"] for s in spans if s["parent"] == compiling[0]}
    assert {"setup/trace", "setup/lower", "setup/first_run"} <= inside
    steady = scope_join.steady_steps({
        "trace": SimpleNamespace(steps=3), "spans": lambda: spans})
    # the last three steady steps: the two after the compiling one, and one
    # from before it (the startup program's is a compiling step too)
    assert len(steady) <= 3
    assert all(not any(n.startswith("setup/") or n in scope_join.COMPILING
                       for n in c) for c in steady)
    ours = [c for c in steady if "executor/run" in c]
    assert len(ours) >= 2
