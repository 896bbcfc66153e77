"""The readers of the program's own names (PR 24): each on the two ERNIE
steps recorded on the chip in PR 23 (data/seq512_two_steps.json.gz) joined
with a hand-made scope map, on hand-made spans, and on a program that has
neither."""
import gzip
import importlib
import json
import os

import pytest

from benchmark import scope_join, xtrace
from paddle_tpu.observability import scopes
from paddle_tpu.observability.tracer import pair_spans

HERE = os.path.dirname(os.path.abspath(__file__))
DEVICE_READERS = ["fwd_ms", "bwd_ms", "opt_ms", "matmul_ms",
                  "scope_coverage", "head_ms", "rows_merge_ms"]
HOST_READERS = ["run_prepare_ms", "run_call_ms", "run_commit_ms",
                "run_telemetry_ms"]
PHASES = ("fwd", "bwd", "opt", "mixed", "none")


def _reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "data", "seq512_two_steps.json.gz"),
                   "rt") as f:
        data = json.load(f)
    return xtrace.Reduced(data["events"], data["steps"])


def _hand_made(recorded, leave_out=()):
    """A scope for every traced label but `leave_out`: the phase by the
    label's place in the sorted list, the last fifth in `rows/merge`, every
    fusion a matmul. The text is an instruction `xtrace.label` reads the
    label back from."""
    labels = sorted({e[0] for e in recorded.devices["/device:TPU:0"]})
    found = {}
    for i, label in enumerate(labels):
        if label in leave_out:
            continue
        name, opcode, *shape = label.split(" ")
        text = f"%{name} = {shape[0] if shape else 'f32[]'} {opcode}(%x)"
        if opcode == "custom-call":
            text += f", {xtrace.MOSAIC_TARGET}"
        assert xtrace.label(text) == label
        phase = PHASES[i % 5]
        unit = ("rows/merge" if 5 * i >= 4 * len(labels)
                else "mlm_head" if i % 7 == 0 else f"bert_layer_{i % 3}")
        found[name] = scopes.OpScope(
            name=name, text=text, phase=phase, unit=unit, op_types=("mul",),
            has_dot=opcode == "fusion")
    return found


def test_the_phases_add_up_to_the_busy_time(recorded):
    ctx = {"trace": recorded, "op_scopes": _hand_made(recorded)}
    by_phase = {p: scope_join.phase_ms(ctx, p) for p in PHASES}
    assert all(v > 0 for v in by_phase.values())
    busy_ms = recorded.busy_s * 1e3 / recorded.steps
    assert sum(by_phase.values()) == pytest.approx(busy_ms, rel=1e-9)
    assert _reader("fwd_ms")(ctx) == by_phase["fwd"]
    assert _reader("bwd_ms")(ctx) == by_phase["bwd"]
    assert _reader("opt_ms")(ctx) == by_phase["opt"]
    named = by_phase["fwd"] + by_phase["bwd"] + by_phase["opt"]
    assert _reader("scope_coverage")(ctx) == pytest.approx(
        100 * named / busy_ms)
    # Mosaic calls count in their phase and not as matmuls
    mosaic = scope_join.device_ms(ctx, lambda s, kind: kind == "mosaic")
    assert mosaic == pytest.approx(28.563, rel=1e-3)
    fusions = scope_join.device_ms(
        ctx, lambda s, kind: xtrace.opcode(s.text) == "fusion")
    assert _reader("matmul_ms")(ctx) == pytest.approx(fusions)
    assert 0 < _reader("head_ms")(ctx) < busy_ms
    assert 0 < _reader("rows_merge_ms")(ctx) < busy_ms


def test_an_operation_the_map_does_not_know_lowers_the_coverage(recorded):
    found = _hand_made(recorded)
    full = {"trace": recorded, "op_scopes": found}
    # the heaviest operation that the hand-made map calls forward
    label, seconds = next(
        (label, sec) for label, sec in recorded.top_ops(50)
        if found[label.split(" ")[0]].phase == "fwd")
    short = {"trace": recorded,
             "op_scopes": _hand_made(recorded, leave_out=(label,))}
    lost = 100 * seconds / (recorded.busy_s / recorded.steps)
    assert lost > 0.5
    assert _reader("scope_coverage")(short) == pytest.approx(
        _reader("scope_coverage")(full) - lost, rel=1e-6)
    assert _reader("fwd_ms")(short) == pytest.approx(
        _reader("fwd_ms")(full) - seconds * 1e3)


def test_another_executable_s_operation_of_the_same_name_is_not_joined(
        recorded):
    """A traced `fusion.N` that is not the step's (other opcode or shape in
    its label: a fold epilogue, a helper jit) takes no phase from the step's
    `fusion.N`: the join is by the whole label."""
    found = _hand_made(recorded)
    label, seconds = next(
        (label, sec) for label, sec in recorded.top_ops(50)
        if found[label.split(" ")[0]].phase == "bwd")
    name = label.split(" ")[0]
    other = dict(found)
    other[name] = found[name]._replace(
        text=found[name].text.replace(" = ", " = (f32[1]{0}, ", 1))
    assert xtrace.label(other[name].text) != label
    a = {"trace": recorded, "op_scopes": found}
    b = {"trace": recorded, "op_scopes": other}
    assert _reader("bwd_ms")(b) == pytest.approx(
        _reader("bwd_ms")(a) - seconds * 1e3)
    assert _reader("scope_coverage")(b) < _reader("scope_coverage")(a)


@pytest.mark.parametrize("name", DEVICE_READERS)
def test_a_device_reader_without_a_scope_map_reads_nothing(
        recorded, name, monkeypatch):
    monkeypatch.setattr(scopes, "hottest_step", lambda: None)
    assert _reader(name)({"trace": recorded}) is None
    # a map of another executable knows none of the traced operations
    alien = {"x": scopes.OpScope("x", "%x = f32[] add(%a, %b)", "fwd", "u",
                                 ("mul",), False)}
    assert _reader(name)({"trace": recorded, "op_scopes": alien}) is None


def test_unit_readers_read_nothing_where_the_step_has_no_such_unit(recorded):
    found = {n: s._replace(unit="bert_layer_0")
             for n, s in _hand_made(recorded).items()}
    ctx = {"trace": recorded, "op_scopes": found}
    assert _reader("head_ms")(ctx) is None
    assert _reader("rows_merge_ms")(ctx) is None
    assert _reader("fwd_ms")(ctx) > 0


def _step_events(start, ordinal, call="executor/run", fetch=0):
    """One `executor/step` of 1000 us and its phases, as B/E events."""
    out, t = [], start

    def span(name, dur, children=()):
        nonlocal t
        out.append({"name": name, "ph": "B", "ts": t, "pid": 1, "tid": 1})
        t0 = t
        for child in children:
            span(*child)
        t = max(t, t0 + dur)
        out.append({"name": name, "ph": "E", "ts": t, "pid": 1, "tid": 1})

    span("executor/step", 1000 + fetch, [
        ("executor/feed", 100), ("executor/state_in", 200 + ordinal),
        (call, 400), ("executor/telemetry", 50),
        ("executor/state_out", 60),
        ("executor/epilogue", 40, [("executor/step", 30, [
            ("executor/feed", 5), ("executor/run", 20)])]),
    ] + ([("executor/fetch", fetch)] if fetch else []))
    return out


def _spans_of(events):
    return lambda: pair_spans(events)


def test_the_host_readers_take_the_median_over_the_traced_steady_steps(
        recorded):
    events = _step_events(0, 0, call="executor/compile+run")
    for i in range(1, 6):
        events += _step_events(2000 * i, 10 * i)
    ctx = {"trace": recorded, "spans": _spans_of(events)}
    assert recorded.steps == 2       # the last two steady steps: 40 and 50
    assert _reader("run_prepare_ms")(ctx) == pytest.approx(
        (100 + 200 + 45) * 1e-3)
    assert _reader("run_call_ms")(ctx) == pytest.approx(0.4)
    # the epilogue's own nested step counts in the epilogue, not as a step
    assert _reader("run_commit_ms")(ctx) == pytest.approx(0.1)
    assert _reader("run_telemetry_ms")(ctx) == pytest.approx(0.05)
    mesh = {"trace": recorded, "spans": _spans_of(
        _step_events(0, 0, call="compiled_program/run"))}
    assert _reader("run_call_ms")(mesh) == pytest.approx(0.4)
    # a step that compiled is not a steady step
    only = {"trace": recorded, "spans": _spans_of(events[:26])}
    assert _reader("run_call_ms")(only) is None


@pytest.mark.parametrize("name", HOST_READERS)
def test_a_host_reader_without_spans_reads_nothing(recorded, name):
    assert _reader(name)({"trace": recorded, "spans": lambda: []}) is None
    elsewhere = [{"name": "bench.dispatch", "ph": "B", "ts": 0, "pid": 1,
                  "tid": 1},
                 {"name": "bench.dispatch", "ph": "E", "ts": 9, "pid": 1,
                  "tid": 1}]
    assert _reader(name)({"trace": recorded,
                          "spans": _spans_of(elsewhere)}) is None


def test_the_readers_on_a_step_the_executor_ran(recorded, monkeypatch):
    """From the program's own map and spans: a tiny program through
    `Executor.run`, its instructions taken for the traced operations."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.observability import get_tracer

    get_tracer().clear()
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", [8])
            with fluid.unit("mlm_head"):
                loss = fluid.layers.mean(fluid.layers.fc(x, 4))
            fluid.optimizer.SGD(0.1).minimize(loss)
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    for _ in range(4):
        exe.run(main, feed={"x": np.ones((2, 8), "float32")},
                fetch_list=[loss], scope=scope, return_numpy=False)
    compiled = exe.compiled_step(main)
    monkeypatch.setattr(scopes, "hottest_step", lambda: compiled)
    found = scopes.op_scopes(compiled)
    entry = [s for s in found.values() if s.phase != "none"][:50]
    events = {"devices": {"/device:TPU:0": [
        (xtrace.label(s.text), xtrace.classify(s.text), 1000 * i, 1000)
        for i, s in enumerate(entry)]}, "host": []}
    ctx = {"trace": xtrace.Reduced(events, 2)}
    parts = [_reader(n)(ctx) for n in ("fwd_ms", "bwd_ms", "opt_ms")]
    assert all(p is not None for p in parts)
    mixed = scope_join.phase_ms(ctx, "mixed")
    assert sum(parts) + mixed == pytest.approx(len(entry) * 1e-3 / 2)
    assert _reader("scope_coverage")(ctx) == pytest.approx(
        100 * sum(parts) / (sum(parts) + mixed))
    assert _reader("head_ms")(ctx) > 0
    total = sum(_reader(n)(ctx) for n in HOST_READERS)
    steps = [s for s in get_tracer().spans() if s["name"] == "executor/step"]
    assert 0 < total <= max(s["dur"] for s in steps[-2:]) * 1e-3
