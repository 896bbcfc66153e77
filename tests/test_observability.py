"""paddle_tpu.observability: registry, span tracer, recompile watchdog.

Covers the telemetry acceptance surface: a single Registry export showing
executor cache hit/miss + compile-time metrics next to serving latency,
chrome-trace export that parses and is well-nested per thread, the
timeline CLI's merge/summary, watchdog detection + diagnosis of a
shape-changing feed (with zero false positives on steady shapes), the
profiler start/stop guards, and the copy-on-read histogram snapshot
under concurrent observers — all on the CPU backend.
"""
import json
import threading

import numpy as np
import pytest

from paddle_tpu import observability as obs


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Each test sees a fresh span stream (the tracer is process-global)."""
    obs.get_tracer().clear()
    yield
    obs.get_tracer().clear()


# -- Registry -------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = obs.Registry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    assert reg.counter("c").value == 5
    reg.gauge("g").set(2.0)
    reg.gauge("g").add(1.5)
    assert reg.gauge("g").value == 3.5
    for v in range(1, 101):
        reg.histogram("h").observe(float(v))
    snap = reg.snapshot()
    assert snap["c"] == 5 and snap["g"] == 3.5
    assert snap["h"]["count"] == 100
    assert snap["h"]["p50"] == pytest.approx(50, abs=1)
    assert snap["h"]["min"] == 1 and snap["h"]["max"] == 100


def test_labels_key_separate_metrics_and_render_in_exports():
    reg = obs.Registry()
    reg.counter("compiles", sig="aa").inc(2)
    reg.counter("compiles", sig="bb").inc(3)
    assert reg.counter("compiles", sig="aa").value == 2
    snap = reg.snapshot()
    assert snap['compiles{sig="aa"}'] == 2
    assert snap['compiles{sig="bb"}'] == 3
    text = reg.prometheus_text()
    assert 'compiles{sig="aa"} 2' in text
    assert text.count("# TYPE compiles counter") == 1


def test_prometheus_text_format():
    reg = obs.Registry()
    reg.counter("serving/requests").inc(7)
    reg.gauge("queue_depth").set(3)
    h = reg.histogram("latency_ms")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    text = reg.prometheus_text()
    # names sanitized, TYPE lines present, summary carries quantiles
    assert "# TYPE serving_requests counter" in text
    assert "serving_requests 7" in text
    assert "# TYPE queue_depth gauge" in text
    assert "# TYPE latency_ms summary" in text
    assert 'latency_ms{quantile="0.5"} 2.0' in text
    assert "latency_ms_count 3" in text
    assert "latency_ms_sum 6.0" in text


def test_registry_json_dump(tmp_path):
    reg = obs.Registry()
    reg.counter("a").inc()
    reg.histogram("b").observe(1.0)
    path = str(tmp_path / "metrics.json")
    reg.dump_json(path)
    with open(path) as f:
        loaded = json.load(f)
    assert loaded["a"] == 1 and loaded["b"]["count"] == 1


def test_attached_children_merge_into_deep_snapshot():
    parent, child_a, child_b = obs.Registry(), obs.Registry(), obs.Registry()
    parent.attach(child_a)
    parent.attach(child_b)
    parent.counter("own").inc()
    child_a.counter("reqs").inc(2)
    child_b.counter("reqs").inc(3)  # same name: counters sum
    child_a.histogram("lat").observe(1.0)
    child_b.histogram("lat").observe(9.0)  # same name: samples merge
    snap = parent.snapshot(deep=True)
    assert snap["own"] == 1
    assert snap["reqs"] == 5
    assert snap["lat"]["count"] == 2
    assert snap["lat"]["min"] == 1.0 and snap["lat"]["max"] == 9.0
    shallow = parent.snapshot(deep=False)
    assert "reqs" not in shallow


def test_detached_child_leaves_export_on_gc():
    import gc

    parent = obs.Registry()
    child = obs.Registry()
    parent.attach(child)
    child.counter("temp").inc()
    assert "temp" in parent.snapshot()
    del child
    gc.collect()
    assert "temp" not in parent.snapshot()


# -- satellite: histogram snapshot under concurrent observe ---------------

def test_histogram_snapshot_copy_on_read_under_writer_threads():
    """Hammer one histogram from writer threads while readers snapshot:
    reads must never raise or see torn state, and the final count must
    equal every observe() made (cap smaller than the write volume so the
    ring wraps constantly — the hostile case for a torn read)."""
    h = obs.Histogram("hammer", cap=64)
    n_writers, per_writer = 8, 2000
    stop = threading.Event()
    errors = []

    def write(seed):
        for i in range(per_writer):
            h.observe(float((seed * per_writer + i) % 997))

    def read():
        while not stop.is_set():
            try:
                s = h.snapshot()
                assert (s["count"] == 0) == (s["p50"] is None)
                if s["p50"] is not None:
                    assert s["min"] <= s["p50"] <= s["p99"] <= s["max"]
                h.percentile(95)
            except Exception as e:  # pragma: no cover - the regression
                errors.append(e)
                return

    readers = [threading.Thread(target=read) for _ in range(4)]
    writers = [threading.Thread(target=write, args=(i,))
               for i in range(n_writers)]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    for t in readers:
        t.join()
    assert not errors, errors
    assert h.count == n_writers * per_writer
    assert h.snapshot()["count"] == n_writers * per_writer


# -- tracer ----------------------------------------------------------------

def _span_events(trace):
    return [e for e in trace["traceEvents"] if e.get("ph") in ("B", "E")]


def test_trace_span_nesting_and_chrome_export(tmp_path):
    with obs.trace_span("outer", step=1):
        with obs.trace_span("inner"):
            pass
        with obs.trace_span("inner"):
            pass
    path = str(tmp_path / "trace.json")
    obs.get_tracer().export_chrome_trace(path)
    with open(path) as f:
        trace = json.load(f)  # valid JSON on disk
    assert "traceEvents" in trace
    evs = _span_events(trace)
    assert [e["name"] for e in evs] == ["outer", "inner", "inner",
                                       "inner", "inner", "outer"]
    assert evs[0]["args"] == {"step": 1}
    # B/E balanced and properly nested per thread
    stack = []
    for e in evs:
        if e["ph"] == "B":
            stack.append(e["name"])
        else:
            assert stack and stack.pop() == e["name"]
    assert not stack
    # timestamps are monotone non-decreasing within the thread
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)
    # thread metadata present
    assert any(e.get("name") == "thread_name" and e.get("ph") == "M"
               for e in trace["traceEvents"])


def test_trace_span_balances_on_exception():
    with pytest.raises(RuntimeError):
        with obs.trace_span("boom"):
            raise RuntimeError("x")
    evs = _span_events(obs.get_tracer().export_chrome_trace())
    assert [e["ph"] for e in evs if e["name"] == "boom"] == ["B", "E"]


def test_trace_span_decorator_and_disable():
    @obs.trace_span("fn_span", kind="test")
    def work(x):
        return x + 1

    assert work(1) == 2
    assert work(2) == 3
    tr = obs.get_tracer()
    assert sum(1 for e in _span_events(tr.export_chrome_trace())
               if e["name"] == "fn_span" and e["ph"] == "B") == 2
    tr.enabled = False
    try:
        with obs.trace_span("hidden"):
            pass
    finally:
        tr.enabled = True
    assert not any(e["name"] == "hidden"
                   for e in _span_events(tr.export_chrome_trace()))


def test_tracer_spans_from_threads_keep_per_thread_nesting():
    # all threads alive at once, else the OS reuses thread identifiers
    barrier = threading.Barrier(4)

    def run(name):
        with obs.trace_span(name):
            barrier.wait()
            with obs.trace_span(name + "/leaf"):
                pass

    threads = [threading.Thread(target=run, args=(f"t{i}",))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    trace = obs.get_tracer().export_chrome_trace()
    by_tid = {}
    for e in _span_events(trace):
        by_tid.setdefault(e["tid"], []).append(e)
    assert len(by_tid) == 4
    for evs in by_tid.values():
        stack = []
        for e in evs:
            if e["ph"] == "B":
                stack.append(e["name"])
            else:
                assert stack.pop() == e["name"]
        assert not stack


def test_tracer_event_cap_drops_and_counts():
    t = obs.Tracer(max_events=4)
    for i in range(4):
        with _span_into(t, f"s{i}"):
            pass
    assert len(t) == 4 and t.dropped == 4  # a ring: the newest 2 spans stay
    assert [s["name"] for s in t.spans()] == ["s2", "s3"]


class _span_into:
    """Minimal span recorded into a specific tracer (trace_span always
    targets the process tracer)."""

    def __init__(self, tracer, name):
        self.tracer, self.name = tracer, name

    def __enter__(self):
        self.tracer.begin(self.name)

    def __exit__(self, *exc):
        self.tracer.end(self.name)


def test_the_ring_keeps_the_newest_events_of_a_long_run():
    t = obs.Tracer(max_events=100)
    for i in range(1000):
        with _span_into(t, f"step{i}"):
            with _span_into(t, "leaf"):
                pass
    assert len(t) == 100 and t.dropped == 4 * 1000 - 100
    names = [s["name"] for s in t.spans() if s["name"] != "leaf"]
    assert names[-1] == "step999" and names[0] == "step975"
    # the E of a span whose B fell out of the ring closes nothing
    assert all(s["dur"] >= 0 for s in t.spans())


def test_spans_come_back_with_parent_and_self_time():
    import time

    with obs.trace_span("root", step=7):
        with obs.trace_span("root/a"):
            time.sleep(0.002)
            with obs.trace_span("root/a/leaf"):
                time.sleep(0.002)
        with obs.trace_span("root/b"):
            time.sleep(0.001)
    with obs.trace_span("other"):
        pass
    spans = obs.get_tracer().spans()
    assert [s["name"] for s in spans] == ["root", "root/a", "root/a/leaf",
                                          "root/b", "other"]
    root, a, leaf, b, other = spans
    assert root["parent"] is None and other["parent"] is None
    assert (a["parent"], leaf["parent"], b["parent"]) == (0, 1, 0)
    assert root["args"] == {"step": 7}
    # self time: the duration less what the child spans cover
    assert a["self"] == pytest.approx(a["dur"] - leaf["dur"])
    assert root["self"] == pytest.approx(root["dur"] - a["dur"] - b["dur"])
    assert leaf["self"] == leaf["dur"] >= 2000 and root["self"] >= 0
    assert sum(s["self"] for s in spans[:4]) == pytest.approx(root["dur"])


def test_a_span_whose_body_raises_still_closes_and_reads_its_duration():
    span = obs.trace_span("doomed")
    with pytest.raises(ValueError):
        with obs.trace_span("outer"):
            with span:
                raise ValueError("boom")
    spans = obs.get_tracer().spans()
    assert [(s["name"], s["parent"]) for s in spans] == [("outer", None),
                                                         ("doomed", 0)]
    assert span.dur_ms == pytest.approx(spans[1]["dur"] * 1e-3)
    # with the tracer off a span records nothing and still times itself
    tracer = obs.get_tracer()
    tracer.enabled = False
    try:
        with obs.trace_span("unseen") as quiet:
            pass
    finally:
        tracer.enabled = True
    assert quiet.dur_ms >= 0 and len(tracer.spans()) == 2


# `Executor.run` is one sequence for a Program and a CompiledProgram: what
# follows holds on both paths, the jitted call under its own span's name
PATHS = pytest.mark.parametrize("path,call", [
    ("plain", "executor/run"), ("mesh", "compiled_program/run")])


def _on_path(path, main):
    import jax

    import paddle_tpu as fluid

    if path == "mesh":
        return fluid.CompiledProgram(main).with_data_parallel(
            places=jax.devices()[:2])
    return main


@PATHS
def test_a_run_records_its_phases_under_one_step_span(path, call):
    import paddle_tpu as fluid

    main, startup, y = _tiny_program()
    program = _on_path(path, main)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    feed = {"x": np.zeros((2, 3), np.float32)}
    for _ in range(3):
        exe.run(program, feed=feed, fetch_list=[y], return_numpy=False)
    spans = obs.get_tracer().spans()
    roots = [i for i, s in enumerate(spans) if s["name"] == "executor/step"]
    assert len(roots) == 4 and all(spans[i]["parent"] is None for i in roots)
    ordinals = [spans[i]["args"]["step"] for i in roots]
    assert ordinals == list(range(ordinals[0], ordinals[0] + 4))
    last = roots[-1]
    children = [s for s in spans if s["parent"] == last]
    # the same children in the same order on both paths
    assert [s["name"] for s in children] == [
        "executor/feed", "executor/state_in", call, "executor/telemetry",
        "executor/state_out", "executor/epilogue"]
    assert sum(s["dur"] for s in children) <= spans[last]["dur"]
    assert spans[last]["self"] == pytest.approx(
        spans[last]["dur"] - sum(s["dur"] for s in children))
    compiled = [s["name"] for s in spans if s["parent"] == roots[1]]
    assert call.replace("run", "compile+run") in compiled
    # a blocking fetch is a phase of its own
    exe.run(program, feed=feed, fetch_list=[y])
    spans = obs.get_tracer().spans()
    fetch = [s for s in spans if s["name"] == "executor/fetch"][-1]
    assert spans[fetch["parent"]]["name"] == "executor/step"
    assert fetch["parent"] == max(i for i, s in enumerate(spans)
                                  if s["name"] == "executor/step")


@PATHS
def test_check_nan_inf_raises_on_a_fetch_that_is_not_finite(path, call):
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        y = fluid.layers.log(fluid.layers.data("x", [2]))   # log(-1) = nan
    exe = fluid.Executor(fluid.TPUPlace())
    fluid.set_flags({"FLAGS_check_nan_inf": True})
    try:
        with pytest.raises(FloatingPointError):
            exe.run(_on_path(path, main), fetch_list=[y],
                    feed={"x": -np.ones((2, 2), np.float32)})
    finally:
        fluid.set_flags({"FLAGS_check_nan_inf": False})


@PATHS
def test_a_fetchless_step_hands_back_a_handle_to_block_on(path, call):
    import paddle_tpu as fluid

    main, startup, _ = _tiny_program()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    handle = exe.run(_on_path(path, main), return_handle=True,
                     feed={"x": np.zeros((2, 3), np.float32)})
    # nothing fetched: a probe cut from the new state is what the in-flight
    # bound waits on
    assert handle.names == [] and handle.numpy() == []
    assert handle._probe is not None
    handle.block_until_ready()


@PATHS
def test_a_delay_injected_at_dispatch_is_inside_the_call_s_span(path, call):
    import paddle_tpu as fluid
    from paddle_tpu import faults

    main, startup, y = _tiny_program()
    program = _on_path(path, main)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    feed = {"x": np.zeros((2, 3), np.float32)}
    exe.run(program, feed=feed, fetch_list=[y])
    faults.install("exec.dispatch", "delay_ms", 60)
    try:
        exe.run(program, feed=feed, fetch_list=[y])
    finally:
        faults.clear()
    # a slow dispatch is a slow step: the straggler detector reads this time
    slow = [s for s in obs.get_tracer().spans() if s["name"] == call][-1]
    assert slow["dur"] >= 60e3


@PATHS
def test_a_second_step_takes_its_state_names_from_the_cached_entry(
        path, call, monkeypatch):
    import paddle_tpu as fluid

    main, startup, y = _tiny_program()
    program = _on_path(path, main)
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    feed = {"x": np.zeros((2, 3), np.float32)}
    exe.run(program, feed=feed, fetch_list=[y], scope=scope)
    cached = exe._state_names_cache
    assert cached[0] is main and cached[2] is scope and cached[4]
    asked = []
    has_var = fluid.Scope.has_var
    monkeypatch.setattr(fluid.Scope, "has_var", lambda self, name: (
        asked.append(name), has_var(self, name))[1])
    exe.run(program, feed=feed, fetch_list=[y], scope=scope)
    # program and scope unchanged: no walk over the program's variables
    assert asked == [] and exe._state_names_cache is cached
    # a variable added to the scope's key set makes the entry anew
    scope.set_var("another", np.zeros(1, np.float32))
    exe.run(program, feed=feed, fetch_list=[y], scope=scope)
    assert asked and exe._state_names_cache is not cached


# -- timeline CLI ----------------------------------------------------------

def test_timeline_summary_on_synthetic_trace():
    from paddle_tpu.tools import timeline as tl

    trace = {"traceEvents": [
        {"name": "step", "ph": "B", "ts": 0, "pid": 1, "tid": 1},
        {"name": "op", "ph": "B", "ts": 100, "pid": 1, "tid": 1},
        {"name": "op", "ph": "E", "ts": 600, "pid": 1, "tid": 1},
        {"name": "step", "ph": "E", "ts": 1000, "pid": 1, "tid": 1},
        {"name": "op", "ph": "X", "ts": 0, "dur": 2000, "pid": 1, "tid": 2},
        {"name": "stray_end", "ph": "E", "ts": 5, "pid": 9, "tid": 9},
    ]}
    stats = tl.summarize(trace)
    assert stats["step"] == {"count": 1, "total_ms": 1.0,
                             "avg_ms": 1.0, "max_ms": 1.0}
    assert stats["op"]["count"] == 2
    assert stats["op"]["total_ms"] == pytest.approx(2.5)
    assert stats["op"]["max_ms"] == pytest.approx(2.0)
    assert "stray_end" not in stats
    table = tl.format_summary(stats)
    assert table.splitlines()[1].startswith("op")  # sorted by total desc


def test_timeline_merge_remaps_pids(tmp_path):
    from paddle_tpu.tools import timeline as tl

    a = {"traceEvents": [
        {"name": "x", "ph": "X", "ts": 0, "dur": 10, "pid": 7, "tid": 1}]}
    b = {"traceEvents": [
        {"name": "y", "ph": "X", "ts": 0, "dur": 20, "pid": 7, "tid": 1}]}
    merged = tl.merge_traces([a, b], names=["host", "device"])
    xs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    assert xs[0]["pid"] != xs[1]["pid"]  # same source pid, separate tracks
    pnames = {e["pid"]: e["args"]["name"]
              for e in merged["traceEvents"]
              if e.get("name") == "process_name"}
    assert any("host" in v for v in pnames.values())
    assert any("device" in v for v in pnames.values())


def test_timeline_cli_merge_and_summary(tmp_path, capsys):
    from paddle_tpu.tools import timeline as tl

    with obs.trace_span("cli_span"):
        pass
    p1 = str(tmp_path / "a.json")
    obs.get_tracer().export_chrome_trace(p1)
    p2 = str(tmp_path / "b.json")
    with open(p2, "w") as f:
        json.dump({"traceEvents": [{"name": "dev", "ph": "X", "ts": 0,
                                    "dur": 50, "pid": 0, "tid": 0}]}, f)
    out = str(tmp_path / "merged.json")
    tl.main([p1, p2, "--out", out, "--summary"])
    printed = capsys.readouterr().out
    assert "cli_span" in printed and "dev" in printed
    with open(out) as f:
        merged = json.load(f)
    names = {e.get("name") for e in merged["traceEvents"]}
    assert {"cli_span", "dev"} <= names


# -- executor instrumentation ---------------------------------------------

def _tiny_program():
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [3])
        y = fluid.layers.fc(x, 2)
    return main, startup, y


def test_executor_cache_and_compile_metrics():
    import paddle_tpu as fluid

    reg = obs.get_registry()
    hits0 = reg.counter("executor/cache_hits").value
    miss0 = reg.counter("executor/cache_misses").value
    exec0 = reg.histogram("executor/execute_ms").count

    main, startup, y = _tiny_program()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    feed = {"x": np.zeros((2, 3), np.float32)}
    exe.run(main, feed=feed, fetch_list=[y])   # compile
    exe.run(main, feed=feed, fetch_list=[y])   # hit
    exe.run(main, feed=feed, fetch_list=[y])   # hit

    assert reg.counter("executor/cache_misses").value - miss0 == 2  # startup+main
    assert reg.counter("executor/cache_hits").value - hits0 == 2
    assert reg.histogram("executor/execute_ms").count - exec0 == 2
    snap = reg.snapshot()
    compile_keys = [k for k in snap if k.startswith("executor/compile_ms")]
    assert compile_keys, "per-signature compile histograms missing"
    # the span tracer saw the runs too
    names = [e["name"] for e in
             _span_events(obs.get_tracer().export_chrome_trace())]
    assert "executor/compile+run" in names and "executor/run" in names


def test_record_event_routes_to_host_tracer():
    from paddle_tpu import profiler

    assert profiler.record_event is obs.trace_span
    with profiler.record_event("annotated/region", tag=3):
        pass
    evs = _span_events(obs.get_tracer().export_chrome_trace())
    assert [e["ph"] for e in evs if e["name"] == "annotated/region"] \
        == ["B", "E"]


# -- recompile watchdog ----------------------------------------------------

def test_watchdog_diagnoses_shape_changing_feed():
    import paddle_tpu as fluid

    wd = obs.get_watchdog()
    old_threshold = wd.threshold
    wd.threshold = 3
    try:
        main, startup, y = _tiny_program()
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        with pytest.warns(obs.RecompileWarning) as rec:
            for n in range(1, 7):  # a new batch size every step
                exe.run(main, feed={"x": np.zeros((n, 3), np.float32)},
                        fetch_list=[y])
        warns = [w for w in rec if issubclass(w.category,
                                              obs.RecompileWarning)]
        assert len(warns) == 1, "warning must fire exactly once"
        msg = str(warns[0].message)
        assert "'x'" in msg                      # names the diverging feed
        assert "shape" in msg and "->" in msg    # says what changed
        assert "recompiled 4 times" in msg       # past threshold 3
    finally:
        wd.threshold = old_threshold


def test_watchdog_silent_on_steady_shapes():
    import warnings as _warnings

    import paddle_tpu as fluid

    wd = obs.get_watchdog()
    old_threshold = wd.threshold
    wd.threshold = 1  # as twitchy as possible: any recompile would warn
    try:
        main, startup, y = _tiny_program()
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        reg = obs.get_registry()
        hits0 = reg.counter("executor/cache_hits").value
        feed = {"x": np.zeros((4, 3), np.float32)}
        with _warnings.catch_warnings():
            _warnings.simplefilter("error", obs.RecompileWarning)
            for _ in range(6):  # steady shape: one compile, then hits
                exe.run(main, feed=feed, fetch_list=[y])
        assert reg.counter("executor/cache_hits").value - hits0 == 5
    finally:
        wd.threshold = old_threshold


def test_watchdog_diff_signatures_names_added_removed_changed():
    prev = (("a", (2, 3), "float32"), ("b", (4,), "int32"))
    new = (("a", (5, 3), "float32"), ("c", (1,), "float32"))
    diffs = obs.diff_signatures(prev, new)
    text = " | ".join(diffs)
    assert "'a' changed shape (2, 3) -> (5, 3)" in text
    assert "'b' removed" in text
    assert "'c' added" in text


def test_watchdog_dtype_change_reported():
    wd = obs.RecompileWatchdog(threshold=1)
    key = ("prog",)
    wd.record_compile(key, (("x", (2,), "float32"),))
    with pytest.warns(obs.RecompileWarning, match=r"dtype float32 -> int32"):
        wd.record_compile(key, (("x", (2,), "int32"),))


# -- profiler guards (satellite) ------------------------------------------

def test_stop_profiler_without_start_raises_clear_error():
    from paddle_tpu import profiler

    with pytest.raises(RuntimeError, match="matching start_profiler"):
        profiler.stop_profiler()


def test_nested_profiler_rejected_with_clear_error(monkeypatch, tmp_path):
    from paddle_tpu import profiler

    calls = {"start": 0, "stop": 0}
    monkeypatch.setattr(profiler.jax.profiler, "start_trace",
                        lambda d: calls.__setitem__("start",
                                                    calls["start"] + 1))
    monkeypatch.setattr(profiler.jax.profiler, "stop_trace",
                        lambda: calls.__setitem__("stop", calls["stop"] + 1))
    d = str(tmp_path / "prof")
    with profiler.profiler(profile_path=d):
        with pytest.raises(RuntimeError, match="already active"):
            profiler.start_profiler(log_dir=str(tmp_path / "nested"))
    assert calls == {"start": 1, "stop": 1}
    # the session closed cleanly: a fresh one can start
    with profiler.profiler(profile_path=d):
        pass
    assert calls == {"start": 2, "stop": 2}


# -- serving integration ---------------------------------------------------

IN_DIM = 5


@pytest.fixture(scope="module")
def predictor(tmp_path_factory):
    import paddle_tpu as fluid
    from paddle_tpu import inference
    from paddle_tpu.core import program as prog_mod

    old = prog_mod._main_program, prog_mod._startup_program
    prog_mod._main_program = prog_mod.Program()
    prog_mod._startup_program = prog_mod.Program()
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", [IN_DIM])
            out = fluid.layers.fc(x, 3, act="softmax")
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        model_dir = str(tmp_path_factory.mktemp("obs") / "model")
        fluid.io.save_inference_model(model_dir, ["x"], [out], exe, main)
        return inference.create_predictor(inference.Config(model_dir))
    finally:
        prog_mod._main_program, prog_mod._startup_program = old


def test_server_stats_unifies_serving_and_executor_metrics(predictor):
    """THE acceptance property: one export holds executor cache/compile
    metrics and serving latency together."""
    from paddle_tpu import serving

    server = serving.InferenceServer(predictor, buckets=(2, 4),
                                     max_batch_delay_ms=1.0)
    with server:
        for i in range(4):
            server.infer({"x": np.random.RandomState(i)
                          .rand(2, IN_DIM).astype(np.float32)})
    stats = server.stats()
    assert stats["serving/requests"] >= 4
    assert stats["serving/latency_ms"]["count"] >= 4
    assert "executor/cache_hits" in stats
    assert "executor/cache_misses" in stats
    assert any(k.startswith("executor/compile_ms") for k in stats)
    # per-server view still isolated
    assert server.metrics.snapshot()["serving/requests"] == 4
    # and the global prometheus export renders the serving metrics too
    text = obs.get_registry().prometheus_text()
    assert "serving_requests" in text and "executor_cache_misses" in text


def test_serving_dispatch_spans_in_chrome_trace(predictor):
    from paddle_tpu import serving

    server = serving.InferenceServer(predictor, buckets=(2, 4),
                                     max_batch_delay_ms=1.0)
    with server:
        server.infer({"x": np.zeros((2, IN_DIM), np.float32)})
    evs = _span_events(obs.get_tracer().export_chrome_trace())
    dispatch = [e for e in evs if e["name"].startswith("serving/dispatch_b")]
    assert dispatch and dispatch[0]["args"]["rows"] == 2


def test_serving_bench_dumps_metrics_and_trace(tmp_path):
    from paddle_tpu.core import program as prog_mod
    from paddle_tpu.tools import serving_bench as sb

    mpath = str(tmp_path / "m.json")
    tpath = str(tmp_path / "t.json")
    old = prog_mod._main_program, prog_mod._startup_program
    prog_mod._main_program = prog_mod.Program()
    prog_mod._startup_program = prog_mod.Program()
    try:
        rc = sb.main(["--requests", "8", "--concurrency", "4",
                      "--buckets", "2,4", "--batch-delay-ms", "1",
                      "--in-dim", "6", "--hidden", "8", "--layers", "1",
                      "--skip-sequential",
                      "--metrics-out", mpath, "--trace-out", tpath])
    finally:
        prog_mod._main_program, prog_mod._startup_program = old
    assert rc == 0
    with open(mpath) as f:
        loaded = json.load(f)
    assert "executor/cache_misses" in loaded
    assert loaded["serving/requests"] >= 8
    assert loaded["bench/served"]["requests"] == 8
    with open(tpath) as f:
        trace = json.load(f)
    assert any(e.get("name", "").startswith("serving/dispatch")
               for e in trace["traceEvents"])


# -- prometheus exposition hardening ---------------------------------------

def test_prometheus_text_sanitizes_names_and_escapes_labels():
    """Hostile metric/label content (feed signatures, shapes) must not
    break the exposition: names fold to the spec charset, label values
    escape backslash/quote/newline."""
    reg = obs.Registry()
    reg.counter("steps/anomalies", reason="slow_step").inc()
    reg.counter("9starts.with-digit").inc(2)
    reg.counter("shape", sig='x:f32[8,128] "q" \\b\nnext').inc(3)
    text = reg.prometheus_text()
    assert 'steps_anomalies{reason="slow_step"} 1' in text
    assert "_9starts_with_digit 2" in text
    assert ('shape{sig="x:f32[8,128] \\"q\\" \\\\b\\nnext"} 3') in text
    # every line is a comment or `name{...} value` — nothing unparseable
    for line in text.splitlines():
        assert line.startswith("#") or " " in line
        if not line.startswith("#"):
            name = line.split("{")[0].split(" ")[0]
            assert name and (name[0].isalpha() or name[0] == "_")
            assert all(c.isalnum() or c == "_" for c in name)


# -- step profiler / straggler detection -----------------------------------

def test_step_profiler_steady_stream_no_anomalies():
    from paddle_tpu.observability.steps import StepProfiler

    reg = obs.Registry()
    prof = StepProfiler(window=64, registry=reg)
    for _ in range(60):
        rec = prof.record(10.0, program_id=1, sig="aa", sample_env=False)
        assert "anomaly" not in rec
    assert reg.counter("steps/total").value == 60
    snap = reg.snapshot()
    assert not any(k.startswith("steps/anomalies") for k in snap)


def test_step_profiler_flags_straggler_with_deviation():
    from paddle_tpu.observability.steps import StepProfiler

    reg = obs.Registry()
    prof = StepProfiler(window=64, registry=reg)
    for _ in range(40):
        prof.record(10.0, program_id=1, sig="aa", sample_env=False)
    rec = prof.record(200.0, program_id=1, sig="aa", sample_env=False)
    assert rec["anomaly"] == "slow_step"
    assert rec["deviation"] > 6
    assert reg.counter("steps/anomalies", reason="slow_step").value == 1
    # the straggler also landed in the flight recorder's ring
    contents = obs.get_flight_recorder().contents()
    assert any(e.get("reason") == "slow_step" for e in contents["events"])
    assert any(r.get("anomaly") == "slow_step" for r in contents["steps"])


def test_step_profiler_baselines_are_per_stream():
    """A slow eval program interleaved with a fast train program is NOT
    a straggler — baselines key on (program, sig)."""
    from paddle_tpu.observability.steps import StepProfiler

    reg = obs.Registry()
    prof = StepProfiler(window=128, registry=reg)
    for _ in range(40):
        prof.record(5.0, program_id=1, sig="train", sample_env=False)
        rec = prof.record(50.0, program_id=2, sig="eval", sample_env=False)
        assert "anomaly" not in rec


def test_step_profiler_compile_excluded_then_recompile_flagged():
    from paddle_tpu.observability.steps import StepProfiler

    reg = obs.Registry()
    prof = StepProfiler(window=64, registry=reg)
    # first compile: baseline empty, not an anomaly
    rec = prof.record(500.0, program_id=1, sig="aa", compiled=True,
                      sample_env=False)
    assert "anomaly" not in rec
    for _ in range(30):
        rec = prof.record(10.0, program_id=1, sig="aa", sample_env=False)
        assert "anomaly" not in rec   # the 500ms compile didn't pollute it
    # a compile AFTER a steady window is the classic mid-run straggler
    rec = prof.record(500.0, program_id=1, sig="aa", compiled=True,
                      sample_env=False)
    assert rec["anomaly"] == "recompile"
    assert reg.counter("steps/anomalies", reason="recompile").value == 1


def test_executor_run_feeds_step_profiler():
    import paddle_tpu as fluid
    from paddle_tpu.observability.steps import get_step_profiler

    prof = get_step_profiler()
    step0 = prof.step
    main, startup, y = _tiny_program()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    feed = {"x": np.zeros((2, 3), np.float32)}
    exe.run(main, feed=feed, fetch_list=[y])
    exe.run(main, feed=feed, fetch_list=[y])
    recs = prof.records()
    assert prof.step >= step0 + 3   # startup + compile + hit
    new = [r for r in recs if r["step"] > step0]
    assert any(r["compile"] for r in new)
    assert any(not r["compile"] for r in new)
    assert all("wall_ms" in r and "sig" in r for r in new)


# -- flight recorder -------------------------------------------------------

def test_is_oom_markers_and_types():
    from paddle_tpu.observability import flight

    assert flight.is_oom(RuntimeError("RESOURCE_EXHAUSTED: out of HBM"))
    assert flight.is_oom(ValueError("Out of memory while allocating"))
    assert not flight.is_oom(ValueError("shape mismatch"))
    XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
    assert flight.is_oom(XlaRuntimeError("anything"))


def test_flight_guard_dumps_on_injected_oom_and_reraises(
        tmp_path, monkeypatch):
    """THE acceptance property: a RESOURCE_EXHAUSTED raised inside
    Executor.run produces a post-mortem dump (step records, registry
    snapshot, device memory, forensic sections) and the original
    exception propagates unchanged."""
    import paddle_tpu as fluid
    from paddle_tpu.core import executor as executor_mod

    monkeypatch.setenv("PDTPU_FLIGHT_DIR", str(tmp_path))
    rec = obs.get_flight_recorder()
    rec.reset()

    main, startup, y = _tiny_program()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    feed = {"x": np.zeros((2, 3), np.float32)}
    exe.run(main, feed=feed, fetch_list=[y])   # steady steps in the ring

    boom = RuntimeError("RESOURCE_EXHAUSTED: fake OOM for test")

    def explode(self, state, fd, key):
        raise boom

    monkeypatch.setattr(executor_mod._AutoLayoutStep, "__call__", explode)
    with pytest.raises(RuntimeError) as ei:
        exe.run(main, feed=feed, fetch_list=[y])
    assert ei.value is boom   # unchanged, not wrapped

    dumps = sorted(tmp_path.glob("flight_*.json"))
    assert len(dumps) == 1
    with open(dumps[0]) as f:
        dump = json.load(f)
    assert dump["exception"]["type"] == "RuntimeError"
    assert "RESOURCE_EXHAUSTED" in dump["exception"]["message"]
    assert dump["context"]["where"] == "Executor.run"
    assert dump["steps"], "ring of step records missing"
    assert "registry" in dump and "device_memory" in dump
    assert "compiled_signatures" in dump["sections"]
    assert rec.last_dump_path == str(dumps[0])


def test_flight_guard_ignores_non_oom_errors(tmp_path, monkeypatch):
    monkeypatch.setenv("PDTPU_FLIGHT_DIR", str(tmp_path))
    rec = obs.get_flight_recorder()
    rec.reset()
    with pytest.raises(ValueError):
        with rec.guard("test/site"):
            raise ValueError("shape mismatch")
    assert not list(tmp_path.glob("flight_*.json"))
    assert rec.last_dump is None


def test_flight_dump_section_errors_captured_inline(monkeypatch):
    from paddle_tpu.observability import flight

    flight.register_dump_section("broken", lambda: 1 / 0)
    try:
        rec = flight.FlightRecorder(step_cap=4)
        rec.record_failure(RuntimeError("RESOURCE_EXHAUSTED: x"))
        assert "ZeroDivisionError" in \
            rec.last_dump["sections"]["broken"]["error"]
    finally:
        flight.unregister_dump_section("broken")


# -- HTTP introspection plane ----------------------------------------------

def _http_get(url):
    import urllib.error
    import urllib.request
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


@pytest.fixture
def introspection():
    from paddle_tpu.observability import http as ihttp
    srv = ihttp.IntrospectionServer(port=0).start()
    yield srv
    srv.stop()


def test_http_metrics_endpoints(introspection):
    from paddle_tpu.observability.steps import get_step_profiler

    get_step_profiler().record(1.0, program_id=7, sig="sg",
                               sample_env=False)
    code, body = _http_get(introspection.url + "/metrics")
    assert code == 200
    assert "# TYPE steps_total counter" in body
    assert "steps_wall_ms_count" in body
    code, body = _http_get(introspection.url + "/metrics.json")
    assert code == 200
    snap = json.loads(body)
    assert snap["steps/total"] >= 1


def test_http_debug_and_404(introspection):
    from paddle_tpu.observability.steps import get_step_profiler

    for _ in range(3):
        get_step_profiler().record(2.0, program_id=9, sig="dd",
                                   sample_env=False)
    code, body = _http_get(introspection.url + "/debug/steps?n=2")
    assert code == 200
    assert len(json.loads(body)["records"]) == 2
    code, body = _http_get(introspection.url + "/debug/flight")
    assert code == 200
    flight = json.loads(body)
    assert {"steps", "events", "last_dump_path", "last_dump"} <= set(flight)
    code, _ = _http_get(introspection.url + "/nope")
    assert code == 404


def test_healthz_aggregation_and_503(introspection):
    from paddle_tpu.observability import http as ihttp

    code, body = _http_get(introspection.url + "/healthz")
    assert code == 200 and json.loads(body)["status"] == "ok"
    ihttp.register_health_check("t/degraded", lambda: ("degraded", "warm"))
    try:
        code, body = _http_get(introspection.url + "/healthz")
        assert code == 200 and json.loads(body)["status"] == "degraded"
        ihttp.register_health_check("t/dead", lambda: 1 / 0)
        code, body = _http_get(introspection.url + "/healthz")
        assert code == 503
        parsed = json.loads(body)
        assert parsed["status"] == "failing"
        assert "ZeroDivisionError" in parsed["checks"]["t/dead"]["detail"]
    finally:
        ihttp.unregister_health_check("t/degraded")
        ihttp.unregister_health_check("t/dead")


def test_serve_introspection_idempotent_and_env(monkeypatch):
    from paddle_tpu.observability import http as ihttp

    ihttp.stop_introspection()
    try:
        srv = ihttp.serve_introspection(0)
        assert srv.port > 0
        assert ihttp.serve_introspection(0) is srv
        # env-driven startup path used by Executor / InferenceServer
        monkeypatch.setenv("PDTPU_INTROSPECT_PORT", str(srv.port))
        assert ihttp.maybe_serve_from_env() is srv
        code, _ = _http_get(srv.url + "/metrics")
        assert code == 200
    finally:
        ihttp.stop_introspection()
    monkeypatch.delenv("PDTPU_INTROSPECT_PORT")
    assert ihttp.maybe_serve_from_env() is None


# -- serving health checks -------------------------------------------------

def test_serving_registers_and_unregisters_health_checks(predictor):
    from paddle_tpu import serving
    from paddle_tpu.observability import http as ihttp

    srv = serving.InferenceServer(predictor, num_workers=1)
    srv.start()
    try:
        names = list(srv._health_names)
        assert sorted(n.rsplit("/", 1)[1] for n in names) == \
            ["deadlines", "queue", "workers"]
        overall, detail = ihttp.run_health_checks()
        assert overall == "ok"
        for n in names:
            assert detail[n]["status"] == "ok"
        # a genuinely served request keeps deadlines ok
        out = srv.submit({"x": np.zeros((2, IN_DIM), np.float32)}).result(30)
        assert out[0].shape == (2, 3)
    finally:
        srv.stop()
    _, detail = ihttp.run_health_checks()
    assert not any(n in detail for n in names)


# -- timeline --flight renderer --------------------------------------------

def test_timeline_renders_flight_dump(tmp_path, capsys):
    from paddle_tpu.tools import timeline

    dump = {
        "pid": 123,
        "exception": {"type": "XlaRuntimeError",
                      "message": "RESOURCE_EXHAUSTED: 1.5G over"},
        "context": {"where": "Executor.run"},
        "device_memory": {"TPU_0": {"bytes_in_use": 15_000_000_000,
                                    "peak_bytes_in_use": 15_800_000_000,
                                    "bytes_limit": 16_000_000_000}},
        "steps": [
            {"step": 41, "wall_ms": 12.5, "compile": False, "sig": "ab12",
             "queue_depth": 3, "h2d_ms": 0.4,
             "mem_bytes_in_use": 14_000_000_000},
            {"step": 42, "wall_ms": 480.0, "compile": False, "sig": "ab12",
             "anomaly": "slow_step", "deviation": 92.1},
        ],
        "events": [{"level": "warning", "message": "slow step: step=42"}],
    }
    path = tmp_path / "flight.json"
    path.write_text(json.dumps(dump))
    timeline.main(["--flight", str(path)])
    out = capsys.readouterr().out
    assert "XlaRuntimeError during Executor.run (pid 123)" in out
    assert "RESOURCE_EXHAUSTED" in out
    assert "slow_step (92.1x sigma)" in out
    assert "15.00GB" in out and "limit=16.00GB" in out
    assert "slow step: step=42" in out


def test_serving_bench_with_introspection_scrape(tmp_path):
    from paddle_tpu.core import program as prog_mod
    from paddle_tpu.observability import http as ihttp
    from paddle_tpu.tools import serving_bench as sb

    ihttp.stop_introspection()
    mpath = str(tmp_path / "m.json")
    old = prog_mod._main_program, prog_mod._startup_program
    prog_mod._main_program = prog_mod.Program()
    prog_mod._startup_program = prog_mod.Program()
    try:
        rc = sb.main(["--requests", "8", "--concurrency", "4",
                      "--buckets", "2,4", "--batch-delay-ms", "1",
                      "--in-dim", "6", "--hidden", "8", "--layers", "1",
                      "--skip-sequential", "--introspect-port", "0",
                      "--metrics-out", mpath])
    finally:
        prog_mod._main_program, prog_mod._startup_program = old
        ihttp.stop_introspection()
    assert rc == 0
    with open(mpath) as f:
        loaded = json.load(f)
    scrape = loaded["bench/introspection"]
    assert scrape["/metrics"]["status"] == 200
    assert scrape["/metrics"]["bytes"] > 0
    assert scrape["/healthz"]["status"] == 200
