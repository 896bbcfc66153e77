"""Cross-process observability (ISSUE 13): trace context propagation
over the PS and fleet RPC planes, metrics federation, and the autoscaler
signal surface.

The load-bearing claims: (1) one routed request / one training step is
ONE distributed trace — client spans in the caller, server spans in the
pserver / worker subprocess, linked by trace_id/parent_id over the
existing JSON frame header, surviving torn-frame retries with the same
trace_id; (2) a `FederatedScraper` sweep reaches every process kind
(HTTP introspection, pserver socket op, in-process handle), re-exports
with process/role/shard labels through the SAME renderer as local
/metrics, and distills the ROADMAP-5 autoscaler gauges; (3) the fleet
timeline merger aligns per-process clocks from RPC send/recv pairs and
draws flow arrows.
"""
import json
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid  # noqa: F401  (backend init, scope fixtures)
from paddle_tpu.observability import context as trace_ctx
from paddle_tpu.observability.federate import (FederatedScraper,
                                               ScrapeTarget,
                                               install_scraper)
from paddle_tpu.observability.registry import (Registry, get_registry,
                                               render_prometheus)
from paddle_tpu.observability.tracer import (get_tracer, server_span,
                                             start_trace, trace_span)
from paddle_tpu.ps import (EmbeddingShard, RangeSpec, ShardServer,
                           SocketClient)

from test_ps_faults import _TearingProxy, _fast_retry

V = 64


def _events(trace=None):
    """Non-metadata events of a chrome trace (default: local tracer)."""
    trace = trace or get_tracer().export_chrome_trace()
    return [e for e in trace["traceEvents"] if e.get("ph") != "M"]


def _spans_named(events, name):
    return [e for e in events if e.get("name") == name
            and e.get("ph") == "B"]


# -- context ---------------------------------------------------------------

def test_trace_context_identity_and_wire():
    root = trace_ctx.new_trace()
    assert root.parent_id is None
    child = root.child()
    assert child.trace_id == root.trace_id
    assert child.span_id != root.span_id
    assert child.parent_id == root.span_id
    # server-side adoption: fresh span in the sender's trace, parented
    # to the SENDER'S span (not its parent)
    adopted = trace_ctx.from_wire(child.to_wire())
    assert adopted.trace_id == root.trace_id
    assert adopted.parent_id == child.span_id
    assert adopted.span_id not in (root.span_id, child.span_id)
    # malformed headers never fail an RPC
    for bad in (None, "x", {}, {"trace_id": "t"}, {"trace_id": 3,
                                                   "span_id": "s"}):
        assert trace_ctx.from_wire(bad) is None


def test_trace_context_thread_local_use():
    assert trace_ctx.current() is None
    ctx = trace_ctx.new_trace()
    with trace_ctx.use(ctx):
        assert trace_ctx.current() is ctx
        seen = []
        t = threading.Thread(  # thread-locals don't follow threads...
            target=lambda: seen.append(trace_ctx.current()))
        t.start()
        t.join()
        assert seen == [None]
        # ...the hop idiom re-activates the captured context
        t = threading.Thread(
            target=lambda: [seen.append(trace_ctx.current())
                            for _ in [trace_ctx.use(ctx).__enter__()]])
        t.start()
        t.join()
        assert seen[-1] is ctx
    assert trace_ctx.current() is None
    with trace_ctx.use(None):  # no-op form: call sites don't branch
        assert trace_ctx.current() is None


def test_spans_stamp_distributed_ids():
    tr = get_tracer()
    tr.clear()
    with trace_span("plain"):  # no active trace: no ids, no cost
        pass
    with start_trace("root") as _:
        root = trace_ctx.current()
        with trace_span("inner"):
            inner = trace_ctx.current()
            assert inner.trace_id == root.trace_id
            assert inner.parent_id == root.span_id
    assert trace_ctx.current() is None
    evs = _events()
    (plain,) = _spans_named(evs, "plain")
    assert "trace_id" not in (plain.get("args") or {})
    (root_ev,) = _spans_named(evs, "root")
    (inner_ev,) = _spans_named(evs, "inner")
    assert root_ev["args"]["trace_id"] == inner_ev["args"]["trace_id"]
    assert inner_ev["args"]["parent_id"] == root_ev["args"]["span_id"]
    # server_span with a bad header degrades to a plain local span
    with server_span("srv", None):
        pass
    (srv,) = _spans_named(_events(), "srv")
    assert "trace_id" not in (srv.get("args") or {})


# -- satellite 1: exposition conformance local vs federated ----------------

def test_prometheus_federated_output_matches_local():
    """`prometheus_text` == `render_prometheus(series())` by
    construction; the federated renderer must emit IDENTICAL lines plus
    appended process/role labels — same # TYPE lines, same escaping of
    hostile label values (quotes, backslashes, newlines)."""
    reg = Registry()
    hostile = 'x:f32[8,128] "quoted" back\\slash\nnewline'
    reg.counter("t/reqs", sig=hostile).inc(3)
    reg.gauge("t/depth").set(2.0)
    reg.histogram("t/lat_ms", sig=hostile).observe(1.5)
    local = reg.prometheus_text(deep=True)
    assert local == render_prometheus(reg.series(deep=True))
    # one # TYPE line per metric name, typed correctly
    assert local.count("# TYPE t_reqs counter") == 1
    assert local.count("# TYPE t_depth gauge") == 1
    assert local.count("# TYPE t_lat_ms summary") == 1
    # escaping: raw newline/quote/backslash never appear un-escaped
    esc = 'x:f32[8,128] \\"quoted\\" back\\\\slash\\nnewline'
    assert f'sig="{esc}"' in local

    fed = FederatedScraper(
        [ScrapeTarget.call(lambda: reg.series(deep=True),
                           name='w "1"', role="worker")]
    ).prometheus_text(refresh=True)
    # by construction: the federated text IS the shared renderer with
    # extra labels, nothing else
    assert fed == render_prometheus(
        reg.series(deep=True),
        extra_labels=(("process", 'w "1"'), ("role", "worker")))
    # every labeled local sample reappears verbatim with the target
    # labels appended inside the same brace group (quantile pseudo-label
    # sorts after the extras, checked separately below)
    for line in local.splitlines():
        if line.startswith("#") or "{" not in line or "quantile=" in line:
            continue
        head, tail = line.rsplit("}", 1)
        assert f'{head},process="w \\"1\\"",role="worker"}}{tail}' in fed
    assert (f't_lat_ms{{sig="{esc}",process="w \\"1\\"",role="worker",'
            'quantile="0.5"} 1.5') in fed
    # label-less local samples gain a brace group in federated output
    assert 't_depth{process="w \\"1\\"",role="worker"} 2.0' in fed
    assert fed.count("# TYPE t_depth gauge") == 1


# -- satellite 4: trace propagation across real sockets --------------------

def test_ps_trace_propagates_to_subprocess_shard_server():
    """A pull against a REAL pserver subprocess: the server-side span
    comes back (trace_export op) carrying the client's trace_id and the
    client RPC span's id as parent."""
    import os
    runner = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "ps_server_runner.py")
    p = subprocess.Popen([sys.executable, runner, "--port", "0",
                          "--table", f"tb:0:{V}"],
                         stdout=subprocess.PIPE,
                         stderr=subprocess.DEVNULL, text=True)
    try:
        ep = p.stdout.readline().strip()
        assert ep, "pserver runner died at boot"
        get_tracer().clear()
        c = SocketClient(ep, retries=0)
        try:
            with start_trace("test/req"):
                root = trace_ctx.current()
                c.pull("tb", np.array([1, 5, V - 1], dtype=np.int64))
            remote = c.trace_export()
        finally:
            c.close()
        # client side: ps/rpc/pull span in OUR trace
        (cli,) = [e for e in _spans_named(_events(), "ps/rpc/pull")
                  if (e.get("args") or {}).get("trace_id")
                  == root.trace_id]
        assert cli["args"]["rpc"] == "client"
        assert cli["args"]["endpoint"] == ep
        # server side: ps/pull span in the SUBPROCESS trace, parented to
        # the client span
        srv_spans = [e for e in _spans_named(_events(remote), "ps/pull")
                     if (e.get("args") or {}).get("trace_id")
                     == root.trace_id]
        assert len(srv_spans) == 1
        assert srv_spans[0]["args"]["parent_id"] == cli["args"]["span_id"]
        assert srv_spans[0]["args"]["rpc"] == "server"
        assert srv_spans[0]["pid"] != cli["pid"]
    finally:
        p.kill()
        p.wait()


def test_torn_frame_retry_keeps_trace_id_fresh_span(monkeypatch):
    """A torn reply forces a re-send: the retry attempt must be a SECOND
    client span in the SAME trace — fresh span_id, `retry: 1` tag — so
    the timeline shows two RPCs, not a forked trace."""
    _fast_retry(monkeypatch)
    srv = ShardServer([EmbeddingShard("tb", 0, V)]).serve_in_thread()
    proxy = _TearingProxy(srv.endpoint)
    proxy.start()
    c = SocketClient(proxy.endpoint)
    try:
        get_tracer().clear()
        with start_trace("test/torn"):
            root = trace_ctx.current()
            c.pull("tb", np.array([1, 2], dtype=np.int64))
        assert proxy.tears_left == 0
        attempts = [e for e in _spans_named(_events(), "ps/rpc/pull")
                    if (e.get("args") or {}).get("trace_id")
                    == root.trace_id]
        assert len(attempts) == 2
        first, second = sorted(attempts, key=lambda e: e["ts"])
        assert "retry" not in first["args"]
        assert second["args"]["retry"] == 1
        assert second["args"]["span_id"] != first["args"]["span_id"]
    finally:
        c.close()
        proxy.stop()
        srv.stop()


def test_fleet_worker_rpc_propagates_trace(xla_8dev_subprocess_env):
    """The other RPC plane: a ProcessReplica infer carries the header to
    the fleet worker subprocess, whose `serve/infer` server span adopts
    the caller's trace."""
    import test_serving_fleet as tsf
    from paddle_tpu.serving.fleet.registry import ModelRegistry
    from paddle_tpu.serving.fleet.replica import ProcessReplica

    d = tsf._save_mlp("/tmp/pdtpu_obs_worker_model", seed=3)
    mv = ModelRegistry().register("v1", d)
    rep = None
    try:
        rep = ProcessReplica("r0", mv, buckets=tsf.BUCKETS,
                             env=xla_8dev_subprocess_env,
                             server_kwargs={"max_batch_delay_ms": 1.0})
        get_tracer().clear()
        feed = {"x": np.random.RandomState(0).rand(
            2, tsf.IN_DIM).astype(np.float32)}
        with start_trace("test/infer"):
            root = trace_ctx.current()
            out = rep.submit(feed).result(timeout=120)
        assert out[0].shape == (2, tsf.CLASSES)
        (cli,) = [e for e in _spans_named(_events(), "fleet/rpc/infer")
                  if (e.get("args") or {}).get("trace_id")
                  == root.trace_id]
        remote = rep.trace_export()
        srv = [e for e in _spans_named(_events(remote), "serve/infer")
               if (e.get("args") or {}).get("trace_id") == root.trace_id]
        assert len(srv) == 1
        assert srv[0]["args"]["parent_id"] == cli["args"]["span_id"]
        assert srv[0]["pid"] != cli["pid"]
        # the worker's metrics surface exists too (federation target)
        names = {s["name"] for s in rep.metrics()}
        assert "serving/requests" in names
    finally:
        if rep is not None:
            rep.stop()


# -- federation ------------------------------------------------------------

def test_federated_scraper_merges_and_derives_signals():
    """One sweep over a pserver socket target, an in-process call
    target, and a dead endpoint: per-target labels land in the doc, the
    dead target is recorded (not raised), and the autoscaler gauges
    distill out of the merged series."""
    srv = ShardServer([EmbeddingShard("tb", 0, V)]).serve_in_thread()
    # The in-thread pserver target serves the process-global registry, so
    # straggler anomalies recorded by earlier tests in this process ride
    # along in its series — only the stub's contribution is exact.
    pre_anomalies = sum(
        float(s.get("value") or 0.0)
        for s in get_registry().series(deep=True)
        if s.get("name") == "steps/anomalies")
    # the same for shard 0's pull latency: the signal is the worst p99 over
    # the targets, and which ps tests ran in this process first depends on
    # how the run's files were dealt out
    pre_pull_p99 = max(
        (float((s.get("summary") or {}).get("p99") or 0.0)
         for s in get_registry().series(deep=True)
         if s.get("name") == "ps/shard_pull_ms"
         and (s.get("labels") or {}).get("shard") == "0"), default=0.0)
    stub = [{"name": "ps/shard_pull_ms", "type": "summary",
             "labels": {"shard": "0"},
             "summary": {"count": 4, "sum": 8.0, "p50": 2.0, "p95": 3.0,
                         "p99": 3.5}},
            {"name": "serving/queue_depth", "type": "gauge", "labels": {},
             "value": 7.0},
            {"name": "steps/anomalies", "type": "counter",
             "labels": {"reason": "slow_step"}, "value": 2}]
    try:
        sc = FederatedScraper(
            [ScrapeTarget.ps(srv.endpoint, shard=0),
             ScrapeTarget.call(lambda: stub, name="w0", role="worker"),
             ScrapeTarget.ps("127.0.0.1:9", shard=1)])
        doc = sc.scrape_once()
        assert doc["ok"] is False  # port 9 refused
        by_name = {t["process"]: t for t in doc["targets"]}
        assert by_name["w0"]["ok"] and by_name["w0"]["role"] == "worker"
        ps_t = by_name[f"pserver:{srv.endpoint}"]
        assert ps_t["ok"] and ps_t["shard"] == 0
        assert any(s["name"] == "ps/server_requests"
                   for s in ps_t["series"])
        sig = doc["signals"]
        # per-key: the pserver target may carry real shard_pull/queue
        # series from earlier in-process tests alongside the stub's, and the
        # signal is the worst of them
        expected_pull = max(3.5, pre_pull_p99)
        assert sig["ps_pull_p99_ms"]["0"] == expected_pull
        assert sig["queue_depth"]["w0"] == 7.0
        assert sig["stragglers"] == 2.0 + pre_anomalies
        assert sig["targets_unreachable"] == 1
        reg = get_registry()
        assert reg.gauge("autoscale/ps_pull_p99_ms",
                         shard="0").value == expected_pull
        assert reg.gauge("autoscale/queue_depth",
                         process="w0").value == 7.0
        assert reg.gauge("autoscale/targets_unreachable").value == 1.0
    finally:
        srv.stop()


@pytest.fixture()
def introspection():
    from paddle_tpu.observability import http as ihttp
    s = ihttp.IntrospectionServer(port=0)
    s.start()
    yield s
    s.stop()


def test_fleet_endpoint_and_metrics_series(introspection):
    """/metrics/series is the structured scrape; /fleet 404s with no
    scraper, then serves the federated doc (503 while any target is
    down, 200 when all answer); federated text rides /metrics."""
    from test_observability import _http_get

    code, body = _http_get(introspection.url + "/metrics/series")
    assert code == 200
    series = json.loads(body)
    assert isinstance(series, list) and all("name" in s for s in series)

    code, _ = _http_get(introspection.url + "/fleet")
    assert code == 404
    srv = ShardServer([EmbeddingShard("tb", 0, V)]).serve_in_thread()
    sc = FederatedScraper([
        ScrapeTarget.ps(srv.endpoint, shard=0),
        ScrapeTarget.http(introspection.url, name="self", role="worker")])
    install_scraper(sc)
    try:
        code, body = _http_get(introspection.url + "/fleet")
        assert code == 200
        doc = json.loads(body)
        assert doc["ok"] is True
        assert {t["process"] for t in doc["targets"]} == {
            f"pserver:{srv.endpoint}", "self"}
        # the last scrape's federated text is appended to /metrics with
        # per-process labels
        code, body = _http_get(introspection.url + "/metrics")
        assert code == 200
        assert f'process="pserver:{srv.endpoint}"' in body
        assert 'shard="0"' in body
        srv.stop()
        code, body = _http_get(introspection.url + "/fleet")
        assert code == 503
        assert json.loads(body)["ok"] is False
    finally:
        install_scraper(None)
        srv.stop()
    code, _ = _http_get(introspection.url + "/fleet")
    assert code == 404


def test_ps_admin_fleet_subcommand(capsys):
    """Operator surface: one table row per process, exit 0 when every
    scrape answered, 1 when any failed, --json emits the /fleet doc."""
    from paddle_tpu.tools import ps_admin

    srv = ShardServer([EmbeddingShard("tb", 0, V)]).serve_in_thread()
    try:
        rc = ps_admin.main(["fleet", "--endpoints", srv.endpoint])
        out = capsys.readouterr().out
        assert rc == 0
        assert "pserver" in out and "autoscaler signals:" in out
        rc = ps_admin.main(["fleet", "--endpoints",
                            srv.endpoint + ",127.0.0.1:9", "--json"])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 1 and doc["ok"] is False
        assert [t["ok"] for t in doc["targets"]] == [True, False]
        # no endpoints anywhere is a usage error, not a crash
        with pytest.raises(SystemExit):
            ps_admin.main(["fleet", "--endpoints", ""])
    finally:
        srv.stop()


# -- timeline merge --------------------------------------------------------

def test_merge_fleet_traces_aligns_clocks_and_links():
    """Two processes whose perf_counter epochs differ by 5000 us: the
    RPC send/recv pair recovers the offset, the server span lands inside
    the client span on the merged timeline, s/f flow events link them,
    and each source keeps its own pid."""
    from paddle_tpu.tools.timeline import merge_fleet_traces

    client = {"traceEvents": [
        {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
         "args": {"name": "client host"}},
        {"name": "fleet/rpc/infer", "ph": "B", "ts": 100.0, "pid": 1,
         "tid": 7, "args": {"rpc": "client", "trace_id": "t1",
                            "span_id": "c1"}},
        {"name": "fleet/rpc/infer", "ph": "E", "ts": 200.0, "pid": 1,
         "tid": 7}]}
    server = {"traceEvents": [
        {"name": "serve/infer", "ph": "B", "ts": 5120.0, "pid": 1,
         "tid": 9, "args": {"rpc": "server", "trace_id": "t1",
                            "span_id": "s1", "parent_id": "c1"}},
        {"name": "serve/infer", "ph": "E", "ts": 5180.0, "pid": 1,
         "tid": 9}]}
    merged = merge_fleet_traces([client, server], ["client", "server"])
    evs = merged["traceEvents"]
    (srv_b,) = [e for e in evs if e.get("name") == "serve/infer"
                and e.get("ph") == "B"]
    (cli_b,) = [e for e in evs if e.get("name") == "fleet/rpc/infer"
                and e.get("ph") == "B"]
    # theta = ((5120-100)+(5180-200))/2 = 5000 -> 5120 aligns to 120
    assert srv_b["ts"] == pytest.approx(120.0)
    assert cli_b["ts"] == pytest.approx(100.0)
    assert srv_b["pid"] != cli_b["pid"]  # distinct tracks per process
    flows = [e for e in evs if e.get("ph") in ("s", "f")]
    assert sorted(e["ph"] for e in flows) == ["f", "s"]
    assert len({e["id"] for e in flows}) == 1
    names = [e["args"]["name"] for e in evs
             if e.get("name") == "process_name"]
    assert any("client" in n for n in names)
    assert any("server" in n for n in names)


# -- satellite 2: anomalies as instant events ------------------------------

def test_step_anomalies_emit_instant_and_flight_events():
    from paddle_tpu.observability.flight import get_flight_recorder
    from paddle_tpu.observability.steps import StepProfiler

    reg = get_registry()
    get_tracer().clear()
    prof = StepProfiler(window=64, min_samples=8)
    slow0 = reg.counter("steps/anomalies", reason="slow_step").value
    rec0 = reg.counter("steps/anomalies", reason="recompile").value
    for _ in range(10):
        prof.record(1.0, program_id=1, sig="s", sample_env=False)
    prof.record(50.0, program_id=1, sig="s", sample_env=False)
    prof.record(5.0, program_id=1, sig="s", compiled=True,
                sample_env=False)
    assert reg.counter("steps/anomalies",
                       reason="slow_step").value == slow0 + 1
    assert reg.counter("steps/anomalies",
                       reason="recompile").value == rec0 + 1
    evs = [e for e in _events() if e.get("ph") == "i"]
    (slow,) = [e for e in evs if e["name"] == "steps/slow_step"]
    assert slow["args"]["reason"] == "slow_step"
    assert slow["args"]["wall_ms"] == 50.0
    assert slow["args"]["deviation"] >= 1
    assert any(e["name"] == "steps/recompile" for e in evs)
    flight = [e for e in get_flight_recorder().contents()["events"]
              if e.get("reason") in ("slow_step", "recompile")]
    assert len(flight) >= 2


# -- end to end: step-rooted PS trace --------------------------------------

def test_train_step_roots_one_trace_across_shard_pulls():
    """`PsEmbeddingTier.run_step` roots a trace; the pulls it triggers
    (socket RPCs on pool threads) must join it, proving the thread-hop
    re-activation in ShardedTable works under the real tier."""
    from paddle_tpu.ps import ShardedTable, make_shards

    spec = RangeSpec.even(V, 2)
    servers = [ShardServer([sh]).serve_in_thread()
               for sh in make_shards("tb", spec)]
    table = ShardedTable("tb", spec,
                         [SocketClient(s.endpoint) for s in servers])
    try:
        get_tracer().clear()
        with start_trace("ps/train_step"):
            root = trace_ctx.current()
            table.pull(np.arange(V, dtype=np.int64))
        pulls = [e for e in _spans_named(_events(), "ps/rpc/pull")
                 if (e.get("args") or {}).get("trace_id")
                 == root.trace_id]
        # one client RPC span per shard, all in the step's trace even
        # though they ran on pool threads
        assert len(pulls) == 2
        assert {e["args"]["endpoint"] for e in pulls} == {
            s.endpoint for s in servers}
    finally:
        table.close()
        for s in servers:
            s.stop()


# -- target churn (ISSUE 17 satellite) -------------------------------------

def test_scraper_target_churn_retires_stale_autoscale_gauges():
    """An autoscaled fleet adds and removes targets between sweeps. The
    distilled autoscale/* gauges must follow: a vanished shard/process
    leaves NO stale gauge behind (an autoscaler keying on it would act
    on a ghost), and re-adding a target under the SAME name replaces
    the old one instead of double-counting its series."""
    def stub(shard, depth):
        return [{"name": "ps/shard_pull_ms", "type": "summary",
                 "labels": {"shard": str(shard)},
                 "summary": {"count": 4, "sum": 8.0, "p50": 2.0,
                             "p95": 3.0, "p99": 3.5}},
                {"name": "serving/queue_depth", "type": "gauge",
                 "labels": {}, "value": float(depth)}]

    reg = get_registry()
    sc = FederatedScraper(
        [ScrapeTarget.call(lambda: stub(77, 5), name="churn-a",
                           role="worker"),
         ScrapeTarget.call(lambda: stub(78, 9), name="churn-b",
                           role="worker")])
    try:
        sc.scrape_once()
        assert reg.gauge("autoscale/ps_pull_p99_ms",
                         shard="77").value == 3.5
        assert reg.gauge("autoscale/queue_depth",
                         process="churn-b").value == 9.0

        # target vanishes: its per-shard and per-process gauges retire
        # on the next sweep rather than freezing at the last value
        assert sc.remove_target("churn-b") is True
        assert sc.remove_target("churn-b") is False  # already gone
        doc = sc.scrape_once()
        assert {t["process"] for t in doc["targets"]} == {"churn-a"}
        live = {(s["name"], tuple(sorted(s["labels"].items())))
                for s in reg.series()}
        assert ("autoscale/ps_pull_p99_ms",
                (("shard", "78"),)) not in live
        assert ("autoscale/queue_depth",
                (("process", "churn-b"),)) not in live
        assert ("autoscale/ps_pull_p99_ms", (("shard", "77"),)) in live

        # same-name re-add REPLACES: one target row, one series set, the
        # new reader's numbers (not a sum with the stale registration)
        sc.add_target(ScrapeTarget.call(lambda: stub(78, 2),
                                        name="churn-b", role="worker"))
        sc.add_target(ScrapeTarget.call(lambda: stub(78, 4),
                                        name="churn-b", role="worker"))
        doc = sc.scrape_once()
        rows = [t for t in doc["targets"] if t["process"] == "churn-b"]
        assert len(rows) == 1
        assert doc["signals"]["queue_depth"]["churn-b"] == 4.0
        assert reg.gauge("autoscale/queue_depth",
                         process="churn-b").value == 4.0
        assert reg.gauge("autoscale/ps_pull_p99_ms",
                         shard="78").value == 3.5
    finally:
        for g in (("autoscale/ps_pull_p99_ms", {"shard": "77"}),
                  ("autoscale/ps_pull_p99_ms", {"shard": "78"}),
                  ("autoscale/queue_depth", {"process": "churn-a"}),
                  ("autoscale/queue_depth", {"process": "churn-b"})):
            reg.remove(g[0], **g[1])
