"""paddle_tpu.observability — unified runtime telemetry.

Reference analog: the reference framework's observability pipeline was
RecordEvent/DeviceTracer (platform/profiler.h:166) streaming into
profiler.proto, converted to chrome://tracing by ``tools/timeline.py``,
plus the sorted per-op profiler summary. The TPU build splits the same
capability along its natural seam:

- **Registry** (registry.py) — process-wide, thread-safe counters /
  gauges / histograms (labeled, percentile snapshots), with a
  Prometheus-style text exporter, JSON dump, and composition: per-server
  `serving.Metrics` registries attach as children so ONE
  ``get_registry().snapshot()`` shows executor cache hits/misses,
  compile time, and serving latency together.
- **trace_span / Tracer** (tracer.py) — host-side nested wall-clock
  spans per thread, exported as chrome-trace JSON (chrome://tracing /
  Perfetto). Device-side tracing stays with jax.profiler (XPlane);
  every span is also a ``TraceAnnotation`` there, so under a profiler
  session the program's spans lie beside the device operations on one
  clock (``paddle_tpu.profiler.record_event`` is ``trace_span``).
  ``Executor.run`` records ``executor/step`` with its phases as
  children; ``get_tracer().spans()`` gives spans back with parent and
  self time, and ``python -m paddle_tpu.tools.timeline``
  merges/summarizes exported files.
- **scopes** (scopes.py) — the names inside the compiled step: the
  lowering writes ``[opt/]u.<unit>/op.<op type>`` into every instruction's
  metadata from the Program IR, ``op_scopes(exe.compiled_step(main))``
  reads phase, unit and op types back per HLO instruction, and
  ``hottest_step()`` hands out the step this process runs.
- **setup_account** (setup_account.py) — who staged which function, in
  which phase, how long: every trace, lowering, compile, cache read,
  relayout and first run in the process lands in
  ``setup/seconds{phase,reason}`` and as a ``setup/<phase>`` span under
  ``executor/compile+run`` (or a root ``executor/stage``), each second once;
  the op walk's time by op type in ``setup/trace_op_seconds{op}``.
- **RecompileWatchdog** (watchdog.py) — the executor reports every
  executable-cache miss; past a threshold the watchdog warns once,
  naming exactly which feed's shape/dtype diverged between the cached
  and the new signature (the actionable diagnosis of a recompile storm).
- **IntrospectionServer** (http.py) — stdlib HTTP server exposing the
  live process: ``/metrics`` (Prometheus text), ``/metrics.json``,
  ``/healthz`` (pluggable named checks), ``/debug/steps``,
  ``/debug/flight``. Start with ``serve_introspection(port)`` or by
  setting ``PDTPU_INTROSPECT_PORT``. While ``run_elastic`` runs it
  carries the ``elastic/progress`` (wedge detection,
  ``PDTPU_WEDGE_TIMEOUT``) and ``elastic/checkpoint`` (save in flight /
  writer died) checks; the crash-consistency stack also feeds the
  registry — ``checkpoint/fallback_steps``, ``checkpoint/write_retries``,
  ``elastic/guard_degraded``, and ``faults/injected{site,action}`` from
  the ``paddle_tpu.faults`` chaos harness.
- **StepProfiler** (steps.py) — one structured record per executor
  dispatch (wall time, signature, compile flag, dataio queue/h2d,
  fetch wait, device memory) in a rolling window, with a median/MAD
  straggler detector feeding ``steps/anomalies{reason=...}``.
- **FlightRecorder** (flight.py) — bounded ring of step records +
  warning events; on ``XlaRuntimeError``/``RESOURCE_EXHAUSTED`` the
  dispatch sites dump a post-mortem (steps, registry snapshot, device
  memory, compiled signatures, watchdog state) to ``PDTPU_FLIGHT_DIR``
  before re-raising.
- **SloEngine / AlertManager** (slo.py / alerts.py) — the judgment
  layer over the sensor plane: declarative `SloSpec`s compiled into
  recording rules evaluated on every `FederatedScraper` sweep, the
  standard multi-window multi-burn-rate page/warn formulation, a
  pending→firing→resolved alert state machine publishing
  ``ALERTS{alertname,severity,alertstate}``, pluggable sinks (file /
  webhook / callback — the autoscaler hook), an ``/alerts`` endpoint,
  an ``alerts`` health check, and alert-triggered flight dumps.
- **MetricsHistory / ProfileTrigger** (history.py / profile_trigger.py)
  — the root-cause loop: a bounded ring TSDB recording every scraper
  sweep (raw + 10 s + 120 s tiers, LRU memory cap, ``/history``
  endpoint, optional JSONL spill via ``PDTPU_HISTORY_DIR``), and an
  anomaly-triggered profiler that captures a bounded trace window on
  ``slow_step``/``recompile``/page events, diffs the per-kernel table
  against a recorded golden, and enriches the firing alert with the
  culprit kernels + the surrounding history window.
  ``tools/postmortem.py`` bundles all of it into one report.

Quick start::

    from paddle_tpu import observability as obs

    with obs.trace_span("train/epoch", epoch=e):
        exe.run(main, feed=..., fetch_list=[loss])

    print(obs.get_registry().report())           # text table
    obs.get_registry().dump_json("metrics.json") # registry export
    obs.get_tracer().export_chrome_trace("host_trace.json")
"""
from . import calibrate  # noqa: F401
from . import context  # noqa: F401
from . import federate  # noqa: F401
from . import perf  # noqa: F401
from . import scopes  # noqa: F401
from . import setup_account  # noqa: F401
from .alerts import (Alert, AlertFiringError, AlertManager,  # noqa: F401
                     FileSink, WebhookSink, get_alert_manager,
                     install_alert_manager)
from .calibrate import Calibration, get_calibration  # noqa: F401
from .context import TraceContext  # noqa: F401
from .federate import (FederatedScraper, ScrapeTarget,  # noqa: F401
                       get_scraper, install_scraper)
from .flight import (FlightRecorder, get_flight_recorder,  # noqa: F401
                     is_oom, register_dump_section,
                     unregister_dump_section)
from .history import (MetricsHistory, get_history,  # noqa: F401
                      install_history)
from .http import (IntrospectionServer, maybe_serve_from_env,  # noqa: F401
                   register_health_check, run_health_checks,
                   serve_introspection, stop_introspection,
                   unregister_health_check)
from .memory import (device_memory_stats,  # noqa: F401
                     per_device_state_bytes, record_state_memory)
from .perf import CostLedger, ProgramCost, attribute, get_ledger  # noqa: F401
from .profile_trigger import (ProfileTrigger, get_trigger,  # noqa: F401
                              golden_path, install_trigger,
                              record_golden)
from .registry import (Counter, Gauge, Histogram, Registry,  # noqa: F401
                       get_registry, render_prometheus)
from .slo import (BURN_RATE_WINDOWS, SloEngine, SloSpec,  # noqa: F401
                  default_slos)
from .steps import StepProfiler, get_step_profiler  # noqa: F401
from .tracer import (Tracer, get_tracer, pair_spans,  # noqa: F401
                     server_span, start_trace, step_span, trace_span)
from .watchdog import (RecompileWarning, RecompileWatchdog,  # noqa: F401
                       diff_signatures, get_watchdog)

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "get_registry",
    "render_prometheus",
    "Calibration", "get_calibration", "calibrate",
    "CostLedger", "ProgramCost", "attribute", "get_ledger", "perf",
    "TraceContext", "context",
    "FederatedScraper", "ScrapeTarget", "install_scraper", "get_scraper",
    "device_memory_stats", "per_device_state_bytes", "record_state_memory",
    "Tracer", "get_tracer", "trace_span", "step_span", "start_trace",
    "server_span", "pair_spans", "scopes",
    "RecompileWarning", "RecompileWatchdog", "diff_signatures",
    "get_watchdog",
    "FlightRecorder", "get_flight_recorder", "is_oom",
    "register_dump_section", "unregister_dump_section",
    "StepProfiler", "get_step_profiler",
    "IntrospectionServer", "serve_introspection", "stop_introspection",
    "maybe_serve_from_env", "register_health_check",
    "unregister_health_check", "run_health_checks",
    "SloSpec", "SloEngine", "default_slos", "BURN_RATE_WINDOWS",
    "Alert", "AlertManager", "AlertFiringError", "FileSink",
    "WebhookSink", "install_alert_manager", "get_alert_manager",
    "MetricsHistory", "install_history", "get_history",
    "ProfileTrigger", "install_trigger", "get_trigger",
    "golden_path", "record_golden",
]
