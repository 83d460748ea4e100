"""Runtime telemetry: metrics registry, span tracing, Prom/JSONL export.

The measurement substrate for the production-serving north star —
process-local, stdlib-only, and a guaranteed no-op unless enabled:

    import paddle_tpu.observability as telemetry

    telemetry.enable()               # or PDT_TELEMETRY=1 in the env
    ...serve / train...
    snap = telemetry.snapshot()      # JSON-safe programmatic view
    print(telemetry.to_prometheus()) # text exposition for scrapers

Three modules:

* `registry` — typed Counter/Gauge/Histogram instruments (labels,
  fixed bucket boundaries, monotonic-clock timers) behind the global
  `REGISTRY`.
* `trace` — nestable `span()` (each with its self time) / point
  `event()` / unscoped `interval()` -> structured JSONL
  into a bounded ring buffer + optional file sink
  (`PDT_TELEMETRY_TRACE_FILE=`), interoperating with
  `profiler.RecordEvent` so spans land in the XLA timeline too. PLUS
  request-scoped distributed traces: `start_trace(request_id)` opens a
  trace whose carrier any span/event carrying that `request_id` attr
  joins automatically (router -> replica -> engine), `request_tree()`
  rebuilds one request's causal tree, and `export_chrome_trace()`
  renders Perfetto/chrome://tracing JSON (pid=replica, tid=request).
* `export` — Prometheus text exposition + JSON snapshot, with a
  `parse_prometheus()` round-trip verifier and an offline
  `render_prometheus(snapshot)` for saved snapshots.
* `slo` — streaming quantiles (le-bucket interpolation + an exact
  windowed reservoir) and the `SloMonitor` grading declarative
  objectives (TTFT/TPOT percentiles, error rate, availability) into
  pass/warn/breach with burn rates, exported as `pdt_slo_*` gauges.
* `profile` — the performance attribution plane: the fleet step's
  self-time table (`span_summary`, read from the
  `pdt_span_self_seconds{name}` series every span observes),
  compile-cache observability (`compile_timed` behind the engine's
  `_jit_lru`/`_jit_singleton` seam + the retrace-storm detector), the
  `pdt_mem_bytes{pool}` memory ledger, and
  `render_profile_report(snapshot)` for the waterfall / compile-table /
  ledger text report.
* `status` — `render_fleet_status()`: the human-readable fleet report.
* `__main__` — the operator CLI (`python -m paddle_tpu.observability
  snapshot|slo|trace ...`, installed as `paddle-tpu-obs`).

Instrumented out of the box: the continuous-batching engine (TTFT,
time-per-output-token, tokens/sec, queue depth, admissions/rejections,
preemptions, page occupancy, terminal-status counters, invariant-check
duration), `generate()` compile/dispatch, fault-injection fires,
elastic launcher restarts + heartbeat staleness, checkpoint save/load
spans + bytes, and checkpoint durability (save retries, quarantines,
resume fallback depth, verify duration — docs/checkpointing.md).
Metric catalog: docs/serving.md "Observability".
"""
from __future__ import annotations

from .registry import (DEFAULT_BUCKETS, REGISTRY, Counter, Gauge,  # noqa: F401
                       Histogram, Registry, counter, disable, enable,
                       enabled, gauge, histogram, reset, snapshot, value)
from .trace import (clear as clear_events, event, events,  # noqa: F401
                    interval, set_trace_file, span, trace_file,
                    start_trace, end_trace, trace_of,
                    attach as trace_attach,
                    request_tree, export_chrome_trace,
                    load_trace_jsonl)
from .export import (parse_prometheus, render_prometheus,  # noqa: F401
                     to_json, to_prometheus, write_json)
from .slo import (Reservoir, SloMonitor, SloObjective,  # noqa: F401
                  SloStatus, default_serving_objectives,
                  evaluate_snapshot, format_slo_report,
                  objectives_from_spec, quantile_from_buckets)
from .status import render_fleet_status  # noqa: F401
from . import profile  # noqa: F401
from .profile import (memory_ledger,  # noqa: F401
                      render_profile_report, snapshot_report)

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "REGISTRY",
    "DEFAULT_BUCKETS", "counter", "gauge", "histogram",
    "enable", "disable", "enabled", "reset", "snapshot", "value",
    "span", "event", "interval", "events", "clear_events",
    "set_trace_file", "trace_file", "start_trace", "end_trace", "trace_of",
    "trace_attach", "request_tree", "export_chrome_trace",
    "load_trace_jsonl", "to_prometheus", "render_prometheus",
    "to_json", "write_json", "parse_prometheus",
    "Reservoir", "SloMonitor", "SloObjective", "SloStatus",
    "default_serving_objectives", "evaluate_snapshot",
    "format_slo_report", "objectives_from_spec",
    "quantile_from_buckets", "render_fleet_status",
    "profile", "memory_ledger",
    "render_profile_report", "snapshot_report",
]
