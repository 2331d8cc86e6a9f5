"""Query observability plane: span tracing, metrics registry, EXPLAIN
ANALYZE support, and failure diagnostics.

Reference mapping: the plugin wires a standard metric set into every
GpuExec (GpuMetricNames, GpuExec.scala:27-56) and brackets hot paths in
NVTX ranges so the SQL UI and nsight timelines can explain a query; this
headless engine unifies its equivalents here:

* ``obs.trace``    — Dapper-style request-scoped span tracing (Sigelman
  et al., 2010): one ``query_id``/``trace_id`` pair per execution,
  propagated across the TCP shuffle wire, exported as Perfetto/Chrome
  ``trace_event`` JSON alongside the existing xprof hook.
* ``obs.registry`` — one process-wide metrics registry unifying operator
  Metrics, BufferCatalog counters, and shuffle-plane counters, with
  snapshot/delta semantics and JSON + Prometheus-text exposition.
* ``obs.diag``     — bounded diagnostic bundles emitted on query failure
  (annotated plan, metrics snapshot, last span events, fault config,
  catalog tier occupancy, recent query-history tail).
* ``obs.http``     — stdlib-only live metrics endpoint (/metrics in
  Prometheus text, /healthz, /queries) bound to 127.0.0.1, owned by the
  session and off by default (``spark.rapids.obs.http.port``).
* ``obs.history``  — append-only JSONL query history log with atomic
  rotation (``spark.rapids.obs.history.dir``), browsed offline by
  ``python -m tools.history``.
* ``obs.profile``  — cost-attribution plane: per-operator device/wall
  attribution (fused/mesh members included), HBM occupancy timeline,
  collapsed-stack flamegraphs + Perfetto counter tracks
  (``spark.rapids.obs.profile.enabled``).
* ``obs.metering`` — per-tenant / per-fingerprint resource metering
  (device-seconds, HBM-byte-seconds, bytes) with a conservation
  cross-check, served at ``/tenants``.

Import discipline: the hot path must stay obs-free when observability is
disabled, so this package __init__ resolves submodule attributes LAZILY
— ``spark_rapids_tpu.obs.trace`` / ``obs.diag`` are only imported when a
tracer is enabled or a query actually fails
(tests/test_telemetry.py::test_disabled_path_never_imports holds the
disabled path to it).
"""
from __future__ import annotations

__all__ = ["Tracer", "MetricsRegistry", "get_registry",
           "query_metrics_snapshot", "maybe_emit_bundle",
           "ObsHttpServer", "QueryHistoryLog", "history_log",
           "QueryProfiler", "TenantMeter", "get_meter"]

_LAZY = {
    "Tracer": ("spark_rapids_tpu.obs.trace", "Tracer"),
    "QueryProfiler": ("spark_rapids_tpu.obs.profile", "QueryProfiler"),
    "TenantMeter": ("spark_rapids_tpu.obs.metering", "TenantMeter"),
    "get_meter": ("spark_rapids_tpu.obs.metering", "get_meter"),
    "MetricsRegistry": ("spark_rapids_tpu.obs.registry", "MetricsRegistry"),
    "get_registry": ("spark_rapids_tpu.obs.registry", "get_registry"),
    "query_metrics_snapshot": ("spark_rapids_tpu.obs.registry",
                               "query_metrics_snapshot"),
    "maybe_emit_bundle": ("spark_rapids_tpu.obs.diag", "maybe_emit_bundle"),
    "ObsHttpServer": ("spark_rapids_tpu.obs.http", "ObsHttpServer"),
    "QueryHistoryLog": ("spark_rapids_tpu.obs.history", "QueryHistoryLog"),
    "history_log": ("spark_rapids_tpu.obs.history", "history_log"),
}


def __getattr__(name: str):
    target = _LAZY.get(name)
    if target is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib
    return getattr(importlib.import_module(target[0]), target[1])
