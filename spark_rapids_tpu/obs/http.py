"""Live telemetry endpoint: a stdlib-only HTTP server owned by the
session.

The reference ecosystem operates through the Spark UI + a Prometheus
sink (PAPER.md §L3 GpuMetric plumbing); this headless engine exposes the
same operational surface as three read-only routes:

* ``/metrics`` — the process-wide :class:`MetricsRegistry` in Prometheus
  text exposition (counters, gauges, and the latency histograms with
  cumulative ``_bucket``/``_sum``/``_count`` series).
* ``/healthz`` — liveness + readiness: admission state (active/queued /
  shutting-down), memory-governor pressure, cluster worker liveness.
  Returns 503 once the session begins shutdown — load balancers drain
  on readiness, not liveness.
* ``/queries`` — the in-flight query table (query_id -> lifecycle
  state/tenant/tenant wall so far), the live analog of the history log;
  with profiling on each row also carries rows-processed,
  percent-complete, and ETA against the plan's history medians.
  ``finished`` holds the last 64 queries' records (query_id, state,
  start/end, and the counter movement over the query: spans, transfers,
  waits, the per-program table — "what did my last queries cost and
  where").
* ``/control`` — the self-driving control plane's learned state
  (current admission cap, adapted governor watermarks, per-tenant SLO
  status, last 32 decisions), or ``{"enabled": false}`` when the
  control loop is off.
* ``/profile`` — the cost-attribution plane (obs/profile.py): HBM
  occupancy timeline and per-fingerprint operator cost tables, or
  ``{"enabled": false}`` with ``spark.rapids.obs.profile.enabled``
  unset (the profiler modules are never imported then).
* ``/tenants`` — per-tenant resource metering (device-seconds,
  HBM-byte-seconds, shuffle/spill/scan bytes, compile-seconds) with
  the tenant-sums-vs-process-totals conservation cross-check.

Security: binds 127.0.0.1 ONLY.  The registry carries operational
detail (tenant names, peer addresses, plan fingerprints) that must not
face a network; operators who need remote scrape should sidecar a real
exporter.  Off by default (``spark.rapids.obs.http.port`` = 0) and the
module is never imported on the disabled path (session gates on the raw
conf string; tests/test_telemetry.py::test_disabled_path_never_imports).
"""
from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from spark_rapids_tpu.conf import ConfEntry, register
from spark_rapids_tpu.obs.registry import get_registry

__all__ = ["OBS_HTTP_PORT", "ObsHttpServer"]

OBS_HTTP_PORT = register(ConfEntry(
    "spark.rapids.obs.http.port", 0,
    "TCP port for the live telemetry endpoint (/metrics Prometheus "
    "text, /healthz, /queries), bound to 127.0.0.1 only. 0 (default): "
    "no server, and the HTTP module is never imported.",
    conv=int))

_BIND_HOST = "127.0.0.1"


class _Handler(BaseHTTPRequestHandler):
    # the protocol default (HTTP/1.0) closes per request; 1.1 lets a
    # scraper keep its connection
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):  # noqa: ARG002 - silence stderr
        pass

    def _reply(self, code: int, body: bytes, ctype: str) -> None:
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _json(self, code: int, obj) -> None:
        self._reply(code, json.dumps(obj, indent=1, sort_keys=True,
                                     default=str).encode(),
                    "application/json")

    def do_GET(self) -> None:  # noqa: N802 - http.server API
        srv: "ObsHttpServer" = self.server.obs  # type: ignore[attr-defined]
        path = self.path.split("?", 1)[0]
        try:
            if path == "/metrics":
                self._reply(200, get_registry().to_prometheus().encode(),
                            "text/plain; version=0.0.4; charset=utf-8")
            elif path == "/healthz":
                body = srv.health()
                self._json(200 if body["status"] == "ok" else 503, body)
            elif path == "/queries":
                self._json(200, srv.queries())
            elif path == "/control":
                self._json(200, srv.control())
            elif path == "/profile":
                self._json(200, srv.profile())
            elif path == "/tenants":
                self._json(200, srv.tenants())
            else:
                self._reply(404,
                            b"not found: /metrics /healthz /queries "
                            b"/control /profile /tenants\n",
                            "text/plain")
        except BrokenPipeError:  # scraper hung up mid-reply
            pass
        # enginelint: disable=RL001 (endpoint must never kill the engine)
        except Exception as e:
            try:
                self._reply(500, f"{type(e).__name__}: {e}\n".encode(),
                            "text/plain")
            except OSError:
                pass


class ObsHttpServer:
    """One telemetry server per :class:`TpuSession`, 127.0.0.1-bound.

    ``port=0`` binds an ephemeral port (tests); the session itself
    treats conf port 0 as "off" and never constructs one."""

    def __init__(self, session, port: int):
        self._session = session
        self._server = ThreadingHTTPServer((_BIND_HOST, port), _Handler)
        self._server.daemon_threads = True
        self._server.obs = self  # type: ignore[attr-defined]
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="obs-http",
            daemon=True)
        self._thread.start()

    @property
    def port(self) -> int:
        return self._server.server_address[1]

    @property
    def address(self) -> str:
        return f"http://{_BIND_HOST}:{self.port}"

    # -- route bodies (also the programmatic surface for tests) --------
    def health(self) -> dict:
        s = self._session
        adm = s._admission_controller()
        out: dict = {
            "status": "draining" if adm.shutting_down else "ok",
            "unix_s": time.time(),
            "admission": {"active": adm.active, "queued": adm.queued,
                          "shutting_down": adm.shutting_down},
        }
        try:
            from spark_rapids_tpu.memory.governor import (GOVERNOR_ENABLED,
                                                          get_governor)
            if GOVERNOR_ENABLED.get(s.conf.settings):
                gov = get_governor()
                out["governor"] = {
                    "reserved_bytes": gov.reserved_bytes(),
                    "pressure": gov.admission_pressure(),
                }
        # enginelint: disable=RL001 (health must degrade, not fail — the error string is the report)
        except Exception as e:
            out["governor"] = {"error": f"{type(e).__name__}: {e}"}
        cluster = getattr(s, "_cluster_handle", None)
        if cluster is not None:
            workers = []
            now = time.monotonic()
            for h in cluster.workers():
                workers.append({
                    "worker_id": h.worker_id, "pid": h.pid,
                    "alive": h.alive, "lost_reason": h.lost_reason,
                    "state": getattr(h, "state", None)
                    or ("alive" if h.alive else "lost"),
                    "heartbeat_age_s": (
                        None if not h.last_heartbeat
                        else round(now - h.last_heartbeat, 3)),
                })
            out["cluster"] = {"workers": workers}
            out["cluster"]["epoch"] = getattr(cluster, "epoch", 1)
            recovery = getattr(cluster, "recovery_info", None)
            if recovery is not None:
                # this driver was rebuilt from its write-ahead journal
                # (cluster/journal.py): surface what the recovery
                # re-attached, replaced, and salvaged
                out["cluster"]["recovery"] = recovery
            # only UNPLANNED loss degrades readiness: a draining or
            # retired worker is a planned scale-down, a quarantined one
            # still serves its map outputs
            if any(w["state"] == "lost" for w in workers) \
                    and out["status"] == "ok":
                out["status"] = "degraded"
        control = getattr(s, "_control", None)
        if control is not None:
            shed = dict(control.slo.shed)
            if shed:
                # a shed tenant is a PLANNED partial outage: the
                # engine is protecting everyone else's SLO, so
                # readiness degrades with the tenant NAMED rather
                # than flipping hard-down
                out["shed_tenants"] = sorted(shed)
                if out["status"] == "ok":
                    out["status"] = "degraded"
        return out

    def control(self) -> dict:
        """The /control body: learned knob values, per-tenant SLO
        table, and the last 32 decisions — or a stub when the control
        plane is off (the endpoint must answer either way so
        dashboards can probe for it)."""
        control = getattr(self._session, "_control", None)
        if control is None:
            return {"enabled": False}
        out = control.status()
        out["enabled"] = True
        return out

    # -- cost-attribution plane (obs/profile.py, raw-conf gated) -------
    def _profile_on(self) -> bool:
        raw = self._session.conf.settings.get(
            "spark.rapids.obs.profile.enabled")
        return raw is not None and str(raw).lower() in ("true", "1",
                                                        "yes")

    def _progress_index(self):
        """The HistoryIndex live progress reads its medians from: the
        control loop's (already fed in-process) when the controller is
        on, else a session-owned one refreshed from the history file.
        None when there is no history to compare against."""
        s = self._session
        control = getattr(s, "_control", None)
        idx = getattr(control, "_history_index", None) \
            if control is not None else None
        if idx is not None:
            return idx
        hist_dir = s.conf.settings.get("spark.rapids.obs.history.dir")
        if not hist_dir:
            return None
        from spark_rapids_tpu.obs.history import HISTORY_FILE, \
            HistoryIndex
        import os
        idx = getattr(s, "_progress_hist_index", None)
        if idx is None:
            idx = s._progress_hist_index = HistoryIndex()
        idx.refresh_from(os.path.join(hist_dir, HISTORY_FILE))
        return idx

    def profile(self) -> dict:
        """The /profile body: HBM occupancy timeline, per-fingerprint
        operator cost tables, live per-query device-seconds — or
        ``{"enabled": false}`` when profiling is off (the endpoint
        answers either way; the profile module is only imported when
        the conf is on)."""
        if not self._profile_on():
            return {"enabled": False}
        from spark_rapids_tpu.obs.profile import profile_view
        return profile_view(self._session)

    def tenants(self) -> dict:
        """The /tenants body: per-tenant and per-fingerprint usage
        plus the conservation cross-check — or ``{"enabled": false}``
        when profiling is off."""
        if not self._profile_on():
            return {"enabled": False}
        from spark_rapids_tpu.obs.metering import get_meter
        meter = get_meter()
        out = meter.snapshot()
        out["conservation"] = meter.conservation()
        out["enabled"] = True
        return out

    def queries(self) -> dict:
        s = self._session
        with s._lc_cond:
            live = dict(s._live)
        now = time.monotonic()
        prof_on = self._profile_on()
        idx = self._progress_index() if prof_on else None
        out = {}
        for qid, lc in live.items():
            started = lc._started_at
            row = {
                "state": lc.state,
                "tenant": lc.tenant,
                "wall_s": (None if started is None
                           else round(now - started, 3)),
            }
            if prof_on:
                from spark_rapids_tpu.obs.profile import live_progress
                row.update(live_progress(lc, idx))
            out[qid] = row
        # what the last queries cost and where: the per-query records
        # (exec/lifecycle.py QueryLifecycle.seal_record), newest last
        return {"active": out, "count": len(out),
                "finished": get_registry().recent_queries(64)}

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        self._thread.join(timeout=5.0)
