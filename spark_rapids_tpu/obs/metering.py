"""Per-tenant resource metering: who burned the device, the HBM, and
the bytes.

The reference ecosystem answers "which team's queries cost us this
cluster" with the Spark history server + per-stage task metrics rolled
up by external billing jobs; this engine meters in-process.  Every
profiled query (``spark.rapids.obs.profile.enabled``) is charged to its
admission tenant and its plan fingerprint at lifecycle end:

* ``device_seconds``     — operator active time (profiler attribution)
* ``hbm_byte_seconds``   — integrated device-buffer occupancy
* ``shuffle_bytes``      — shuffle fetch traffic during the query
* ``spill_bytes``        — host+disk spill written by its catalog
* ``scan_bytes``         — input file bytes decoded
* ``compile_seconds``    — jit tracing/compilation wall charged to it
* ``queries``            — executed runs (cache hits never meter)

Conservation invariant: the per-tenant charge path is INDEPENDENT of
the process-totals path (charges come from each query's own profiler /
catalog / registry delta; totals from the raw instrumentation counters
and the HBM sampler's process integration), so ``conservation()`` is a
real cross-check — tenant sums within 5% of process totals — not a
tautology.  Under concurrent queries the registry-delta byte charges
can overlap (two in-flight queries each observe the other's counter
movement); the invariant holds on serial runs, where the two paths
must agree (tests/test_profile.py asserts it on books of its own).

Import discipline: this module is only imported when the raw conf
string enables profiling
(tests/test_telemetry.py::test_disabled_path_never_imports).
"""
from __future__ import annotations

import threading

from spark_rapids_tpu.obs.registry import get_registry

__all__ = ["TenantMeter", "get_meter", "USAGE_METRICS"]

#: every metric a query charge may carry, in exposition order
USAGE_METRICS = ("device_seconds", "hbm_byte_seconds", "shuffle_bytes",
                 "spill_bytes", "scan_bytes", "compile_seconds", "queries")

#: process totals derived from raw registry counters (incremented at
#: the I/O chokepoints themselves, not by the charge path)
_REGISTRY_TOTALS = {
    "shuffle_bytes": ("shuffle.fetch.bytes",),
    "scan_bytes": ("scan.bytes",),
    "compile_seconds": ("compile_wall_s",),
    "queries": ("queries_executed",),
}

#: fingerprint table bound: a long-lived driver seeing unbounded
#: distinct plans keeps a fixed metering footprint (LRU on charge)
_MAX_FINGERPRINTS = 512


class TenantMeter:
    """Process-wide accumulator of per-tenant / per-fingerprint usage.

    ``charge`` is the query-side path (session lifecycle end);
    ``add_total`` is the instrumentation-side path (profiler record_op,
    HBM sampler tick).  The two never share a call site — that is what
    makes ``conservation()`` worth checking.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._tenants: dict[str, dict[str, float]] = {}
        self._fps: dict[str, dict[str, float]] = {}
        self._totals: dict[str, float] = {}
        # per-worker totals folded in from cluster heartbeats — kept
        # OUT of conservation (each process conserves its own books)
        self._workers: dict[str, dict[str, float]] = {}
        # registry-counter baseline so totals are meter-relative, not
        # process-lifetime-relative (profiling may be enabled late)
        self._baseline = self._registry_read()
        # last-shipped copies for cluster heartbeat deltas
        self._shipped_tenants: dict[str, dict[str, float]] = {}
        self._shipped_totals: dict[str, float] = {}

    # -- write side ----------------------------------------------------
    def charge(self, tenant: str, fingerprint: "str | None",
               usage: dict) -> None:
        """Attribute one query's usage to its tenant (and fingerprint
        when the plan has one).  Only :data:`USAGE_METRICS` keys are
        folded — the vocabulary is closed so a buggy caller can never
        grow per-tenant key cardinality without bound."""
        tenant = tenant or "default"
        usage = {k: v for k, v in (usage or {}).items()
                 if k in USAGE_METRICS}
        with self._lock:
            self._fold(self._tenants.setdefault(tenant, {}), usage)
            if fingerprint:
                self._fold(self._fps.setdefault(fingerprint, {}), usage)
                if len(self._fps) > _MAX_FINGERPRINTS:
                    # dict preserves insertion order: drop the oldest
                    self._fps.pop(next(iter(self._fps)))

    def add_total(self, metric: str, amount: float) -> None:
        """Instrumentation-side process total (never called by the
        charge path — see the conservation contract above)."""
        if not amount:
            return
        with self._lock:
            self._totals[metric] = self._totals.get(metric, 0.0) \
                + float(amount)

    def ingest_worker(self, worker_id: str, totals: dict) -> None:
        """Fold a cluster worker's shipped totals delta under its own
        ledger (heartbeat path, cluster/driver.py)."""
        with self._lock:
            self._fold(self._workers.setdefault(str(worker_id), {}),
                       totals)

    @staticmethod
    def _fold(dst: dict, src: dict) -> None:
        for k, v in (src or {}).items():
            if isinstance(v, (int, float)):
                dst[k] = dst.get(k, 0.0) + float(v)

    # -- read side -----------------------------------------------------
    def _registry_read(self) -> dict[str, float]:
        counters = get_registry().snapshot().get("counters", {})
        return {m: sum(float(counters.get(n, 0.0)) for n in names)
                for m, names in _REGISTRY_TOTALS.items()}

    def totals(self) -> dict[str, float]:
        now = self._registry_read()
        with self._lock:
            out = dict(self._totals)
            for m, v in now.items():
                out[m] = out.get(m, 0.0) + v - self._baseline.get(m, 0.0)
            return out

    def snapshot(self) -> dict:
        with self._lock:
            tenants = {t: dict(u) for t, u in self._tenants.items()}
            fps = {f: dict(u) for f, u in self._fps.items()}
            workers = {w: dict(u) for w, u in self._workers.items()}
        return {"tenants": tenants, "fingerprints": fps,
                "totals": self.totals(), "workers": workers}

    def conservation(self, tolerance: float = 0.05) -> dict:
        """Per-metric cross-check of the two accounting paths: the sum
        of tenant charges vs. the independently-accumulated process
        total.  ``ok`` when they agree within ``tolerance`` (or both
        are ~zero).  A failing metric means attribution double-counted
        or dropped work — exactly the bug class this plane must not
        have."""
        snap = self.snapshot()
        out = {}
        for m in USAGE_METRICS:
            s = sum(u.get(m, 0.0) for u in snap["tenants"].values())
            t = snap["totals"].get(m, 0.0)
            hi = max(abs(s), abs(t))
            ok = hi <= 1e-9 or abs(s - t) <= tolerance * hi
            out[m] = {"tenants_sum": s, "total": t, "ok": ok}
        out["ok"] = all(v["ok"] for v in out.values()
                        if isinstance(v, dict))
        return out

    # -- cluster shipping ---------------------------------------------
    def drain_delta(self) -> "dict | None":
        """Per-tenant charges + accumulated totals moved since the last
        drain — the heartbeat payload a worker ships (registry-derived
        totals ride the existing metrics snapshot, so only the
        instrumentation accumulators ship here)."""
        with self._lock:
            d_tenants: dict = {}
            for t, u in self._tenants.items():
                prev = self._shipped_tenants.setdefault(t, {})
                moved = {k: v - prev.get(k, 0.0) for k, v in u.items()
                         if v != prev.get(k, 0.0)}
                if moved:
                    d_tenants[t] = moved
                self._shipped_tenants[t] = dict(u)
            d_totals = {k: v - self._shipped_totals.get(k, 0.0)
                        for k, v in self._totals.items()
                        if v != self._shipped_totals.get(k, 0.0)}
            self._shipped_totals = dict(self._totals)
        if not d_tenants and not d_totals:
            return None
        return {"tenants": d_tenants, "totals": d_totals}

    def merge_delta(self, delta: dict) -> None:
        """Fold a shipped delta's tenant charges into this process's
        books (driver side of :meth:`drain_delta`)."""
        with self._lock:
            for t, u in (delta.get("tenants") or {}).items():
                self._fold(self._tenants.setdefault(str(t), {}), u)
            self._fold(self._totals, delta.get("totals") or {})


_meter: "TenantMeter | None" = None
_meter_lock = threading.Lock()


def get_meter() -> TenantMeter:
    """Process-wide meter singleton (first call sets the registry
    baseline for the counter-derived totals)."""
    global _meter
    with _meter_lock:
        if _meter is None:
            _meter = TenantMeter()
        return _meter
