"""Always-on operator/device cost attribution + HBM occupancy timeline.

The reference wires GpuMetricNames into every GpuExec and brackets the
hot paths in NVTX ranges so Nsight can say where a query's device time
went (PAPER.md §L5, GpuExec.scala:27-56); this engine's analog rides
the instrumentation that already exists — the per-(operator, partition)
summary the base PlanNode wrapper records at iterator exhaustion — so
profiling adds ONE bounded record per operator-partition, never
per-batch work.

Three surfaces per query:

* **operator cost table** — active (device) seconds, wall, batches,
  rows per operator; fused stages and mesh regions additionally
  attribute their time across member ops via ``fused_ops`` /
  ``region_ops`` metadata, so a FusedStageExec no longer hides which
  member burned the time.
* **flamegraph** — collapsed-stack text (``query;container;member N``)
  loadable by any flamegraph renderer, plus Perfetto counter tracks
  (ph="C") merged into the query's existing trace_event timeline.
* **HBM occupancy timeline** — a ring-buffer sampler over the live
  BufferCatalogs (and the governor's per-query ledger when it is on):
  per-query device bytes and watermark position over time, integrated
  into HBM-byte-seconds for metering, served at ``/profile``.

Import discipline: ExecCtx gates on the RAW conf string, so with
``spark.rapids.obs.profile.enabled`` unset this module (and
``obs.metering``) is never imported
(tests/test_telemetry.py::test_disabled_path_never_imports).
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time
import weakref

from spark_rapids_tpu.conf import ConfEntry, register, _bool
from spark_rapids_tpu.obs.metering import get_meter
from spark_rapids_tpu.obs.registry import get_registry

__all__ = ["PROFILE_ENABLED", "PROFILE_DIR", "QueryProfiler",
           "ProfileStore", "get_store", "live_progress", "profile_view",
           "drain_hbm_for_shipping", "ingest_worker_hbm"]

PROFILE_ENABLED = register(ConfEntry(
    "spark.rapids.obs.profile.enabled", False,
    "Cost-attribution plane: per-operator device/wall attribution "
    "(fused-stage and mesh-region members included), HBM occupancy "
    "timeline, and per-tenant metering (/profile, /tenants). Off by "
    "default: the disabled path never imports obs.profile/obs.metering "
    "and adds no per-batch work.",
    conv=_bool))
PROFILE_DIR = register(ConfEntry(
    "spark.rapids.obs.profile.dir", "",
    "When set, every profiled query exports profile_<query_id>.json "
    "(operator cost table + HBM timeline, schema ci/obs_schema.json) "
    "and flamegraph_<query_id>.txt (collapsed-stack text) into this "
    "directory at ExecCtx close. Empty (default): in-memory only "
    "(still served at /profile and embedded in diag bundles)."))
PROFILE_HBM_INTERVAL_MS = register(ConfEntry(
    "spark.rapids.obs.profile.hbm.intervalMs", 50,
    "HBM occupancy sampling period for the ring-buffer timeline; one "
    "process-wide daemon thread samples every live profiled query's "
    "catalog (and the governor ledger when it is on).",
    conv=int))
PROFILE_HBM_MAX_SAMPLES = register(ConfEntry(
    "spark.rapids.obs.profile.hbm.maxSamples", 2048,
    "Ring-buffer bound on retained HBM occupancy samples (per query "
    "and process-wide): older samples rotate out; the byte-seconds "
    "integral keeps accumulating regardless.",
    conv=int))
PROFILE_MAX_OPS = register(ConfEntry(
    "spark.rapids.obs.profile.maxOps", 256,
    "Bound on distinct operator rows per query cost table; overflow "
    "folds into an \"(other)\" row so a pathological plan cannot grow "
    "the profiler without limit.",
    conv=int))


# ---------------------------------------------------------------------------
# HBM occupancy sampler (process-wide)
# ---------------------------------------------------------------------------

class _HbmSampler:
    """One daemon thread sampling every live :class:`QueryProfiler`'s
    catalog occupancy.  Starts on the first profiler registration and
    exits when the last one unregisters — a process that never profiles
    never spawns it.  Each tick also integrates the PROCESS total into
    the meter's independent hbm-byte-seconds ledger (the conservation
    counterpart of the per-query integrals)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._profilers: dict[int, "weakref.ref"] = {}
        self._thread: "threading.Thread | None" = None
        self._interval = 0.05
        self._samples: collections.deque = collections.deque(maxlen=2048)
        self._remote: dict[str, collections.deque] = {}
        self._seq = 0
        self._ship_seq = 0
        self._last_t: "float | None" = None
        self.total_byte_seconds = 0.0

    def register(self, prof: "QueryProfiler") -> None:
        with self._lock:
            first = not self._profilers
            self._profilers[id(prof)] = weakref.ref(prof)
            self._interval = prof.hbm_interval_s if first \
                else min(self._interval, prof.hbm_interval_s)
            if first and not self._samples and \
                    self._samples.maxlen != prof.hbm_max_samples:
                self._samples = collections.deque(
                    maxlen=prof.hbm_max_samples)
            if self._thread is None:
                self._last_t = None
                self._thread = threading.Thread(
                    target=self._loop, name="obs-hbm-sampler", daemon=True)
                self._thread.start()

    def unregister(self, prof: "QueryProfiler") -> None:
        with self._lock:
            self._profilers.pop(id(prof), None)

    def _loop(self) -> None:
        while True:
            time.sleep(self._interval)
            with self._lock:
                refs = list(self._profilers.values())
                if not refs:
                    self._thread = None
                    return
            self._tick(refs)

    def _tick(self, refs) -> None:
        now = time.time()
        dt = 0.0 if self._last_t is None else max(0.0, now - self._last_t)
        self._last_t = now
        per_query: dict[str, int] = {}
        total = 0
        for r in refs:
            p = r()
            if p is None:
                continue
            b = p._sample_hbm(now, dt)
            per_query[p.query_id] = b
            total += b
        if dt:
            get_meter().add_total("hbm_byte_seconds", total * dt)
            self.total_byte_seconds += total * dt
        sample = {"unix_s": round(now, 4), "device_bytes": total,
                  "per_query": per_query}
        # governor view only when the governor is actually running —
        # never import-as-side-effect from the sampler thread
        import sys
        gov_mod = sys.modules.get("spark_rapids_tpu.memory.governor")
        if gov_mod is not None:
            try:
                gov = gov_mod.get_governor()
                sample["governor"] = gov.occupancy_sample()
            # enginelint: disable=RL001 (sampler must outlive any governor hiccup; a failed tick just drops the governor lane)
            except Exception:
                pass
        with self._lock:
            self._seq += 1
            sample["seq"] = self._seq
            self._samples.append(sample)

    # -- read side -----------------------------------------------------
    def snapshot(self, last: "int | None" = None) -> list[dict]:
        with self._lock:
            out = list(self._samples)
        return out if last is None else out[-last:]

    def drain_for_shipping(self) -> list[dict]:
        """Samples not yet shipped (worker heartbeat path); each is
        shipped exactly once, like drained spans."""
        with self._lock:
            out = [s for s in self._samples if s["seq"] > self._ship_seq]
            if out:
                self._ship_seq = out[-1]["seq"]
        return out

    def ingest_remote(self, worker_id: str, samples: list[dict]) -> None:
        with self._lock:
            dq = self._remote.setdefault(
                str(worker_id), collections.deque(maxlen=512))
            dq.extend(samples)

    def remote_snapshot(self, last: int = 32) -> dict:
        with self._lock:
            return {w: list(dq)[-last:] for w, dq in self._remote.items()}


_sampler = _HbmSampler()


def drain_hbm_for_shipping() -> list[dict]:
    return _sampler.drain_for_shipping()


def ingest_worker_hbm(worker_id: str, samples: list[dict]) -> None:
    _sampler.ingest_remote(worker_id, samples)


# ---------------------------------------------------------------------------
# Per-query profiler
# ---------------------------------------------------------------------------

class QueryProfiler:
    """Operator cost table + HBM ring buffer for ONE query execution.

    ``record_op`` is called once per (operator, partition) at iterator
    exhaustion by the base PlanNode wrapper — the amortized cost is a
    dict update, not per-batch work.  Containers exposing ``fused_ops``
    / ``region_ops`` split their time equally across members as
    attributed child rows (key ``Container/Member``), keeping the
    container row as the authoritative total."""

    def __init__(self, query_id: str, conf, ctx=None):
        self.query_id = query_id
        self.profile_dir = conf.get(PROFILE_DIR)
        self.max_ops = max(8, conf.get(PROFILE_MAX_OPS))
        self.hbm_interval_s = max(0.001,
                                  conf.get(PROFILE_HBM_INTERVAL_MS) / 1e3)
        self.hbm_max_samples = max(16, conf.get(PROFILE_HBM_MAX_SAMPLES))
        self._ctx = (lambda: None) if ctx is None else weakref.ref(ctx)
        self._lock = threading.Lock()
        self._ops: dict[str, dict] = {}
        self._hbm: collections.deque = collections.deque(
            maxlen=self.hbm_max_samples)
        self._hbm_byte_s = 0.0
        self._hbm_peak = 0
        self._spill_bytes = 0.0
        self._meter = get_meter()
        self._finalized = False
        self._t0 = time.time()
        _sampler.register(self)

    # -- write side (exec hot path) ------------------------------------
    def record_op(self, node, label: str, active_s: float, wall_s: float,
                  batches: int, rows: int, partition: int) -> None:
        """One (operator, partition) exhausted: fold its totals in and
        attribute container time to member ops."""
        members = getattr(node, "fused_ops", None)
        if members is None:
            members = getattr(node, "region_ops", None)
        mem: list[str] = []
        if members:
            try:
                mem = [type(m).__name__ for m in members]
            # enginelint: disable=RL001 (profiling is best-effort attribution; a node with odd metadata still gets its container row)
            except Exception:
                mem = []
        with self._lock:
            self._acc(label, None, active_s, wall_s, batches, rows)
            if mem:
                share, wshare = active_s / len(mem), wall_s / len(mem)
                for ml in mem:
                    self._acc(f"{label}/{ml}", label, share, wshare, 0, 0)
        # the INDEPENDENT process-totals path (conservation contract:
        # tenant charges are derived from this profiler's table instead)
        self._meter.add_total("device_seconds", active_s)
        get_registry().inc("profile.device_seconds", active_s)

    def _acc(self, key: str, parent: "str | None", dev: float,
             wall: float, batches: int, rows: int) -> None:
        e = self._ops.get(key)
        if e is None:
            if len(self._ops) >= self.max_ops:
                key, parent = "(other)", None
                e = self._ops.get(key)
            if e is None:
                e = self._ops[key] = {
                    "op": key.rsplit("/", 1)[-1], "parent": parent,
                    "device_s": 0.0, "wall_s": 0.0,
                    "batches": 0, "rows": 0, "calls": 0}
        e["device_s"] += dev
        e["wall_s"] += wall
        e["batches"] += int(batches)
        e["rows"] += int(rows)
        e["calls"] += 1

    def _sample_hbm(self, now: float, dt: float) -> int:
        """One sampler tick: this query's current device bytes (its
        catalog's ledger; 0 before the catalog exists).  Never CREATES
        the catalog — profiling a host-only query must not allocate
        device machinery."""
        ctx = self._ctx()
        cat = None if ctx is None else ctx.cache.get("catalog")
        used = int(getattr(cat, "device_used", 0) or 0)
        with self._lock:
            self._hbm.append((round(now, 4), used))
            self._hbm_byte_s += used * dt
            if used > self._hbm_peak:
                self._hbm_peak = used
        return used

    # -- read side -----------------------------------------------------
    def operators(self) -> dict:
        with self._lock:
            return {k: dict(e) for k, e in self._ops.items()}

    def device_seconds(self) -> float:
        """Top-level active seconds (member rows are attribution views
        of their container, never counted twice)."""
        with self._lock:
            return sum(e["device_s"] for e in self._ops.values()
                       if e["parent"] is None)

    def hbm_byte_seconds(self) -> float:
        with self._lock:
            return self._hbm_byte_s

    def usage(self) -> dict:
        """This query's charge-side usage (the byte metrics derived
        from registry deltas are added by the session, which owns the
        before-snapshot)."""
        with self._lock:
            dev = sum(e["device_s"] for e in self._ops.values()
                      if e["parent"] is None)
            return {"device_seconds": dev,
                    "hbm_byte_seconds": self._hbm_byte_s,
                    "spill_bytes": self._spill_bytes,
                    "queries": 1}

    def flamegraph(self) -> str:
        """Collapsed-stack text (one ``frame;frame value`` line per
        stack, value = device µs).  Container frames with attributed
        members contribute through their member lines, so totals do not
        double count."""
        ops = self.operators()
        parents = {e["parent"] for e in ops.values() if e["parent"]}
        lines = []
        for key, e in sorted(ops.items()):
            us = int(round(e["device_s"] * 1e6))
            if e["parent"]:
                lines.append(f"{self.query_id};{e['parent']};{e['op']} "
                             f"{us}")
            elif key not in parents:
                lines.append(f"{self.query_id};{e['op']} {us}")
        return "\n".join(lines) + ("\n" if lines else "")

    def hbm_timeline(self, last: "int | None" = None) -> list:
        with self._lock:
            out = [[t, b] for t, b in self._hbm]
        return out if last is None else out[-last:]

    def artifact(self) -> dict:
        """The schema-checked profile document (ci/obs_schema.json
        kind="profile"; scripts/validate_obs.py accepts it)."""
        ops = {}
        for k, e in self.operators().items():
            ops[k] = {"op": e["op"], "parent": e["parent"],
                      "device_s": round(e["device_s"], 6),
                      "wall_s": round(e["wall_s"], 6),
                      "batches": e["batches"], "rows": e["rows"],
                      "calls": e["calls"]}
        with self._lock:
            hbm = {"samples": len(self._hbm),
                   "byte_seconds": round(self._hbm_byte_s, 3),
                   "peak_bytes": self._hbm_peak,
                   "timeline": [[t, b] for t, b in list(self._hbm)[-256:]]}
        return {"kind": "profile", "version": 1,
                "query_id": self.query_id,
                "unix_s": round(self._t0, 3),
                "operators": ops, "hbm": hbm,
                "flamegraph": self.flamegraph()}

    def history_blob(self) -> dict:
        """Compact per-query table for the history entry (no timeline —
        the jsonl must stay one lean line per query)."""
        ops = {k: {"op": e["op"], "parent": e["parent"],
                   "device_s": round(e["device_s"], 6),
                   "wall_s": round(e["wall_s"], 6),
                   "batches": e["batches"], "rows": e["rows"]}
               for k, e in self.operators().items()}
        return {"operators": ops,
                "device_seconds": round(self.device_seconds(), 6),
                "hbm_byte_seconds": round(self.hbm_byte_seconds(), 3)}

    # -- lifecycle -----------------------------------------------------
    def finalize(self, ctx) -> None:
        """End-of-execution hook (ExecCtx.close, BEFORE the catalog is
        popped and BEFORE trace export): capture the catalog's spill
        totals, merge counter tracks into the query trace, and export
        the artifact files.  Idempotent."""
        with self._lock:
            if self._finalized:
                return
            self._finalized = True
        _sampler.unregister(self)
        cat = ctx.cache.get("catalog")
        if cat is not None:
            m = getattr(cat, "metrics", None) or {}
            self._spill_bytes = float(
                m.get("bytes_spilled_to_host", 0)
                + m.get("bytes_spilled_to_disk", 0))
            if self._spill_bytes:
                self._meter.add_total("spill_bytes", self._spill_bytes)
        tracer = ctx.cache.get("tracer")
        if tracer is not None:
            for t_wall, b in self.hbm_timeline():
                tracer.counter("hbm.device_bytes", wall_t=t_wall, bytes=b)
            top = {e["op"]: round(e["device_s"], 6)
                   for e in self.operators().values()
                   if e["parent"] is None}
            if top:
                tracer.counter("operator.device_seconds", **top)
        d = self.profile_dir
        if d:
            # enginelint: disable=RL001 (artifact export is best-effort teardown; the query already finished)
            try:
                os.makedirs(d, exist_ok=True)
                path = os.path.join(d, f"profile_{self.query_id}.json")
                tmp = f"{path}.tmp.{os.getpid()}"
                with open(tmp, "w") as f:
                    json.dump(self.artifact(), f)
                os.replace(tmp, path)
                with open(os.path.join(
                        d, f"flamegraph_{self.query_id}.txt"), "w") as f:
                    f.write(self.flamegraph())
            # enginelint: disable=RL001 (artifact export is best-effort; a full disk must not fail the query)
            except Exception:
                pass


# ---------------------------------------------------------------------------
# Per-fingerprint aggregation (the /profile "where does this PLAN spend")
# ---------------------------------------------------------------------------

class ProfileStore:
    """LRU-bounded per-fingerprint merge of operator cost tables, so
    /profile answers "where does q18 spend" across runs without
    re-reading the history file."""

    def __init__(self, max_fingerprints: int = 128, max_ops: int = 64):
        self.max_fingerprints = max_fingerprints
        self.max_ops = max_ops
        self._lock = threading.Lock()
        self._fps: "collections.OrderedDict" = collections.OrderedDict()

    def note(self, fingerprint: str, operators: dict,
             wall_s: "float | None" = None) -> None:
        if not fingerprint or not operators:
            return
        with self._lock:
            agg = self._fps.get(fingerprint)
            if agg is None:
                agg = self._fps[fingerprint] = {"runs": 0, "wall_s": 0.0,
                                                "operators": {}}
            agg["runs"] += 1
            if isinstance(wall_s, (int, float)):
                agg["wall_s"] += float(wall_s)
            for k, e in operators.items():
                o = agg["operators"].get(k)
                if o is None:
                    if len(agg["operators"]) >= self.max_ops:
                        continue
                    o = agg["operators"][k] = {
                        "op": e.get("op", k), "parent": e.get("parent"),
                        "device_s": 0.0, "wall_s": 0.0, "rows": 0}
                o["device_s"] += float(e.get("device_s", 0.0))
                o["wall_s"] += float(e.get("wall_s", 0.0))
                o["rows"] += int(e.get("rows", 0))
            self._fps.move_to_end(fingerprint)
            while len(self._fps) > self.max_fingerprints:
                self._fps.popitem(last=False)

    def snapshot(self) -> dict:
        with self._lock:
            return {fp: {"runs": a["runs"],
                         "wall_s": round(a["wall_s"], 4),
                         "operators": {
                             k: {kk: (round(vv, 6)
                                      if isinstance(vv, float) else vv)
                                 for kk, vv in o.items()}
                             for k, o in a["operators"].items()}}
                    for fp, a in self._fps.items()}


_store: "ProfileStore | None" = None
_store_lock = threading.Lock()


def get_store() -> ProfileStore:
    global _store
    with _store_lock:
        if _store is None:
            _store = ProfileStore()
        return _store


# ---------------------------------------------------------------------------
# Live progress / HTTP view helpers
# ---------------------------------------------------------------------------

def live_progress(lc, index) -> dict:
    """Progress fields for one in-flight query: rows processed so far,
    percent complete and ETA against the fingerprint's historical
    medians (HistoryIndex).  Partial knowledge degrades gracefully —
    rows without history, history without rows, or neither."""
    out: dict = {}
    rows = None
    ctx = getattr(lc, "ctx", None)
    if ctx is not None:
        try:
            rows = int(sum(m.values.get("numOutputRows", 0.0)
                           for m in list(ctx.metrics.values())))
        # enginelint: disable=RL001 (a snapshot racing operator registration just skips this poll)
        except Exception:
            rows = None
    if rows is not None:
        out["rows_processed"] = rows
    fp = getattr(lc, "plan_fingerprint", None)
    stats = index.lookup(fp) if (index is not None and fp) else None
    if not stats:
        return out
    med_rows = stats.get("median_rows")
    med_wall = stats.get("median_wall_s")
    started = getattr(lc, "_started_at", None)
    elapsed = None if started is None else time.monotonic() - started
    pct = None
    if med_rows and rows:
        pct = min(0.99, rows / med_rows)
    elif med_wall and elapsed is not None:
        pct = min(0.99, elapsed / med_wall)
    if pct is not None:
        out["percent_complete"] = round(100.0 * pct, 1)
        if elapsed is not None and pct > 0:
            out["eta_s"] = round(max(0.0, elapsed * (1.0 - pct) / pct), 3)
    if med_wall is not None:
        out["median_wall_s"] = round(med_wall, 4)
    return out


def profile_view(session) -> dict:
    """The /profile HTTP body: process HBM timeline (+ per-worker lanes
    shipped over heartbeats), per-fingerprint cost tables, and a brief
    per-live-query line."""
    out: dict = {
        "enabled": True,
        "hbm": {"byte_seconds": round(_sampler.total_byte_seconds, 3),
                "samples": _sampler.snapshot(last=120),
                "workers": _sampler.remote_snapshot()},
        "fingerprints": get_store().snapshot(),
    }
    live: dict = {}
    with session._lc_cond:
        lcs = dict(session._live)
    for qid, lc in lcs.items():
        ctx = getattr(lc, "ctx", None)
        prof = None if ctx is None else ctx.cache.get("profiler")
        if prof is None:
            continue
        tl = prof.hbm_timeline(last=1)
        live[qid] = {"device_seconds": round(prof.device_seconds(), 6),
                     "hbm_bytes": tl[-1][1] if tl else 0,
                     "hbm_byte_seconds": round(prof.hbm_byte_seconds(),
                                               3)}
    out["live"] = live
    return out
