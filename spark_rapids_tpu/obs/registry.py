"""Process-wide metrics registry: one place where operator ``Metrics``,
``BufferCatalog`` counters, and shuffle-plane counters meet.

The reference plugin threads a standard metric set (GpuMetricNames)
through every GpuExec and lets Spark's accumulator machinery aggregate
and expose it; this engine has no driver/UI, so the registry plays that
role: monotonically increasing **counters** (``inc``), point-in-time
**gauges** (``set_gauge``), and pull-style **sources** (callables
returning flat dicts — the existing per-object metrics dicts on
catalogs/transports register themselves here without copying code).

Snapshot/delta semantics let the bench runner report per-query counter
movement, and ``to_prometheus`` renders the standard text exposition so
a scrape endpoint is one ``open().write()`` away.

Dependency discipline: this module imports nothing from the engine (only
stdlib), so hot modules (shuffle/retry.py, faults.py) may import it at
module level without creating cycles or dragging jax into light paths
(``span`` imports ``jax.profiler`` when it is first called, not before).

Spans (``span``) are the engine's one span primitive: a
``jax.profiler.TraceAnnotation`` — on the clock the device planes of a
profiler trace share, free when no profiler session is open — that on
exit moves ``span.<name>.count`` and ``span.<name>.seconds`` (host
seconds).  Names: ``query`` > ``query.plan`` / ``query.execute`` /
``query.fetch`` (session.py), ``program.compile@<program>``
(exec/compile_cache.py), and ``<phase>@<Operator>Exec`` for work done
for an operator outside its per-pull annotation — ``decode@`` /
``stage@ParquetScanExec`` on the scan's worker threads and
``starved@ParquetScanExec`` around the staging thread's ``next()`` on
its input (io/scan.py ``_device_batches``: blocked on the reader pool,
or decoding in-thread with ``decode@`` inside it),
``fetch@HashAggregateExec`` … around every blocking device fetch
(exec/core.py ``fetch_to_host``).  The counters at the same seams:
``program.<name>.launches`` / ``.arg_bytes`` / ``.result_bytes`` /
``.dispatch_s`` per SharedJit program (the last: host seconds inside a
warm launch, exec/compile_cache.py ``SharedJit.__call__``),
``dispatch_wait_s`` (blocked on the DeviceSemaphore), ``h2d_calls`` /
``h2d_bytes`` (one per ``jax.device_put``) / ``h2d_put_s`` (host seconds
inside the puts of a ``_PackBuilder.build``, columnar/batch.py) and
``unpack.leaves.static`` / ``unpack.leaves.gather`` (that build's
leaves decoded with no gather, and the dictionary gathers left),
``wire.double.scaled`` / ``wire.double.raw`` / ``wire.double.bytes``
(that build's float64 columns shipped as scaled integers the device
rebuilds exactly, those shipped as 8-byte doubles, and the
host-to-device bytes of both: columnar/wirecodec.py),
``d2h_calls`` / ``d2h_bytes`` / ``sync_wait_s`` (host blocked inside a
fetch), ``scan_backpressure_s`` (scan worker blocked on its full
queue), and from the other end of that queue ``scan.wait_s`` (the
pulling thread blocked in ``q.get()``), ``scan.first_batch_s`` (the
first get of a pipeline) and ``scan.pipelines`` (io/scan.py).  One
record per finished query — the counter movement over its interval —
is kept in a ring of ``RECENT_QUERIES`` entries (``recent_queries``);
exec/lifecycle.py fills it.

Well-known counter families (beyond per-object sources):
``shuffle.fetch.*`` (retry ladder), ``faults.injected[.point]``
(injection sites), and the query lifecycle plane's
``queries_admitted`` / ``queries_rejected`` / ``queries_cancelled`` /
``queries_deadline_exceeded`` (exec/lifecycle.py — incremented exactly
once per query at the admission decision or the first terminal
transition, so a delta over a run counts QUERIES, not checkpoints); and
the compile plane's ``compile_count`` / ``compile_wall_s`` (one move per
NEW jit input signature of a SharedJit program — a zero delta across a
repeated query proves pure cache reuse; they are blind to the compiles
of eager ``jnp`` operations outside any program, a 20 s ``argsort``
among them, which only a ``jax.monitoring`` duration listener on
``/jax/core/compile/backend_compile_duration`` sees:
benchmark/harness/compiles.py, chip_smoke.py) plus ``fusion_cache_hits`` / ``fusion_cache_misses``
(process-wide program-cache lookups, exec/compile_cache.py); and the
adaptive-execution plane's ``aqe_broadcast_switches`` (shuffle-join ->
broadcast-join rewrites, plan/adaptive.py) /
``aqe_partitions_coalesced`` / ``aqe_skew_splits`` (reader-group
regrouping from map-output sizes, exec/exchange.py) /
``aqe_dynamic_filters`` (build-side IN-set/min-max filters pushed into
probe scans) — each incremented at the decision site, so a per-query
delta shows exactly what the re-optimizer did; and the cross-query
memory governor's ``governor_*`` family (memory/governor.py):
``governor_reclaims`` / ``governor_spill_bytes_own`` /
``governor_spills_peer`` / ``governor_spill_bytes_peer`` (need-sized
arbitration, own-then-younger-peer order), ``governor_grant_waits`` /
``governor_grants`` / ``governor_grant_timeouts`` (wound-wait losers
parked for memory), ``governor_background_spills`` /
``governor_spill_bytes_background`` (watermark thread),
``governor_pressure_sheds`` (admissions rejected under sustained
occupancy), ``governor_victim_errors`` (peer spills skipped because
the victim failed), and ``governor_storm_denials`` (injected
``memory.governor.oom_storm`` reclaim denials) — plus the ``governor`` pull source's aggregate and
per-query ``q.<query_id>.{device,pinned,peak}_bytes`` gauges; and the
serving tier's two families: the result-cache plane's
``result_cache_hits`` / ``result_cache_misses`` (whole-query serves vs
computes — a hit moves NO ``queries_executed`` and NO
``compile_count``), ``result_cache_fragment_hits`` /
``result_cache_fragment_misses`` (cross-query shared-scan
materializations), ``result_cache_corrupt`` (CRC-failed hits dropped
and recomputed), ``result_cache_evictions``,
``result_cache_coalesced`` (waiters single-flighted onto an in-flight
identical query), ``governor_cache_evict_bytes`` (cache bytes the
governor reclaimed under pressure) plus the ``result_cache`` pull
source (entries/bytes gauges, exec/result_cache.py); and the
multi-tenant admission plane's ``queries_executed`` (incremented at
executor entry — the zero-delta proof that a cache hit never touched
the executor), per-tenant ``admission.tenant.<t>.admitted`` /
``admission.tenant.<t>.rejected``, and ``admission_pressure_spared``
(pressure sheds skipped because the arriving tenant was under its
weighted share — exec/lifecycle.py).

Beyond counters and gauges the registry carries log-bucketed
**histograms** (``observe``) for the hot latency distributions the
serving tier's SLOs are defined by: ``query.wall_seconds`` (plus
per-tenant ``query.tenant.<t>.wall_seconds``), ``admission.queue_wait_seconds``,
``shuffle.fetch.round_trip_seconds``, ``compile.wall_seconds``,
``spill.io_seconds``, and ``cluster.rpc.round_trip_seconds`` — each
observed at its existing chokepoint.  Histogram snapshots ride the same
snapshot/delta plane as counters (worker heartbeats ship them; the
driver merges them with :func:`merge_histogram_snapshots`), and
``to_prometheus`` renders the standard cumulative
``_bucket``/``_sum``/``_count`` exposition.
"""
from __future__ import annotations

import bisect
import json
import re
import threading
import time
import weakref
from collections import deque

_SAN = re.compile(r"[^a-zA-Z0-9_]")

#: dotted metric names that ENCODE a label in the name: rendered as one
#: Prometheus family with a proper label instead of one invalid family
#: per tenant/point/peer.  (pattern, family template, label name) —
#: ``val`` is the label value, ``leaf`` the trailing metric leaf.
_LABELED = (
    (re.compile(r"^admission\.tenant\.(?P<val>.+)\.(?P<leaf>admitted|rejected|pressure_spared)$"),
     "admission_tenant_{leaf}", "tenant"),
    (re.compile(r"^query\.tenant\.(?P<val>.+)\.(?P<leaf>wall_seconds|e2e_seconds)$"),
     "query_tenant_{leaf}", "tenant"),
    (re.compile(r"^control\.decision\.(?P<val>.+)$"),
     "control_decisions_by_rule", "decision"),
    (re.compile(r"^control\.route\.(?P<val>.+)$"),
     "control_routes_by_kind", "kind"),
    (re.compile(r"^faults\.injected\.(?P<val>.+)$"),
     "faults_injected", "point"),
    (re.compile(r"^shuffle\.peer\.(?P<val>.+)\.(?P<leaf>[A-Za-z0-9_]+)$"),
     "shuffle_peer_{leaf}", "peer"),
    (re.compile(r"^shuffle\.breaker\.(?P<val>.+)\.(?P<leaf>failures|open)$"),
     "shuffle_breaker_{leaf}", "peer"),
    (re.compile(r"^cluster\.workers\.state\.(?P<val>.+)$"),
     "cluster_workers", "state"),
)


def _series_parts(name: str) -> "tuple[str, str | None]":
    """(family, label) for one dotted metric name; label is a rendered
    ``key="value"`` pair (escaped) or None for plain names."""
    for pat, fam, label in _LABELED:
        m = pat.match(name)
        if m is None:
            continue
        gd = m.groupdict()
        family = _SAN.sub("_", fam.format(leaf=gd.get("leaf", "")))
        val = gd["val"].replace("\\", "\\\\").replace('"', '\\"') \
            .replace("\n", "\\n")
        return family, f'{label}="{val}"'
    return _SAN.sub("_", name), None


# -- histograms -------------------------------------------------------------

#: default log-bucketed boundaries: 1ms doubling up to ~35 minutes —
#: wide enough for spill I/O at the bottom and stuck cluster RPCs at
#: the top.  Every histogram in the process shares these bounds, so
#: cross-process snapshot merges are bucket-aligned by construction.
_DEFAULT_BOUNDS = tuple(0.001 * (2.0 ** i) for i in range(22))


def empty_histogram_snapshot(bounds=_DEFAULT_BOUNDS) -> dict:
    le = [float(b) for b in bounds]
    return {"le": le, "counts": [0] * (len(le) + 1), "sum": 0.0,
            "count": 0}


def histogram_percentile(snap: "dict | None", q: float) -> "float | None":
    """Estimate the q-th percentile (q in (0, 100]) from a histogram
    snapshot by linear interpolation inside the covering bucket.
    Monotone in q by construction; None for an empty histogram."""
    if not snap or not snap.get("count"):
        return None
    le = snap["le"]
    counts = snap["counts"]
    target = (q / 100.0) * snap["count"]
    cum = 0.0
    for i, c in enumerate(counts):
        if c <= 0:
            continue
        if cum + c >= target:
            lo = le[i - 1] if i > 0 else 0.0
            # the +Inf bucket has no upper bound: report its lower edge
            hi = le[i] if i < len(le) else le[-1]
            return lo + (hi - lo) * max(0.0, min(1.0, (target - cum) / c))
        cum += c
    return float(le[-1])


def merge_histogram_snapshots(a: "dict | None",
                              b: "dict | None") -> dict:
    """Combine two snapshots (worker heartbeat deltas, partial buffers
    from a worker that died mid-run).  Either side may be None/empty —
    an empty delta is inert.  Mismatched bucket bounds (a worker on an
    older build) are re-bucketed conservatively by upper bound."""
    if not a or not a.get("count"):
        return dict(b) if b and b.get("count") \
            else empty_histogram_snapshot((a or b or {}).get(
                "le", _DEFAULT_BOUNDS))
    if not b or not b.get("count"):
        return dict(a)
    le = list(a["le"])
    counts = list(a["counts"])
    if list(b["le"]) == le:
        counts = [x + y for x, y in zip(counts, b["counts"])]
    else:
        for j, c in enumerate(b["counts"]):
            if not c:
                continue
            if j < len(b["le"]):
                i = bisect.bisect_left(le, float(b["le"][j]))
            else:
                i = len(le)
            counts[i] += c
    return {"le": le, "counts": counts,
            "sum": a["sum"] + b["sum"], "count": a["count"] + b["count"]}


def delta_histogram_snapshot(cur: dict,
                             prev: "dict | None") -> "dict | None":
    """Per-bucket movement since ``prev``; None when no new samples
    landed (so empty deltas disappear instead of accumulating)."""
    if prev is None or list(prev.get("le", ())) != list(cur["le"]):
        prev = empty_histogram_snapshot(cur["le"])
    moved = cur["count"] - prev.get("count", 0)
    if moved <= 0:
        return None
    return {"le": list(cur["le"]),
            "counts": [max(0, c - p) for c, p in
                       zip(cur["counts"], prev["counts"])],
            "sum": max(0.0, cur["sum"] - prev.get("sum", 0.0)),
            "count": moved}


class Histogram:
    """Thread-safe log-bucketed latency histogram.

    Fixed bucket boundaries (``_DEFAULT_BOUNDS`` unless given) keep
    ``observe`` at one bisect + three adds, make snapshots mergeable
    across processes, and render directly as the Prometheus cumulative
    ``_bucket{le=...}`` exposition."""

    __slots__ = ("le", "_counts", "_sum", "_count", "_lock")

    def __init__(self, bounds=None):
        self.le = tuple(float(b) for b in (bounds or _DEFAULT_BOUNDS))
        self._counts = [0] * (len(self.le) + 1)
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        v = float(value)
        i = bisect.bisect_left(self.le, v)
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"le": list(self.le), "counts": list(self._counts),
                    "sum": self._sum, "count": self._count}

    def percentile(self, q: float) -> "float | None":
        return histogram_percentile(self.snapshot(), q)

    def merge_snapshot(self, snap: "dict | None") -> None:
        """Fold a shipped snapshot (another process's delta) into this
        histogram; an empty/None snapshot is a no-op."""
        if not snap or not snap.get("count"):
            return
        with self._lock:
            cur = {"le": list(self.le), "counts": list(self._counts),
                   "sum": self._sum, "count": self._count}
            merged = merge_histogram_snapshots(cur, snap)
            self._counts = list(merged["counts"])
            self._sum = merged["sum"]
            self._count = merged["count"]


#: finished-query records kept in memory (``recent_queries``): room for
#: every collect of a benchmark window (48 s of a 0.5 s query is 90; with
#: 64 the window's first, traced, collects had left the ring when the
#: benchmark read them, PERF.md PR 28), at about 10 KB a record
RECENT_QUERIES = 512


class _Span:
    """One open span: the profiler annotation plus the host clock that
    feeds ``span.<name>.count`` / ``.seconds``.  ``seconds`` is readable
    after exit."""

    __slots__ = ("_reg", "name", "_ann", "_t0", "seconds")

    def __init__(self, reg: "MetricsRegistry", name: str, ann):
        self._reg = reg
        self.name = name
        self._ann = ann
        self.seconds = 0.0

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        name = self.name
        self._reg.inc_many(((f"span.{name}.count", 1),
                            (f"span.{name}.seconds", self.seconds)))
        return False


class MetricsRegistry:
    """Thread-safe counters + gauges + histograms + pull sources."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, float] = {}
        self._gauges: dict[str, float] = {}
        self._sources: dict[str, object] = {}
        self._histograms: dict[str, Histogram] = {}
        self._recent: deque = deque(maxlen=RECENT_QUERIES)

    # -- write side --------------------------------------------------------

    def inc(self, name: str, value: float = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + value

    def inc_many(self, pairs) -> None:
        """``inc`` for several ``(name, value)`` pairs under one lock
        acquisition (a span's two counters, a launch's four)."""
        with self._lock:
            counters = self._counters
            for name, value in pairs:
                counters[name] = counters.get(name, 0) + value

    def span(self, name: str, **args) -> _Span:
        """Context manager: a ``jax.profiler.TraceAnnotation`` named
        ``name`` (``args`` — query_id, parent — are its metadata) whose exit
        adds 1 to ``span.<name>.count`` and the elapsed host seconds to
        ``span.<name>.seconds``.  With no profiler session open the
        annotation costs a flag check."""
        from jax.profiler import TraceAnnotation
        return _Span(self, name, TraceAnnotation(name, **args))

    def set_gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = value

    def histogram(self, name: str, bounds=None) -> Histogram:
        """Get-or-create the named histogram (bounds only apply on
        first creation; everyone after shares the instance)."""
        h = self._histograms.get(name)
        if h is None:
            with self._lock:
                h = self._histograms.get(name)
                if h is None:
                    h = self._histograms[name] = Histogram(bounds)
        return h

    def observe(self, name: str, value: float) -> None:
        """Record one latency sample into the named histogram."""
        self.histogram(name).observe(value)

    def register_source(self, name: str, fn) -> None:
        """``fn() -> dict[str, number]``; folded into snapshots under
        ``<name>.<key>``. A source raising or returning junk is dropped
        from that snapshot, never propagated — observability must not
        fail the query."""
        with self._lock:
            self._sources[name] = fn

    def unregister_source(self, name: str) -> None:
        with self._lock:
            self._sources.pop(name, None)

    def register_object_source(self, name: str, obj, attr: str = "metrics"):
        """Register ``obj.<attr>`` (a plain dict) as a source via weakref
        so the registry never keeps a catalog/transport alive."""
        ref = weakref.ref(obj)

        def _pull(_ref=ref, _attr=attr):
            o = _ref()
            if o is None:
                return {}
            d = getattr(o, _attr, None)
            return dict(d) if isinstance(d, dict) else {}

        self.register_source(name, _pull)
        return name

    # -- read side ---------------------------------------------------------

    def counters(self) -> dict:
        """A copy of the counter dict alone — what a per-query record
        differences; unlike ``snapshot`` it walks no pull source."""
        with self._lock:
            return dict(self._counters)

    def counters_since(self, before: dict) -> dict:
        """Counters that moved since ``before`` (a ``counters()``)."""
        return {k: v - before.get(k, 0)
                for k, v in self.counters().items()
                if v != before.get(k, 0)}

    def note_query(self, record: dict) -> None:
        """Append one finished query's record to the bounded ring."""
        with self._lock:
            self._recent.append(record)

    def recent_queries(self, n: "int | None" = None) -> list:
        """The last ``n`` (default: all kept, at most
        ``RECENT_QUERIES``) finished-query records, oldest first."""
        with self._lock:
            out = list(self._recent)
        return out if n is None else out[len(out) - min(n, len(out)):]

    def snapshot(self) -> dict:
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            sources = list(self._sources.items())
            hists = list(self._histograms.items())
        for name, fn in sources:
            try:
                vals = fn()
            # enginelint: disable=RL001 (metric source callbacks are best-effort; a failing source is skipped)
            except Exception:
                continue
            if not isinstance(vals, dict):
                continue
            for k, v in vals.items():
                if isinstance(v, (int, float)):
                    gauges[f"{name}.{k}"] = v
        return {"counters": counters, "gauges": gauges,
                "histograms": {n: h.snapshot() for n, h in hists}}

    def delta(self, prev: dict) -> dict:
        """Counter and histogram movement since ``prev`` (a prior
        ``snapshot()``); gauges are point-in-time and reported as-is.
        Histograms with no new samples are omitted — an empty delta is
        inert (it merges to nothing on the other side)."""
        cur = self.snapshot()
        before = prev.get("counters", {}) if prev else {}
        moved = {}
        for k, v in cur["counters"].items():
            d = v - before.get(k, 0)
            if d:
                moved[k] = d
        hbefore = prev.get("histograms", {}) if prev else {}
        hmoved = {}
        for k, snap in cur.get("histograms", {}).items():
            d = delta_histogram_snapshot(snap, hbefore.get(k))
            if d is not None:
                hmoved[k] = d
        return {"counters": moved, "gauges": cur["gauges"],
                "histograms": hmoved}

    def to_json(self) -> str:
        return json.dumps(self.snapshot(), sort_keys=True)

    def to_prometheus(self, prefix: str = "srt_") -> str:
        """Standard Prometheus text exposition (version 0.0.4).

        Metric names are sanitized to ``[a-zA-Z0-9_]``; dotted names
        that encode a tenant/point/peer (``admission.tenant.<t>.admitted``,
        ``faults.injected.<point>``, ``shuffle.peer.<addr>.*``) become
        one family with a proper label.  Histograms render as the
        cumulative ``_bucket{le=...}``/``_sum``/``_count`` triple."""
        snap = self.snapshot()
        lines = []
        for kind, bucket in (("counter", snap["counters"]),
                             ("gauge", snap["gauges"])):
            fams: dict = {}
            for name in sorted(bucket):
                family, label = _series_parts(name)
                metric = prefix + family
                v = bucket[name]
                val = f"{v:g}" if isinstance(v, float) else str(v)
                series = f"{metric}{{{label}}} {val}" if label \
                    else f"{metric} {val}"
                fams.setdefault(metric, []).append(series)
            for metric in sorted(fams):
                lines.append(f"# TYPE {metric} {kind}")
                lines.extend(fams[metric])
        hfams: dict = {}
        for name in sorted(snap.get("histograms", {})):
            family, label = _series_parts(name)
            hfams.setdefault(prefix + family, []).append(
                (label, snap["histograms"][name]))
        for metric in sorted(hfams):
            lines.append(f"# TYPE {metric} histogram")
            for label, h in hfams[metric]:
                lbl = f"{label}," if label else ""
                suffix = f"{{{label}}}" if label else ""
                cum = 0
                for bound, c in zip(h["le"], h["counts"]):
                    cum += c
                    lines.append(
                        f'{metric}_bucket{{{lbl}le="{bound:g}"}} {cum}')
                cum += h["counts"][-1]
                lines.append(f'{metric}_bucket{{{lbl}le="+Inf"}} {cum}')
                lines.append(f"{metric}_sum{suffix} {h['sum']:g}")
                lines.append(f"{metric}_count{suffix} {h['count']}")
        return "\n".join(lines) + "\n"

    def reset(self) -> None:
        """Test hook: drop all counters/gauges/sources/histograms."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._sources.clear()
            self._histograms.clear()
            self._recent.clear()


_REGISTRY = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """The process-wide registry singleton."""
    return _REGISTRY


def query_metrics_snapshot(ctx) -> dict:
    """Unified per-query view: operator Metrics aggregated by operator
    class, plus the registry snapshot. Used by EXPLAIN ANALYZE footers,
    diagnostics bundles, and the bench runner."""
    ops: dict[str, dict] = {}
    for key, m in getattr(ctx, "metrics", {}).items():
        name = key.split("@")[0]
        agg = ops.setdefault(name, {})
        for k, v in m.values.items():
            agg[k] = agg.get(k, 0) + v
    return {"operators": ops, "registry": _REGISTRY.snapshot()}
