"""Request-scoped span tracing with Perfetto/Chrome ``trace_event`` export.

Dapper-style (Sigelman et al., 2010): one ``query_id``/``trace_id`` pair
is minted per execution and every span/event carries it, so a reduce-side
fetch, its retries, and any lineage recompute — possibly on another
process, propagated through the TCP fetch request — all land under the
originating query's trace.  The reference plugin leans on NVTX ranges +
the Spark SQL UI for the same story (GpuExec withResources/NvtxWithMetrics);
this headless engine exports the Chrome ``trace_event`` JSON array format
(ph="X" complete events, ph="i" instants, µs timestamps) which both
Perfetto and chrome://tracing load directly, alongside the existing xprof
hook (`spark.rapids.tpu.profile.dir`).

This module is only imported when `spark.rapids.obs.trace.enabled` is set
(ExecCtx checks the raw conf string first) or when a diagnostic bundle is
being emitted — the disabled path never touches it
(tests/test_telemetry.py::test_disabled_path_never_imports).
"""
from __future__ import annotations

import contextlib
import itertools
import json
import os
import threading
import time
import uuid
from collections import deque

from ..conf import ConfEntry, register, _bool

TRACE_ENABLED = register(ConfEntry(
    "spark.rapids.obs.trace.enabled", False,
    "Open a span per query/stage/partition/operator and record them as "
    "Chrome trace_event dicts with a propagated query_id/trace_id "
    "(carried across the TCP shuffle wire). Off by default: the disabled "
    "path never imports the tracer and adds no per-batch work.",
    conv=_bool))
TRACE_DIR = register(ConfEntry(
    "spark.rapids.obs.trace.dir", "",
    "When set, ExecCtx.close() exports the query's trace as "
    "trace_<query_id>.json (Perfetto/Chrome trace_event JSON) into this "
    "directory. Empty (default): spans are kept in memory only (still "
    "available to diagnostics bundles and EXPLAIN ANALYZE)."))
TRACE_MAX_EVENTS = register(ConfEntry(
    "spark.rapids.obs.trace.maxEvents", 10000,
    "Bounded span-event buffer per query: oldest events are dropped past "
    "this count so a long query cannot grow the tracer without limit.",
    conv=int))


def new_query_id() -> str:
    """16-hex-char query id; doubles as the default trace id."""
    return uuid.uuid4().hex[:16]


class _Span:
    """One open span; append-only until closed. Not a context manager
    itself — ``Tracer.span`` wraps open/close with parent bookkeeping."""

    __slots__ = ("name", "cat", "span_id", "parent_id", "t0", "args")

    def __init__(self, name, cat, span_id, parent_id, args):
        self.name = name
        self.cat = cat
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0 = time.perf_counter()
        self.args = args

    def annotate(self, **kv):
        self.args.update(kv)


class Tracer:
    """Per-query tracer: bounded event buffer + thread-local span stacks.

    Spans nest per-thread (each worker thread sees its own parent chain),
    but generator-driven operators can suspend mid-span and close out of
    order — the stack pop is therefore by identity, not strictly LIFO.
    All methods are safe to call from multiple threads.
    """

    def __init__(self, query_id: str | None = None,
                 trace_id: str | None = None, max_events: int = 10000):
        self.query_id = query_id or new_query_id()
        self.trace_id = trace_id or self.query_id
        self._events: deque = deque(maxlen=max(1, int(max_events)))
        self._dropped = 0
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._tls = threading.local()
        # trace_event ts fields are µs relative to a common origin
        self._origin = time.perf_counter()
        self._wall_origin = time.time()
        self.pid = os.getpid()
        # pid -> ph="M" process_name metadata, kept OUTSIDE the bounded
        # deque so lane names survive event-buffer rotation; prepended
        # at export (Perfetto reads metadata in any position, but names
        # must not be evictable)
        self._lanes: dict[int, dict] = {}
        # terminal lifecycle state (CANCELLED / DEADLINE_EXCEEDED / ...)
        # stamped by the query root when the run ends abnormally; carried
        # in the export header so a trace says WHY it ends early
        self.query_state: str | None = None

    # -- internals ---------------------------------------------------------

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _ts_us(self, t: float) -> float:
        return (t - self._origin) * 1e6

    def _base_args(self, span_id, parent_id) -> dict:
        return {"query_id": self.query_id, "trace_id": self.trace_id,
                "span_id": span_id, "parent_id": parent_id}

    def _push(self, ev: dict):
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self._dropped += 1
            self._events.append(ev)

    # -- span API ----------------------------------------------------------

    def current_span_id(self) -> int | None:
        st = self._stack()
        return st[-1].span_id if st else None

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "query", *,
             parent_id: int | None = None, **args):
        """Open a span; yields the span object for ``annotate(**kv)``.

        ``parent_id`` overrides the thread-local parent — used when the
        logical parent lives on another thread (worker pools) or another
        process (the TCP server re-parents onto the propagated span id).
        """
        st = self._stack()
        if parent_id is None:
            parent_id = st[-1].span_id if st else None
        sp = _Span(name, cat, next(self._ids), parent_id, dict(args))
        st.append(sp)
        try:
            yield sp
        finally:
            # identity removal: suspended generators may close spans out
            # of LIFO order on this thread
            try:
                st.remove(sp)
            except ValueError:
                pass
            t1 = time.perf_counter()
            ev = {"name": name, "cat": cat, "ph": "X",
                  "ts": self._ts_us(sp.t0), "dur": (t1 - sp.t0) * 1e6,
                  "pid": self.pid, "tid": threading.get_ident(),
                  "args": {**self._base_args(sp.span_id, sp.parent_id),
                           **sp.args}}
            self._push(ev)

    def event(self, name: str, cat: str = "query", *,
              parent_id: int | None = None, **args):
        """Record an instant event under the current (or given) span."""
        if parent_id is None:
            parent_id = self.current_span_id()
        ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
              "ts": self._ts_us(time.perf_counter()),
              "pid": self.pid, "tid": threading.get_ident(),
              "args": {**self._base_args(next(self._ids), parent_id),
                       **args}}
        self._push(ev)

    def complete(self, name: str, cat: str, t0: float, t1: float, *,
                 parent_id: int | None = None, **args):
        """Record an already-timed span (perf_counter endpoints)."""
        if parent_id is None:
            parent_id = self.current_span_id()
        ev = {"name": name, "cat": cat, "ph": "X",
              "ts": self._ts_us(t0), "dur": (t1 - t0) * 1e6,
              "pid": self.pid, "tid": threading.get_ident(),
              "args": {**self._base_args(next(self._ids), parent_id),
                       **args}}
        self._push(ev)

    def counter(self, name: str, *, t: "float | None" = None,
                wall_t: "float | None" = None, **values):
        """Record a Perfetto counter sample (ph="C"): ``values`` are
        the numeric series plotted as a stacked counter track.  ``t``
        is a perf_counter timestamp; ``wall_t`` an absolute
        ``time.time()`` one (the HBM sampler's clock) rebased onto this
        tracer's origin; neither = now.  The cost-attribution plane
        (obs/profile.py) merges HBM occupancy and per-operator
        device-seconds lanes into the query trace through this."""
        if wall_t is not None:
            ts = (wall_t - self._wall_origin) * 1e6
        else:
            ts = self._ts_us(time.perf_counter() if t is None else t)
        ev = {"name": name, "cat": "counter", "ph": "C", "ts": ts,
              "pid": self.pid, "tid": 0,
              "args": {**self._base_args(next(self._ids), None),
                       **values}}
        self._push(ev)

    def set_query_state(self, state: str) -> None:
        """Record the query's terminal lifecycle state (exec/lifecycle)."""
        self.query_state = state

    # -- cluster aggregation ----------------------------------------------

    def ensure_lane(self, pid: int, name: str) -> None:
        """Name a process lane (driver, each worker) with a ph="M"
        process_name metadata record — ONE Perfetto timeline then shows
        every process's spans on its own labelled track."""
        with self._lock:
            if pid in self._lanes:
                return
            self._lanes[pid] = {
                "name": "process_name", "cat": "__metadata", "ph": "M",
                "pid": pid, "tid": 0, "ts": 0,
                "args": {**self._base_args(next(self._ids), None),
                         "name": name},
            }

    def drain_events(self) -> list[dict]:
        """Pop and return every buffered event (oldest first).  Used by
        cluster workers to ship spans incrementally on heartbeats and
        fragment completion — an event is shipped exactly once."""
        with self._lock:
            evs = list(self._events)
            self._events.clear()
        return evs

    def ingest_wall(self, events: list[dict]) -> None:
        """Merge events whose ``ts`` is ABSOLUTE wall-clock µs (see
        :func:`stamp_for_shipping`) into this tracer's buffer, rebased
        onto its own origin so driver and worker spans share one
        timeline.  Clock skew between processes on one host is bounded
        by NTP-free time.time() drift — microseconds over a query."""
        base = self._wall_origin * 1e6
        for ev in events:
            ev = dict(ev)
            ev["ts"] = ev.get("ts", 0.0) - base
            self._push(ev)

    # -- export ------------------------------------------------------------

    def events_snapshot(self, last: int | None = None) -> list[dict]:
        with self._lock:
            evs = list(self._lanes.values()) + list(self._events)
        if last is not None and last >= 0:
            evs = evs[-last:]
        return evs

    def export(self, path: str) -> str:
        """Write Perfetto/Chrome trace JSON; returns the path written."""
        doc = {
            "traceEvents": self.events_snapshot(),
            "displayTimeUnit": "ms",
            "otherData": {
                "query_id": self.query_id,
                "trace_id": self.trace_id,
                "wall_clock_origin_unix_s": self._wall_origin,
                "events_dropped": self._dropped,
            },
        }
        if self.query_state is not None:
            doc["otherData"]["query_state"] = self.query_state
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path

    def trace_header(self) -> dict:
        """Propagation header carried in TCP fetch requests: enough for
        the serving side to attribute its work to this query's trace."""
        hdr = {"query_id": self.query_id, "trace_id": self.trace_id}
        sid = self.current_span_id()
        if sid is not None:
            hdr["span_id"] = sid
        return hdr


def stamp_for_shipping(events: list[dict], wall_origin: float,
                       pid: int) -> list[dict]:
    """Prepare drained events for cross-process shipping: rewrite each
    ``ts`` from tracer-origin-relative µs to ABSOLUTE wall-clock µs
    (``wall_origin`` is the shipping tracer's ``_wall_origin``) and
    stamp the shipping process's pid, so the receiving driver can rebase
    onto ITS origin (:meth:`Tracer.ingest_wall`) and keep per-worker
    lanes distinct."""
    base = wall_origin * 1e6
    out = []
    for ev in events:
        ev = dict(ev)
        ev["ts"] = ev.get("ts", 0.0) + base
        ev["pid"] = pid
        out.append(ev)
    return out
