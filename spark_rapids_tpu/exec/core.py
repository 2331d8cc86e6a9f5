"""Physical plan node base + execution context.

TPU analog of the reference's ``GpuExec`` layer (reference
sql-plugin/src/main/scala/com/nvidia/spark/rapids/GpuExec.scala:65-137):
a columnar physical operator produces, per partition, an iterator of
batches.  Where the reference rides Spark's RDD machinery
(``doExecuteColumnar(): RDD[ColumnarBatch]``), this standalone engine
models the same contract directly: ``num_partitions`` + per-partition
batch iterators, with exchanges as stage barriers.

Every node runs on two backends:
* ``device`` — ColumnBatch (jax, jit-compiled kernels), the TPU path;
* ``host``   — HostBatch (numpy), the CPU oracle used for differential
  testing (reference SparkQueryCompareTestSuite.scala:153-167) and as the
  CPU baseline for benchmarks.

Metrics mirror GpuMetricNames (GpuExec.scala:27-56): numOutputRows,
numOutputBatches, totalTime per operator.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Iterator, Sequence

from spark_rapids_tpu import types as T
from spark_rapids_tpu.conf import ConfEntry, TpuConf, register
from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.host.batch import HostBatch
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.runtime import widen_thread_stacks

# worker threads created from here on (drain pools, shuffle servers) get
# deep stacks — XLA:CPU compiles overflow the 8 MiB default (runtime.py)
widen_thread_stacks()

__all__ = [
    "ExecCtx", "PlanNode", "CoalesceGoal", "TargetSize", "RequireSingleBatch",
    "collect", "collect_host", "collect_device", "Metrics",
    "drain_partitions", "drain_partitions_indexed", "fetch_to_host",
]

CONCURRENT_TASKS = register(ConfEntry(
    "spark.rapids.sql.concurrentTpuTasks", 2,
    "Concurrent tasks allowed to occupy the chip (reference "
    "spark.rapids.sql.concurrentGpuTasks, RapidsConf.scala:351). "
    "Partitions execute on a worker pool bounded by this semaphore.",
    conv=int))

PROFILE_DIR = register(ConfEntry(
    "spark.rapids.tpu.profile.dir", "",
    "When set, collect() records an xprof/PJRT trace of the execution "
    "into this directory, with per-operator TraceAnnotation ranges "
    "(reference NVTX ranges + NvtxWithMetrics.scala:27; view with "
    "tensorboard or xprof)."))


# ---------------------------------------------------------------------------
# Batching contracts (reference CoalesceGoal algebra,
# GpuCoalesceBatches.scala:94-130)
# ---------------------------------------------------------------------------

class CoalesceGoal:
    def max_with(self, other: "CoalesceGoal") -> "CoalesceGoal":
        if isinstance(self, RequireSingleBatchT) or \
                isinstance(other, RequireSingleBatchT):
            return RequireSingleBatch
        assert isinstance(self, TargetSize) and isinstance(other, TargetSize)
        return self if self.size >= other.size else other

    def satisfies(self, other: "CoalesceGoal") -> bool:
        if isinstance(other, RequireSingleBatchT):
            return isinstance(self, RequireSingleBatchT)
        return True


@dataclass(frozen=True)
class TargetSize(CoalesceGoal):
    size: int


class RequireSingleBatchT(CoalesceGoal):
    def __repr__(self):
        return "RequireSingleBatch"


RequireSingleBatch = RequireSingleBatchT()


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

class Metrics:
    """Per-operator metric map (reference GpuMetricNames).  add() is
    called concurrently from drain_partitions worker threads, so the
    read-modify-write is locked."""

    def __init__(self):
        self.values: dict[str, float] = {}
        self._lock = threading.Lock()

    def add(self, name: str, v: float):
        with self._lock:
            self.values[name] = self.values.get(name, 0.0) + v

    def __getitem__(self, name: str) -> float:
        return self.values.get(name, 0.0)


class _TracedSpan:
    """The registry's span around the Chrome tracer's (None when that
    tracer is off); ``with`` yields the tracer's span or None."""

    __slots__ = ("_span", "_cm")

    def __init__(self, span, tracer_cm):
        self._span = span
        self._cm = tracer_cm

    def __enter__(self):
        self._span.__enter__()
        return None if self._cm is None else self._cm.__enter__()

    def __exit__(self, *exc):
        try:
            return False if self._cm is None else self._cm.__exit__(*exc)
        finally:
            self._span.__exit__(*exc)


@dataclass
class ExecCtx:
    """Execution context: backend + conf + metrics + device runtime.

    The runtime members are the execution-side wiring of the memory
    subsystem (reference RapidsExecutorPlugin.init, Plugin.scala:124-154):
    a shared BufferCatalog (spill tiers), a DeviceSemaphore bounding chip
    occupancy, and a worker pool draining partitions concurrently.
    """

    backend: str = "device"          # "device" | "host"
    conf: TpuConf = field(default_factory=lambda: TpuConf({}))
    metrics: dict[str, Metrics] = field(default_factory=dict)
    # per-run stage cache: exchanges materialize their shuffle output here
    # once per execution (reference: shuffle files / ShuffleBufferCatalog)
    cache: dict = field(default_factory=dict)
    # shuffle_id -> ShuffleLineage (exec/recovery.py): how each shuffle's
    # map outputs were produced, so a terminal fetch loss re-executes
    # exactly the dead map partitions instead of failing the query
    # (reference: MapOutputTracker registrations driving DAGScheduler
    # stage resubmission on FetchFailed)
    lineage: dict = field(default_factory=dict)
    _lock: threading.RLock = field(default_factory=threading.RLock)
    _inflight: dict = field(default_factory=dict)

    def metrics_for(self, node: "PlanNode") -> Metrics:
        key = f"{type(node).__name__}@{id(node):x}"
        with self._lock:
            if key not in self.metrics:
                self.metrics[key] = Metrics()
            return self.metrics[key]

    @property
    def is_device(self) -> bool:
        return self.backend == "device"

    @property
    def metrics_enabled(self) -> bool:
        if "metrics_enabled" not in self.cache:
            from spark_rapids_tpu.conf import METRICS_ENABLED
            self.cache["metrics_enabled"] = self.conf.get(METRICS_ENABLED)
        return self.cache["metrics_enabled"]

    # -- device runtime ----------------------------------------------------
    @property
    def task_concurrency(self) -> int:
        return max(1, self.conf.get(CONCURRENT_TASKS))

    @property
    def catalog(self):
        with self._lock:
            if "catalog" not in self.cache:
                from spark_rapids_tpu.memory.catalog import BufferCatalog
                cat = BufferCatalog(conf=self.conf)
                # spill I/O is a cooperative cancellation point: a
                # cancelled query must stop pushing bytes to disk
                cat.lifecycle = self.lifecycle
                # cross-query governor (memory/governor.py): attribute
                # this catalog's device bytes to the query and let OOM
                # retries arbitrate against peer queries instead of
                # blind-sweeping.  No-op when the governor conf is off
                from spark_rapids_tpu.memory.governor import maybe_register
                maybe_register(cat, self.query_id, self.lifecycle,
                               self.conf)
                self.cache["catalog"] = cat
            return self.cache["catalog"]

    @property
    def semaphore(self):
        with self._lock:
            if "semaphore" not in self.cache:
                from spark_rapids_tpu.memory.catalog import DeviceSemaphore
                self.cache["semaphore"] = DeviceSemaphore(
                    self.task_concurrency)
            return self.cache["semaphore"]

    # -- query lifecycle (exec/lifecycle.py) -------------------------------
    @property
    def lifecycle(self):
        """Per-query lifecycle handle (cancel event + deadline), minted
        lazily alongside the query id.  Direct ExecCtx users get one
        that is already RUNNING; TpuSession pre-populates the cache
        with an ADMITTED handle it controls."""
        lc = self.cache.get("lifecycle")
        if lc is not None:
            return lc
        with self._lock:
            lc = self.cache.get("lifecycle")
            if lc is None:
                from spark_rapids_tpu.exec.lifecycle import QueryLifecycle
                lc = QueryLifecycle.from_conf(self.query_id, self.conf)
                lc.start()
                self.cache["lifecycle"] = lc
            return lc

    def check_cancel(self) -> None:
        """Cooperative cancellation point: raises the terminal
        QueryCancelled/QueryDeadlineExceeded once the query is
        cancelled or past its deadline (reference: tasks polling
        TaskContext.isInterrupted inside long loops)."""
        self.lifecycle.check()

    def dispatch(self, fn, *args, **kwargs):
        """Run a heavy device program under (a) the DeviceSemaphore
        bounding chip occupancy (reference GpuSemaphore.acquireIfNecessary
        — acquired at the dispatch chokepoint, never while blocking on
        other tasks, so nested partition drains cannot deadlock) and
        (b) the OOM-spill-retry hook (DeviceMemoryEventHandler loop).
        Every dispatch is a cancellation point: a cancelled query stops
        before it can occupy the chip again."""
        self.check_cancel()
        if not self.is_device:
            return fn(*args, **kwargs)
        from spark_rapids_tpu.memory.catalog import run_with_spill_retry
        with self.semaphore:
            return run_with_spill_retry(fn, self.catalog, *args, **kwargs)

    def dispatch_retry(self, fn, batch, *, split: bool = True,
                       op: str | None = None, pairs: bool = False,
                       checkpoint=None, restore=None) -> list:
        """Run ``fn(batch)`` under the full OOM retry scope
        (memory/retry.py): spill on RESOURCE_EXHAUSTED, and when spill
        frees nothing split the batch in half by rows and retry each
        half — the reference's RmmRapidsRetryIterator.withRetry.
        Returns the outputs in row order (one unless a split happened);
        ``split=False`` is withRetryNoSplit for steps whose partial
        outputs would break semantics.  ``pairs=True`` returns
        ``(piece, output)`` tuples so callers can retain the processed
        pieces for a later :meth:`retry_sync` redo."""
        self.check_cancel()
        if not self.is_device:
            r = fn(batch)
            return [(batch, r)] if pairs else [r]
        from spark_rapids_tpu.memory import retry as _retry
        with self.semaphore:
            return _retry.with_retry(
                fn, self.catalog, batch,
                split=_retry.split_half if split else None, op=op,
                pairs=pairs, checkpoint=checkpoint, restore=restore,
                settings=self.conf.settings)

    def retry_sync(self, sync_fn, *, redo=None, op: str = "sync"):
        """Guard a blocking sync of asynchronously dispatched device
        work (chunk-flush device_get): on OOM spill, ``redo()`` the
        poisoned dispatches from retained inputs, and sync again — the
        async-backend OOMs that used to surface outside every retry
        loop are recovered here."""
        self.check_cancel()
        if not self.is_device:
            return sync_fn()
        from spark_rapids_tpu.memory import retry as _retry
        return _retry.retry_sync(sync_fn, self.catalog, redo=redo, op=op,
                                 settings=self.conf.settings)

    def register_lineage(self, shuffle_id, lineage) -> None:
        with self._lock:
            self.lineage[shuffle_id] = lineage

    def lineage_for(self, shuffle_id):
        with self._lock:
            return self.lineage.get(shuffle_id)

    # -- observability (spark_rapids_tpu/obs) ------------------------------
    @property
    def query_id(self) -> str:
        """Stable per-execution id (16 hex chars), minted lazily and
        shared with the tracer and diagnostic bundles."""
        with self._lock:
            qid = self.cache.get("query_id")
            if qid is None:
                import uuid
                qid = self.cache["query_id"] = uuid.uuid4().hex[:16]
            return qid

    @property
    def trace_id(self) -> str:
        t = self.tracer
        return t.trace_id if t is not None else self.query_id

    @property
    def tracer(self):
        """Per-query span tracer, or None when tracing is off.  The
        disabled check reads the RAW conf string so the default path
        never imports the obs package
        (tests/test_telemetry.py::test_disabled_path_never_imports)."""
        with self._lock:
            if "tracer" in self.cache:
                return self.cache["tracer"]
        raw = self.conf.settings.get("spark.rapids.obs.trace.enabled")
        t = None
        if raw is not None and str(raw).lower() in ("true", "1", "yes"):
            from spark_rapids_tpu.obs.trace import TRACE_MAX_EVENTS, Tracer
            t = Tracer(query_id=self.query_id,
                       max_events=self.conf.get(TRACE_MAX_EVENTS))
        with self._lock:
            return self.cache.setdefault("tracer", t)

    @property
    def profiler(self):
        """Per-query cost-attribution profiler (obs/profile.py), or
        None when profiling is off.  Mirrors :attr:`tracer`: the
        disabled check reads the RAW conf string so the default path
        never imports obs.profile/obs.metering (same test)."""
        with self._lock:
            if "profiler" in self.cache:
                return self.cache["profiler"]
        raw = self.conf.settings.get("spark.rapids.obs.profile.enabled")
        p = None
        if raw is not None and str(raw).lower() in ("true", "1", "yes"):
            from spark_rapids_tpu.obs.profile import QueryProfiler
            p = QueryProfiler(self.query_id, self.conf, ctx=self)
        with self._lock:
            return self.cache.setdefault("profiler", p)

    def trace_span(self, name: str, cat: str = "query", *,
                   parent_id=None, **args):
        """Context manager opening the registry's span ``name`` (the
        profiler annotation + ``span.<name>.count/seconds``) and, when
        the Chrome tracer is on, the tracer's span too.  Yields the
        tracer's span for annotate(), None when that tracer is off."""
        t = self.tracer
        return _TracedSpan(
            get_registry().span(name),
            None if t is None else t.span(name, cat, parent_id=parent_id,
                                          **args))

    def trace_event(self, name: str, cat: str = "query", *,
                    parent_id=None, **args) -> None:
        t = self.tracer
        if t is not None:
            t.event(name, cat, parent_id=parent_id, **args)

    def close(self) -> None:
        """End-of-execution cleanup: close shuffle transports, then the
        BufferCatalog (spilled disk files, host arena) if created; last,
        export the query trace when a trace dir is configured."""
        from spark_rapids_tpu.shuffle import ShuffleTransport
        with self._lock:
            prof = self.cache.get("profiler")
        if prof is not None:
            # BEFORE the catalog pop (spill totals are captured off it)
            # and BEFORE trace export (counter tracks must land in it)
            try:
                prof.finalize(self)
            # enginelint: disable=RL001 (profile finalize is best-effort teardown; the query already finished)
            except Exception:
                pass
        with self._lock:
            tkeys = [k for k, v in self.cache.items()
                     if isinstance(v, ShuffleTransport)]
            transports = [self.cache.pop(k) for k in tkeys]
            catalog = self.cache.pop("catalog", None)
            tracer = self.cache.get("tracer")
        for t in transports:
            t.close()
        if catalog is not None:
            lc = self.cache.get("lifecycle")
            if lc is not None:
                # the per-query record's spill totals (lifecycle.py)
                lc.spill = {k: catalog.metrics.get(k, 0) for k in (
                    "bytes_spilled_to_host", "bytes_spilled_to_disk",
                    "device_spills")}
            catalog.close()
        if tracer is not None:
            try:
                from spark_rapids_tpu.obs.trace import TRACE_DIR
                d = self.conf.get(TRACE_DIR)
                if d:
                    import os
                    os.makedirs(d, exist_ok=True)
                    tracer.export(os.path.join(
                        d, f"trace_{tracer.query_id}.json"))
            # enginelint: disable=RL001 (trace export is best-effort teardown; the query already finished)
            except Exception:
                pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def cached(self, key, factory):
        """Thread-safe once-per-execution materialization (exchange /
        broadcast / join-build stage cache).  Exactly one caller runs
        ``factory``; concurrent callers block until it completes."""
        with self._lock:
            if key in self.cache:
                return self.cache[key]
            ev = self._inflight.get(key)
            if ev is None:
                ev = self._inflight[key] = threading.Event()
                owner = True
            else:
                owner = False
        if owner:
            try:
                val = factory()
                with self._lock:
                    self.cache[key] = val
            finally:
                with self._lock:
                    self._inflight.pop(key, None)
                ev.set()
            return val
        ev.wait()
        with self._lock:
            if key in self.cache:
                return self.cache[key]
        # the owner failed; when the query was cancelled or timed out the
        # owner's failure IS the cancellation — surface that, not a
        # secondary "another task" error
        self.check_cancel()
        raise RuntimeError(f"stage materialization failed for {key!r} "
                           "in another task")


# ---------------------------------------------------------------------------
# Plan node
# ---------------------------------------------------------------------------

class PlanNode:
    """Base physical operator.

    Subclasses implement ``partition_iter`` producing batches for one
    partition on the active backend. ``output_schema`` is the operator's
    output schema; ``children`` its inputs.
    """

    def __init__(self, children: Sequence["PlanNode"]):
        self.children = tuple(children)

    def __init_subclass__(cls, **kw):
        """Auto-instrument every operator's partition_iter with the
        standard metric set (totalTime / numOutputBatches /
        numOutputRows + an xprof TraceAnnotation range) — the reference
        wires GpuMetricNames into every GpuExec (GpuExec.scala:27-56);
        here the base class does it so operators cannot forget.
        totalTime is inclusive of children, as in the reference.
        numOutputRows: on the host backend always; on the device backend
        only when the batch already carries a host-side count
        (ColumnBatch.known_rows — set by the pack builder, shuffle
        writers and OOM splitters) — reading num_rows off a device batch
        would force a D2H sync per batch, so unknown counts stay
        unrecorded rather than paid for.  When a tracer is active, one
        summary span per (operator, partition) is recorded on
        exhaustion."""
        super().__init_subclass__(**kw)
        impl = cls.__dict__.get("partition_iter")
        if impl is None:
            return

        def timed_partition_iter(self, ctx, pid, _impl=impl):
            if not ctx.metrics_enabled:
                yield from _impl(self, ctx, pid)
                return
            import jax.profiler as _prof
            m = ctx.metrics_for(self)
            label = type(self).__name__
            tracer = ctx.tracer
            prof = ctx.profiler
            it = _impl(self, ctx, pid)
            first_t0 = None
            batches = 0
            rows = 0
            active = 0.0
            # enginelint: disable=RL004 (driven by next(it); terminates with the child iterator and propagates its exceptions)
            while True:
                t0 = time.perf_counter()
                if first_t0 is None:
                    first_t0 = t0
                try:
                    with _prof.TraceAnnotation(label):
                        batch = next(it)
                except StopIteration:
                    break
                dt = time.perf_counter() - t0
                m.add("totalTime", dt)
                m.add("numOutputBatches", 1)
                active += dt
                batches += 1
                if not ctx.is_device:
                    m.add("numOutputRows", batch.num_rows)
                    rows += batch.num_rows
                else:
                    kr = getattr(batch, "known_rows", None)
                    if kr is not None:
                        m.add("numOutputRows", kr)
                        rows += kr
                yield batch
            if first_t0 is not None:
                if tracer is not None:
                    # dur is wall clock first-pull -> exhaustion
                    # (includes consumer suspension; the active time is
                    # in totalTime)
                    tracer.complete(label, "operator", first_t0,
                                    time.perf_counter(), node=label,
                                    partition=pid, batches=batches,
                                    rows=rows)
                if prof is not None:
                    # one bounded record per (operator, partition) —
                    # never per-batch work (the <3% overhead budget)
                    prof.record_op(self, label, active,
                                   time.perf_counter() - first_t0,
                                   batches, rows, pid)

        timed_partition_iter.__wrapped__ = impl
        cls.partition_iter = timed_partition_iter

    # -- contract ----------------------------------------------------------
    @property
    def output_schema(self) -> T.Schema:
        raise NotImplementedError

    def num_partitions(self, ctx: ExecCtx) -> int:
        if self.children:
            return self.children[0].num_partitions(ctx)
        return 1

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        raise NotImplementedError

    def partition_iter_slice(self, ctx: ExecCtx, pid: int, lo: int,
                             hi: int | None) -> Iterator:
        """Batches [lo, hi) of one partition.  Default: enumerate-and-skip
        over partition_iter; ShuffleExchangeExec overrides with a sliced
        transport fetch that skips materializing the rest.  Keeps the
        adaptive reader safe over ANY child (e.g. a BackendSwitchExec
        inserted by transition overrides).  Uses the UNinstrumented
        implementation: repeated slice windows must not inflate this
        operator's output metrics with skipped batches (the consumer's
        own wrapper records what is actually emitted)."""
        fn = type(self).partition_iter
        fn = getattr(fn, "__wrapped__", fn)
        for i, b in enumerate(fn(self, ctx, pid)):
            if i < lo:
                continue
            if hi is not None and i >= hi:
                break
            yield b

    #: bound (fully-typed) expressions this operator evaluates — the
    #: planner's tagging pass checks device_supported on these, since
    #: dtype-dependent checks can't run on unresolved trees
    @property
    def bound_exprs(self) -> list:
        return []

    @property
    def output_ordering(self) -> list | None:
        """Column names such that, WITHIN each emitted batch, rows equal
        on any prefix of them are contiguous (a lexicographic sort by
        these columns guarantees it).  None = no guarantee.  Downstream
        sort-based group-bys use this to skip their re-sort when the
        child already clusters the grouping keys — the reference keeps
        the analogous sort-order metadata on SparkPlan.outputOrdering
        and GpuSortAggregate picks merge-aggregation off it
        (aggregate.scala:348-560)."""
        return None

    #: True when this operator JITs multiple input batches together
    #: (concat, merge, build-side materialization) — such programs need
    #: same-device inputs, so the planner aligns mesh-committed batches
    #: flowing into them.  Per-batch operators (project/filter/limit)
    #: override to False and pass placement through untouched.
    combines_batches: bool = True

    # -- batching contracts (reference GpuExec.scala:71-86) ----------------
    @property
    def children_coalesce_goal(self) -> list[CoalesceGoal | None]:
        return [None] * len(self.children)

    @property
    def output_batching(self) -> CoalesceGoal | None:
        return None

    # -- execution helpers -------------------------------------------------
    def execute(self, ctx: ExecCtx) -> Iterator:
        """All partitions' batches, in partition order.  On the device
        backend partitions run concurrently on a worker pool (reference:
        Spark's task scheduler running doExecuteColumnar RDD
        partitions).  Metrics/trace ranges are recorded per operator by
        the auto-instrumented partition_iter (see __init_subclass__).

        The FIRST execute() on a ctx is the query root: it opens the
        query span and is the failure-diagnostics chokepoint — a query
        that dies here emits a bounded diagnostic bundle when
        spark.rapids.obs.diagnostics.dir is set (obs/diag.py). Both
        checks read raw conf strings so the disabled path never imports
        the obs package."""
        with ctx._lock:
            root = not ctx.cache.get("query_root_claimed")
            if root:
                ctx.cache["query_root_claimed"] = True
        if not root:
            yield from drain_partitions(ctx, self)
            return
        try:
            # the Chrome tracer's root span only: the registry's
            # ``query`` span is the session's, around the whole collect
            import contextlib
            t = ctx.tracer
            with contextlib.nullcontext() if t is None else t.span(
                    "query", "query", root=type(self).__name__,
                    backend=ctx.backend):
                yield from drain_partitions(ctx, self)
        except GeneratorExit:
            raise
        except Exception as e:
            # a cancelled/deadline-exceeded query closes its trace with
            # the terminal lifecycle state so the timeline shows WHY the
            # query span ended early (and the diag bundle below carries
            # the same state for post-mortems)
            if getattr(e, "terminal", False):
                lc = ctx.cache.get("lifecycle")
                if lc is not None and lc.state in ("CANCELLED",
                                                   "DEADLINE_EXCEEDED"):
                    t = ctx.tracer
                    if t is not None:
                        t.set_query_state(lc.state)
                        t.event("query.lifecycle", "query",
                                state=lc.state)
            out_dir = ctx.conf.settings.get(
                "spark.rapids.obs.diagnostics.dir")
            emit = False
            if out_dir:
                with ctx._lock:
                    emit = not ctx.cache.get("diag_emitted")
                    ctx.cache["diag_emitted"] = True
            if emit:
                from spark_rapids_tpu.obs.diag import maybe_emit_bundle
                maybe_emit_bundle(ctx, self, e, str(out_dir))
            raise

    # -- plan introspection ------------------------------------------------
    def tree_string(self, indent: int = 0) -> str:
        s = "  " * indent + self.node_desc() + "\n"
        for c in self.children:
            s += c.tree_string(indent + 1)
        return s

    def node_desc(self) -> str:
        return type(self).__name__


# ---------------------------------------------------------------------------
# Concurrent partition drain
# ---------------------------------------------------------------------------

def drain_partitions(ctx: ExecCtx, node: PlanNode) -> Iterator:
    """Yield every partition's batches in partition order.

    Device backend with >1 partitions: partitions are drained concurrently
    by a worker pool; each worker holds the DeviceSemaphore while pulling a
    batch (chip-occupancy bound, reference GpuSemaphore.acquireIfNecessary,
    GpuSemaphore.scala:74-126) and parks finished batches in the
    BufferCatalog as spillable buffers (priority READ_SHUFFLE) so completed
    partitions don't pin HBM while earlier partitions are still being
    consumed (reference RapidsCachingWriter storing map output spillable,
    RapidsShuffleInternalManager.scala:90-155).
    """
    for _pid, b in drain_partitions_indexed(ctx, node):
        yield b


def drain_partitions_indexed(ctx: ExecCtx, node: PlanNode) -> Iterator:
    """drain_partitions, but yielding ``(partition_id, batch)`` so the
    consumer knows which child partition produced each batch — the
    shuffle exchange records this as the map-output lineage
    (exec/recovery.py re-drains exactly the partitions whose outputs
    were lost).  Same worker pool, same spillable parking, same
    partition-ordered delivery."""
    n = node.num_partitions(ctx)
    lc = ctx.lifecycle
    workers = min(ctx.task_concurrency, n) if ctx.is_device else 1
    if workers <= 1 or n <= 1:
        for pid in range(n):
            with ctx.trace_span("partition", "partition",
                                node=type(node).__name__, partition=pid):
                for b in node.partition_iter(ctx, pid):
                    lc.check()
                    yield pid, b
        return

    import concurrent.futures as cf
    from spark_rapids_tpu.memory.catalog import (SpillableColumnarBatch,
                                                 SpillPriority)
    catalog = ctx.catalog
    tracer = ctx.tracer
    # worker threads have empty span stacks; parent their partition spans
    # onto whatever span is open on the draining thread (query/stage)
    drain_parent = tracer.current_span_id() if tracer is not None else None
    # early consumer exit (LIMIT satisfied, error, cancel): the finally
    # block raises this flag and in-flight workers stop at their NEXT
    # batch boundary instead of draining every partition to completion
    stop = threading.Event()

    def drain(pid: int):
        # chip occupancy is bounded inside ctx.dispatch, not here: holding
        # the semaphore across a next() that may itself drain partitions
        # (join build sides, nested exchanges) would deadlock
        out: list = []
        with ctx.trace_span("partition", "partition",
                            parent_id=drain_parent,
                            node=type(node).__name__, partition=pid):
            it = node.partition_iter(ctx, pid)
            try:
                while not stop.is_set():
                    lc.check()
                    try:
                        b = next(it)
                    except StopIteration:
                        break
                    out.append(SpillableColumnarBatch(
                        b, catalog, SpillPriority.READ_SHUFFLE))
            except BaseException:
                # the batches already parked would otherwise sit in the
                # catalog until ctx.close(); the post-cancel invariant
                # is "parked spillable batches closed"
                for sb in out:
                    sb.close()
                raise
            finally:
                close = getattr(it, "close", None)
                if close is not None:
                    close()
        return out

    with cf.ThreadPoolExecutor(max_workers=workers,
                               thread_name_prefix="tpu-task") as pool:
        futures = [pool.submit(drain, pid) for pid in range(n)]
        try:
            for pid, fut in enumerate(futures):
                for sb in fut.result():
                    lc.check()
                    yield pid, sb.get()
                    sb.close()
        finally:
            # early consumer exit / error: stop in-flight workers at
            # their next batch boundary, then release every
            # still-registered buffer (close is idempotent; unconsumed
            # = leaked otherwise)
            stop.set()
            for fut in futures:
                if fut.cancel():
                    continue
                try:
                    for sb in fut.result():
                        sb.close()
                # enginelint: disable=RL001 (finally-block cleanup: raising would mask the in-flight exception; normal completion already consumed every future)
                except BaseException:
                    pass


# ---------------------------------------------------------------------------
# Collect surface
# ---------------------------------------------------------------------------

def _rows_from_host(b: HostBatch) -> list[tuple]:
    cols = [c.to_list() for c in b.columns]
    return list(zip(*cols)) if cols else [()] * b.num_rows


def collect_host(plan: PlanNode, conf: TpuConf | None = None,
                 ctx: ExecCtx | None = None) -> list[tuple]:
    """Run on the CPU oracle; rows as python tuples.  ``ctx`` lets the
    session pass a context pre-bound to its lifecycle handle (so
    cancel/deadline reach the run); the ctx is closed here either
    way."""
    with (ctx or ExecCtx(backend="host", conf=conf or TpuConf({}))) as ctx:
        out: list[tuple] = []
        for b in plan.execute(ctx):
            out.extend(_rows_from_host(b))
        return out


def collect_device(plan: PlanNode, conf: TpuConf | None = None,
                   ctx: ExecCtx | None = None) -> list[tuple]:
    """Run on the TPU path; rows as python tuples (D2H at the end only).
    With spark.rapids.tpu.profile.dir set, the whole execution records an
    xprof trace (reference: nsight timelines over NVTX ranges).  ``ctx``
    lets the session pass a context pre-bound to its lifecycle handle."""
    import contextlib
    with (ctx or ExecCtx(backend="device", conf=conf or TpuConf({}))) as ctx:
        profile_dir = ctx.conf.get(PROFILE_DIR)
        prof = contextlib.nullcontext()
        if profile_dir:
            import jax.profiler as _prof
            prof = _prof.trace(profile_dir)
        with prof:
            out: list[tuple] = []
            reg = get_registry()
            for b in plan.execute(ctx):
                # the result's phase: its fetch and the rows to python
                with reg.span("query.fetch", query_id=ctx.query_id,
                              parent="query"):
                    out.extend(_rows_from_host(device_to_host(b, None)))
            return out


def collect(plan: PlanNode, backend: str = "device",
            conf: TpuConf | None = None) -> list[tuple]:
    if backend == "host":
        return collect_host(plan, conf)
    return collect_device(plan, conf)


def fetch_to_host(tree, op: "str | None"):
    """The engine's one blocking device fetch: ``jax.device_get(tree)``
    under the span ``op`` (``fetch@<Operator>Exec`` for an operator's
    own sync; None inside a span the caller holds, as ``query.fetch``
    around the result), counting ``d2h_calls``, ``d2h_bytes`` (of the
    fetched leaves) and ``sync_wait_s`` (seconds the calling thread was
    blocked).  On an asynchronous backend the wait is the device
    finishing what was queued, not only the copy."""
    import jax
    import contextlib
    reg = get_registry()
    t0 = time.perf_counter()
    with contextlib.nullcontext() if op is None else reg.span(op):
        host = jax.device_get(tree)
    waited = time.perf_counter() - t0
    nbytes = 0
    for leaf in jax.tree_util.tree_leaves(host):
        nbytes += getattr(leaf, "nbytes", 0)
    reg.inc_many((("d2h_calls", 1), ("d2h_bytes", nbytes),
                  ("sync_wait_s", waited)))
    return host


def device_to_host(b: ColumnBatch,
                   op: "str | None" = "fetch@device_to_host") -> HostBatch:
    """D2H: ColumnBatch -> HostBatch (reference GpuColumnarToRowExec /
    GpuBringBackToHost transition); ``op`` as in :func:`fetch_to_host`."""
    import numpy as np
    from spark_rapids_tpu.host.batch import HostColumn
    # ONE device_get for num_rows + all column leaves: separate fetches
    # pay a full host round trip each
    n, host = fetch_to_host(
        (b.num_rows, [(c.data, c.validity, c.lengths) for c in b.columns]),
        op)
    n = int(n)
    cols = []
    for f, (data, validity, lengths) in zip(b.schema, host):
        v = np.asarray(validity[:n], dtype=np.bool_)
        if isinstance(f.data_type, T.StringType):
            bm = np.asarray(data[:n])
            ln = np.asarray(lengths[:n])
            py = np.empty(n, dtype=object)
            for i in range(n):
                py[i] = bytes(bm[i, :ln[i]]).decode("utf-8", "replace") \
                    if v[i] else None
            cols.append(HostColumn(py, v, f.data_type))
        elif isinstance(f.data_type, T.ArrayType):
            m = np.asarray(data[:n])
            ln = np.asarray(lengths[:n])
            py = np.empty(n, dtype=object)
            for i in range(n):
                py[i] = m[i, :ln[i]].tolist() if v[i] else None
            cols.append(HostColumn(py, v, f.data_type))
        else:
            cols.append(HostColumn(np.asarray(data[:n]), v, f.data_type))
    return HostBatch(cols, b.schema)


def host_to_device(b: HostBatch, capacity: int | None = None) -> ColumnBatch:
    """H2D: HostBatch -> ColumnBatch (reference HostColumnarToGpu).
    Columns are staged into per-dtype packed buffers and moved with one
    transfer per dtype (columnar/batch._PackBuilder)."""
    import numpy as np
    from spark_rapids_tpu.columnar.batch import _PackBuilder, round_capacity
    from spark_rapids_tpu.columnar.column import round_string_width
    from spark_rapids_tpu.columnar.batch import _codec_auto
    n = b.num_rows
    cap = capacity or round_capacity(max(n, 1))
    pack = _PackBuilder(cap, _codec_auto(cap, None))
    for f, col in zip(b.schema, b.columns):
        if isinstance(f.data_type, T.StringType):
            enc = [(x.encode("utf-8") if x is not None else b"")
                   for x in col.data]
            maxw = max((len(e) for e in enc), default=1)
            w = round_string_width(max(maxw, 1))
            bm = np.zeros((n, w), dtype=np.uint8)
            lens = np.zeros(n, dtype=np.int32)
            for i, e in enumerate(enc):
                bm[i, :len(e)] = np.frombuffer(e, dtype=np.uint8)
                lens[i] = len(e)
            pack.add_var(bm, lens, col.validity, w)
        elif isinstance(f.data_type, T.ArrayType):
            vals = [(v if v is not None else []) for v in col.data]
            maxw = max((len(v) for v in vals), default=1)
            w = round_string_width(max(maxw, 1))
            m = np.zeros((n, w), dtype=f.data_type.np_dtype)
            lens = np.zeros(n, dtype=np.int32)
            for i, v in enumerate(vals):
                m[i, :len(v)] = v
                lens[i] = len(v)
            pack.add_var(m, lens, col.validity, w)
        else:
            pack.add_fixed(np.asarray(col.data), col.validity)
    return pack.build(n, b.schema)
