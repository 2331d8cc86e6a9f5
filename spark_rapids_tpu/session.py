"""TpuSession + DataFrame: the user-facing query API.

The reference has no API of its own — it transparently accelerates
Spark SQL (`spark.plugins=com.nvidia.spark.SQLPlugin`,
SQLPlugin.scala:26-31).  Standalone, this engine exposes a PySpark-like
DataFrame API whose plans run through the same rewrite pipeline: build
logical plan -> lower to dual-backend execs -> TpuOverrides tagging
(per-op conf keys, fallback reasons, explain) -> transitions -> execute
on the TPU with the CPU engine as automatic fallback per node.
"""
from __future__ import annotations

from typing import Iterable, Sequence

from spark_rapids_tpu import types as T
from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.exec.core import ExecCtx, PlanNode, collect_device, \
    collect_host
from spark_rapids_tpu.expr.core import (Alias, Expression, Literal, col,
                                        lit, output_name)
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.overrides import PlannedNode, TpuOverrides, lower

__all__ = ["TpuSession", "DataFrame"]


class TpuSession:
    """Session: conf + data sources (reference: SparkSession + the
    plugin's RapidsConf snapshot, Plugin.scala:116).

    The session is also the query lifecycle control plane
    (exec/lifecycle.py): every ``collect`` runs through FIFO admission
    (``spark.rapids.sql.admission.*``), is registered under its
    query_id while in flight so :meth:`cancel` / :meth:`cancel_all`
    reach it, and carries a deadline from
    ``spark.rapids.sql.queryTimeout`` or ``collect(timeout=...)``.
    :meth:`shutdown` stops admission and drains (or cancels) what is
    left — the analog of SparkContext.stop over the plugin's
    task-kill machinery."""

    def __init__(self, conf: dict | TpuConf | None = None):
        self.conf = conf if isinstance(conf, TpuConf) else TpuConf(conf or {})
        from spark_rapids_tpu.runtime import ensure_runtime
        ensure_runtime(self.conf)
        import threading
        self._lc_cond = threading.Condition()
        self._live: dict = {}        # query_id -> QueryLifecycle
        self._admission = None       # built lazily from the live conf
        self._cluster_handle = None  # ClusterDriver, lazily spawned
        self._http = None            # ObsHttpServer when the conf is on
        self._control = None         # ControlLoop when the conf is on
        # raw-settings gated: with the port conf absent/0 (the default)
        # obs.http is never imported (tests/test_telemetry.py::
        # test_disabled_path_never_imports, as for every gate below)
        port = self.conf.settings.get("spark.rapids.obs.http.port")
        if port and int(port) > 0:
            from spark_rapids_tpu.obs.http import ObsHttpServer
            self._http = ObsHttpServer(self, int(port))
        # raw-settings gated like http/history/cluster: with
        # control.enabled unset (the default) the control package is
        # never imported — plans, confs, and counters stay
        # byte-identical to the static engine
        if str(self.conf.settings.get(
                "spark.rapids.control.enabled", "")).lower() \
                in ("true", "1", "yes"):
            from spark_rapids_tpu.control import ControlLoop
            self._control = ControlLoop(self)
            self._control.start()

    # -- query lifecycle (exec/lifecycle.py) ---------------------------
    def _admission_controller(self):
        with self._lc_cond:
            if self._admission is None:
                from spark_rapids_tpu.exec.lifecycle import \
                    AdmissionController
                self._admission = AdmissionController.from_conf(self.conf)
                from spark_rapids_tpu.memory.governor import (
                    GOVERNOR_ENABLED, get_governor)
                if GOVERNOR_ENABLED.get(self.conf.settings):
                    # memory-pressure shedding: sustained device
                    # occupancy above the shed watermark rejects NEW
                    # queries at admission (memory/governor.py) —
                    # inert with the governor conf off
                    self._admission.pressure_hook = \
                        get_governor().admission_pressure
                # serving-tier fault points (admission.tenant.storm,
                # cache.result.corrupt) — inert unless
                # spark.rapids.test.faults names a plan
                from spark_rapids_tpu.faults import FaultRegistry
                self._admission.faults = FaultRegistry.from_conf(self.conf)
            return self._admission

    def _cluster(self):
        """Lazily spawn the ``local[N]`` worker pool (cluster/driver.py)
        on the first device query.  Raw-settings gated: with
        ``cluster.mode=off`` (the default) the cluster package is never
        imported and this returns None without side effects."""
        if self.conf.settings.get("spark.rapids.cluster.mode",
                                  "off") == "off":
            return None
        with self._lc_cond:
            if self._cluster_handle is None:
                from spark_rapids_tpu.cluster.driver import ClusterDriver
                self._cluster_handle = ClusterDriver(self.conf)
            return self._cluster_handle

    def attach_cluster(self, driver) -> "TpuSession":
        """Adopt an already-built ClusterDriver — the crash-recovery
        entry point: ``ClusterDriver.recover(conf, journal_dir)``
        rebuilds the pool from the write-ahead journal, then the new
        session attaches it instead of spawning fresh workers, so
        resumed queries can claim the recovered map outputs.  The
        session owns the driver from here (session.shutdown tears it
        down)."""
        with self._lc_cond:
            if self._cluster_handle is not None \
                    and self._cluster_handle is not driver:
                raise RuntimeError(
                    "session already has a cluster attached")
            self._cluster_handle = driver
        return self

    def active_queries(self) -> list[str]:
        """query_ids currently admitted and running."""
        with self._lc_cond:
            return sorted(self._live)

    def cancel(self, query_id: str) -> bool:
        """Request cooperative cancellation of one in-flight query.
        Returns True when the request transitioned it to CANCELLED
        (False: unknown id or already terminal).  The run itself
        unwinds at its next cancellation point, raising
        QueryCancelled from ``collect``."""
        with self._lc_cond:
            lc = self._live.get(query_id)
        return lc.cancel("session.cancel") if lc is not None else False

    def cancel_all(self) -> int:
        """Cancel every in-flight query; returns how many transitioned."""
        with self._lc_cond:
            lcs = list(self._live.values())
        return sum(1 for lc in lcs if lc.cancel("session.cancel_all"))

    def shutdown(self, drain: bool = True,
                 timeout: float | None = None) -> None:
        """Graceful session shutdown: stop admission (new queries get
        QueryRejected), then ``drain=True`` waits for in-flight queries
        to finish — cancelling whatever is still running once
        ``timeout`` (seconds, None = wait forever) expires — while
        ``drain=False`` cancels them immediately.  Each query's unwind
        closes its own ExecCtx: shuffle TCP servers stop, catalogs
        close (spill files unlinked), the DeviceSemaphore is released
        in full."""
        # control loop first: a controller actuating knobs while the
        # session tears them down would race, and stop() restores every
        # adapted knob to its static conf value (no thread survives
        # shutdown: tests/test_control.py::
        # test_loop_thread_lifecycle_and_no_leak)
        control, self._control = self._control, None
        if control is not None:
            control.stop()
        self._admission_controller().begin_shutdown()
        if not drain:
            self.cancel_all()
            timeout = None
        if not self._wait_idle(timeout):
            # drain window expired: cancel the stragglers, then give
            # their cooperative checkpoints a bounded grace to unwind
            self.cancel_all()
            self._wait_idle(10.0)
        with self._lc_cond:
            cluster, self._cluster_handle = self._cluster_handle, None
        if cluster is not None:
            cluster.shutdown()
        http, self._http = self._http, None
        if http is not None:
            # torn down LAST so /healthz reports "draining" throughout
            http.close()

    def _wait_idle(self, timeout: float | None) -> bool:
        import time as _time
        deadline = None if timeout is None \
            else _time.monotonic() + timeout
        with self._lc_cond:
            while self._live:
                rem = None if deadline is None \
                    else deadline - _time.monotonic()
                if rem is not None and rem <= 0:
                    return False
                self._lc_cond.wait(rem if rem is not None else 1.0)
        return True

    def _routed_conf(self, logical) -> TpuConf:
        """The conf this plan should run under: the session conf, plus
        the control plane's history-learned routing overrides (mesh
        shape, express lane) when the controller is on and has enough
        samples for this plan's fingerprint.  With control disabled
        this IS ``self.conf`` — same object, zero divergence."""
        control = self._control
        if control is None or logical is None:
            return self.conf
        overrides = control.route_for(logical)
        if not overrides:
            return self.conf
        conf = self.conf
        for k, v in overrides.items():
            conf = conf.set(k, v)
        return conf

    def _run_query(self, plan,
                   timeout: float | None = None, logical=None,
                   tenant: str | None = None,
                   conf: "TpuConf | None" = None) -> list[tuple]:
        """Planning -> result-cache lookup -> admission -> lifecycle
        registration -> execution -> cleanup for one collect.
        ``plan()`` returns ``(exec node, backend)``: it runs here, under
        the ``query.plan`` span, so that the query's id and its record
        (exec/lifecycle.py ``open_record``/``seal_record``) exist from
        the collect's entry.  Spans: ``query`` > ``query.plan``,
        ``query.execute`` (the executor, which also holds the result's
        ``query.fetch`` spans, one per result batch).  The lifecycle is
        registered in ``_live`` BEFORE admission so a cancel reaches a
        query still waiting in the queue (releasing its queue slot;
        counted once as cancelled, never rejected).  The ExecCtx cache
        is pre-seeded with the lifecycle handle (and its query_id) so
        every cancellation point down the stack observes the session's
        cancel/deadline.  A result-cache hit (exec/result_cache.py)
        serves rows without admission or an ExecCtx — zero executor
        dispatches — and a concurrent identical query coalesces onto
        the one in-flight run."""
        import uuid
        from spark_rapids_tpu.exec.lifecycle import (QueryLifecycle,
                                                     QueryLifecycleError)
        from spark_rapids_tpu.obs.registry import get_registry
        if conf is None:
            conf = self.conf
        reg = get_registry()
        admission = self._admission_controller()
        query_id = uuid.uuid4().hex[:16]
        lc = QueryLifecycle.from_conf(query_id, conf,
                                      timeout=timeout, tenant=tenant)
        lc.open_record()
        node = backend = None       # set by plan(), inside the query span
        # the control plane's per-tenant SLOs are end-to-end (queue
        # wait + wall): only control-enabled sessions emit the extra
        # e2e histogram, so a static engine's counter set is untouched
        lc.observe_e2e = self._control is not None
        with self._lc_cond:
            self._live[query_id] = lc
        admitted = False

        def run() -> list[tuple]:
            nonlocal admitted
            admission.admit(query_id, tenant=lc.tenant, lifecycle=lc)
            admitted = True
            lc.start()
            try:
                with reg.span("query.execute", query_id=query_id,
                              parent="query"):
                    out = self._execute_collect(node, backend, query_id,
                                                lc, conf)
            except QueryLifecycleError:
                raise
            except BaseException:
                if not lc.fail():
                    # already terminal: the cancel/deadline unwound
                    # concurrent workers in arbitrary order and a
                    # secondary error won the race to surface — raise
                    # the lifecycle error (the real cause), chaining
                    # the loser as context
                    lc.check()
                raise
            lc.finish()
            return out

        # raw-settings gated: with history.dir unset (the default)
        # obs.history is never imported
        hist_dir = self.conf.settings.get("spark.rapids.obs.history.dir")
        hist_before = None
        submitted = None
        if hist_dir:
            import time as _time
            hist_before = reg.snapshot()
            submitted = _time.time()
        # raw-settings gated like trace/history: with profile.enabled
        # unset (the default) obs.profile/obs.metering are never
        # imported
        prof_on = str(conf.settings.get(
            "spark.rapids.obs.profile.enabled", "")).lower() \
            in ("true", "1", "yes")
        if prof_on and hist_before is None:
            hist_before = reg.snapshot()
        if prof_on:
            # the meter's registry baseline must predate THIS query's
            # counter movement (queries_executed incs at executor entry,
            # before the first profiler would lazily build the meter) or
            # conservation undercounts the first profiled run
            from spark_rapids_tpu.obs.metering import get_meter
            get_meter()
        if (hist_dir or prof_on) and logical is not None:
            # stash the plan fingerprint on the lifecycle NOW so the
            # live /queries view can map this run to its history
            # medians (percent-complete / ETA) while it executes
            # enginelint: disable=RL001 (fingerprinting is best-effort observability; an unfingerprintable plan still runs)
            try:
                from spark_rapids_tpu.exec.compile_cache import fingerprint
                from spark_rapids_tpu.exec.result_cache import _plan_part
                try:
                    lc.plan_fingerprint = fingerprint(_plan_part(logical))
                # enginelint: disable=RL001 (repr fallback mirrors _record_history's fingerprint path)
                except Exception:
                    lc.plan_fingerprint = fingerprint(repr(logical))
            # enginelint: disable=RL001 (fingerprinting is routing metadata; a plan that defeats it still runs)
            except Exception:
                pass
        err: BaseException | None = None
        try:
            with reg.span("query", query_id=query_id):
                with reg.span("query.plan", query_id=query_id,
                              parent="query"):
                    node, backend = plan()
                rcache = None
                key = None
                if logical is not None and not admission.shutting_down:
                    from spark_rapids_tpu.exec.result_cache import \
                        maybe_cache
                    rcache = maybe_cache(conf)
                    if rcache is not None:
                        # backend is part of the key: the host oracle
                        # must never be served a device run's rows
                        # (differential testing would silently compare
                        # a cache to itself).  The ROUTED conf is part
                        # of the key too — an express-routed run and a
                        # full-mesh run of the same logical plan are
                        # different computations.
                        key = rcache.result_key(logical, backend, conf)
                if key is None:
                    out = run()
                else:
                    out = rcache.get_or_compute(
                        key, run, lifecycle=lc, faults=admission.faults)
                    lc.finish()
            return out
        except BaseException as e:
            err = e
            raise
        finally:
            # the record closes after the ``query`` span, so the span
            # is in it
            lc.seal_record(err)
            metered = None
            if prof_on:
                metered = self._meter_query(lc, hist_before, conf)
            if hist_dir:
                self._record_history(lc, node, logical, err,
                                     hist_before, submitted, conf,
                                     metered=metered)
            with self._lc_cond:
                self._live.pop(query_id, None)
                self._lc_cond.notify_all()
            if admitted:
                admission.release(tenant=lc.tenant)

    def _meter_query(self, lc, before: "dict | None",
                     conf: "TpuConf | None") -> "dict | None":
        """Charge one finished run to its tenant + fingerprint
        (obs/metering.py): device/HBM usage from the query's own
        profiler, byte metrics from its registry delta.  Returns the
        usage dict for the history entry, or None when the run never
        built a profiler (cache hit, pre-admission failure).  Metering
        must never fail the query."""
        # enginelint: disable=RL001 (metering is best-effort accounting; the query's own outcome already propagated)
        try:
            import time as _time
            ctx = getattr(lc, "ctx", None)
            prof = None if ctx is None else ctx.cache.get("profiler")
            if prof is None:
                return None
            from spark_rapids_tpu.obs.metering import get_meter
            from spark_rapids_tpu.obs.profile import get_store
            from spark_rapids_tpu.obs.registry import get_registry
            usage = prof.usage()
            counters = {} if before is None else \
                get_registry().delta(before).get("counters", {})
            usage["shuffle_bytes"] = float(
                counters.get("shuffle.fetch.bytes", 0.0))
            usage["scan_bytes"] = float(counters.get("scan.bytes", 0.0))
            usage["compile_seconds"] = float(
                counters.get("compile_wall_s", 0.0))
            fp = getattr(lc, "plan_fingerprint", None)
            get_meter().charge(lc.tenant or "default", fp, usage)
            if fp:
                started = lc._started_at
                wall = None if started is None \
                    else _time.monotonic() - started
                get_store().note(fp, prof.operators(), wall_s=wall)
            return usage
        # enginelint: disable=RL001 (metering must never fail a finished query; unmetered beats broken)
        except Exception:
            return None

    def _record_history(self, lc, node, logical, err,
                        before: dict, submitted: float,
                        conf: "TpuConf | None" = None,
                        metered: "dict | None" = None) -> None:
        """Append this query's terminal record to the history log
        (obs/history.py).  Forensics must never fail the query: any
        error here is swallowed after best-effort assembly."""
        # enginelint: disable=RL001 (history is best-effort forensics)
        try:
            import time as _time
            from spark_rapids_tpu.obs.history import history_log
            from spark_rapids_tpu.obs.registry import get_registry
            log = history_log(self.conf)
            if log is None:
                return
            # the sealed per-query record names the state (REJECTED /
            # FAILED for a query stopped before any transition)
            state = lc.seal_record(err)["state"]
            started = lc._started_at
            if conf is None:
                conf = self.conf
            delta = get_registry().delta(before)
            counters = delta.get("counters", {})
            entry: dict = {
                "kind": "history", "version": 1,
                "query_id": lc.query_id,
                "tenant": lc.tenant,
                "state": state,
                "submitted_unix_s": submitted,
                "wall_s": (None if started is None
                           else round(_time.monotonic() - started, 6)),
                "registry_delta": {
                    "counters": counters,
                    "histograms": delta.get("histograms", {}),
                },
                "executed": bool(getattr(lc, "executed", False)),
                "served_from_cache": (err is None
                                      and not getattr(lc, "executed",
                                                      False)),
                "decisions": {k: v for k, v in counters.items()
                              if k.startswith(("aqe", "result_cache",
                                               "fragment_cache",
                                               "compile_count"))},
                # the mesh shape this run executed under (the ROUTED
                # conf when control routing rewrote it) — what the
                # HistoryIndex learns per-shape walls from
                "mesh_devices": max(1, int(conf.settings.get(
                    "spark.rapids.tpu.mesh.deviceCount", 0) or 0)),
                "control_route": conf is not self.conf,
            }
            if getattr(lc, "plan_fingerprint", None):
                entry["plan_fingerprint"] = lc.plan_fingerprint
            elif logical is not None:
                from spark_rapids_tpu.exec.compile_cache import fingerprint
                from spark_rapids_tpu.exec.result_cache import _plan_part
                try:
                    entry["plan_fingerprint"] = \
                        fingerprint(_plan_part(logical))
                # enginelint: disable=RL001 (fingerprint fallback only; the query's own error already propagated)
                except Exception:
                    # in-memory scans have no stable scan_fingerprint;
                    # the structural repr is identity enough for diffing
                    entry["plan_fingerprint"] = fingerprint(repr(logical))
            if metered is not None:
                entry["metering"] = {
                    k: (round(v, 6) if isinstance(v, float) else v)
                    for k, v in metered.items()}
            ctx = getattr(lc, "ctx", None)
            if ctx is not None:
                # rows actually emitted, summed across operators — the
                # denominator the live /queries progress view compares
                # its in-flight sum against (HistoryIndex median_rows)
                try:
                    entry["rows_processed"] = int(sum(
                        m.values.get("numOutputRows", 0.0)
                        for m in list(ctx.metrics.values())))
                # enginelint: disable=RL001 (metrics race is benign; the entry ships without a row count)
                except Exception:
                    pass
                prof = ctx.cache.get("profiler")
                if prof is not None:
                    entry["profile"] = prof.history_blob()
                try:
                    from spark_rapids_tpu.plan.overrides import \
                        explain_analyze
                    entry["plan_analyzed"] = explain_analyze(node, ctx)
                # enginelint: disable=RL001 (plan render is best-effort; the entry ships without it)
                except Exception:
                    pass  # a plan that failed mid-build may not render
            if err is not None:
                entry["error"] = {
                    "type": type(err).__name__,
                    "message": str(err)[:4096],
                    "terminal": bool(getattr(err, "terminal", False)),
                }
            log.append(entry)
            control = self._control
            if control is not None:
                # in-process fast path: index the entry now instead of
                # waiting for the file-watch refresh at tick cadence
                control.note_history_entry(entry)
        # enginelint: disable=RL001 (history recording must never mask the query's own outcome; the real error already propagated to the caller)
        except Exception:
            pass

    def _execute_collect(self, node, backend: str, query_id: str, lc,
                         conf: "TpuConf | None" = None):
        # the executor-entry chokepoint: a result-cache hit never gets
        # here, so a zero delta on this counter across a repeated query
        # PROVES the executor was untouched (CI serving gate)
        from spark_rapids_tpu.obs.registry import get_registry
        get_registry().inc("queries_executed")
        lc.executed = True  # vs a result-cache hit, which never gets here
        if conf is None:
            conf = self.conf

        def make_ctx(be: str) -> ExecCtx:
            ctx = ExecCtx(backend=be, conf=conf)
            lc.ctx = ctx  # history records explain_analyze post-run
            ctx.cache["query_id"] = query_id
            ctx.cache["lifecycle"] = lc
            if be == "device":
                # the host backend is the differential ORACLE: it must
                # never see the cluster, or cluster bugs would cancel
                # out of the comparison
                cluster = self._cluster()
                if cluster is not None:
                    ctx.cache["cluster"] = cluster
            return ctx

        if backend != "device":
            return collect_host(node, conf, ctx=make_ctx("host"))
        from spark_rapids_tpu.conf import FALLBACK_ON_DEVICE_ERROR
        if not conf.get(FALLBACK_ON_DEVICE_ERROR):
            return collect_device(node, conf, ctx=make_ctx("device"))
        try:
            return collect_device(node, conf, ctx=make_ctx("device"))
        except Exception as e:  # noqa: BLE001 - opt-in resilience path
            # a cancelled/deadline-exceeded (or otherwise terminal)
            # query must NOT be resurrected on the host engine
            if getattr(e, "terminal", False):
                raise
            # opt-in runtime resilience beyond the reference (which only
            # falls back at PLAN time): rerun the whole query on the
            # host oracle with a loud warning. Off by default — masking
            # device bugs silently would defeat differential testing.
            import warnings
            warnings.warn(
                f"device execution failed ({type(e).__name__}: {e}); "
                "re-running on the host engine per "
                "spark.rapids.sql.fallbackOnDeviceError", RuntimeWarning)
            return collect_host(node, conf, ctx=make_ctx("host"))

    # -- sources -------------------------------------------------------
    def read_parquet(self, path, columns=None, **kw) -> "DataFrame":
        from spark_rapids_tpu.io import ParquetScanExec
        return DataFrame(self, L.Scan(ParquetScanExec(path, columns=columns,
                                                      **kw)))

    def read_orc(self, path, columns=None, **kw) -> "DataFrame":
        from spark_rapids_tpu.io import OrcScanExec
        return DataFrame(self, L.Scan(OrcScanExec(path, columns=columns,
                                                  **kw)))

    def read_csv(self, path, schema: T.Schema | None = None,
                 **kw) -> "DataFrame":
        from spark_rapids_tpu.io import CsvScanExec
        return DataFrame(self, L.Scan(CsvScanExec(path, schema=schema, **kw)))

    def from_pydict(self, data: dict, schema: T.Schema,
                    partitions: int = 1,
                    rows_per_batch: int | None = None) -> "DataFrame":
        from spark_rapids_tpu.exec import LocalScanExec
        return DataFrame(self, L.Scan(LocalScanExec.from_pydict(
            data, schema, partitions, rows_per_batch)))

    def from_arrow(self, table) -> "DataFrame":
        from spark_rapids_tpu.exec import LocalScanExec
        from spark_rapids_tpu.host.batch import HostBatch
        import pyarrow as pa
        if isinstance(table, pa.Table):
            batches = [HostBatch.from_arrow(rb)
                       for rb in table.to_batches()]
        else:
            batches = [HostBatch.from_arrow(table)]
        schema = T.Schema.from_arrow(
            table.schema if hasattr(table, "schema") else table.schema)
        return DataFrame(self, L.Scan(LocalScanExec(batches, schema)))

    def range(self, start: int, end: int | None = None, step: int = 1,
              partitions: int = 1) -> "DataFrame":
        from spark_rapids_tpu.exec import RangeExec
        if end is None:
            start, end = 0, start
        return DataFrame(self, L.Scan(RangeExec(start, end, step,
                                                partitions)))

    def set(self, key: str, value) -> "TpuSession":
        self.conf = self.conf.set(key, value)
        return self


class DataFrame:
    def __init__(self, session: TpuSession, plan: L.LogicalPlan):
        self._s = session
        self._plan = plan

    # -- schema --------------------------------------------------------
    @property
    def schema(self) -> T.Schema:
        return self._planned().exec_node.output_schema

    @property
    def columns(self) -> list[str]:
        return self.schema.names

    # -- transformations ----------------------------------------------
    def select(self, *exprs) -> "DataFrame":
        resolved = [self._col_or_expr(e) for e in exprs]
        return DataFrame(self._s, L.Project(resolved, self._plan))

    def where(self, condition: Expression) -> "DataFrame":
        return DataFrame(self._s, L.Filter(condition, self._plan))

    filter = where

    def with_column(self, name: str, expr: Expression) -> "DataFrame":
        # replacing an existing column keeps its position (Spark
        # semantics; round-1 advisor finding: the old code moved it last)
        names = self._schema_names()
        if name in names:
            exprs = [expr.alias(name) if n == name else col(n)
                     for n in names]
        else:
            exprs = [col(n) for n in names] + [expr.alias(name)]
        return self.select(*exprs)

    def group_by(self, *keys) -> "GroupedData":
        return GroupedData(self, [self._col_or_expr(k) for k in keys])

    def rollup(self, *keys) -> "GroupedData":
        """GROUP BY ROLLUP: grouping sets = every key-prefix down to the
        grand total (reference GpuExpandExec-backed rollup)."""
        ks = [self._col_or_expr(k) for k in keys]
        sets = [set(range(i)) for i in range(len(ks), -1, -1)]
        return GroupedData(self, ks, grouping_sets=sets)

    def cube(self, *keys) -> "GroupedData":
        """GROUP BY CUBE: all 2^n grouping sets."""
        from itertools import combinations
        ks = [self._col_or_expr(k) for k in keys]
        n = len(ks)
        sets = [set(c) for r in range(n, -1, -1)
                for c in combinations(range(n), r)]
        return GroupedData(self, ks, grouping_sets=sets)

    def grouping_sets(self, keys, sets) -> "GroupedData":
        """Explicit GROUPING SETS; ``sets`` lists per-set key names (or
        indices into ``keys``)."""
        ks = [self._col_or_expr(k) for k in keys]
        names = [output_name(k) for k in ks]
        idx_sets = []
        for s in sets:
            idx = set()
            for item in s:
                idx.add(item if isinstance(item, int) else
                        names.index(item))
            idx_sets.append(idx)
        return GroupedData(self, ks, grouping_sets=idx_sets)

    def agg(self, *aggs) -> "DataFrame":
        return GroupedData(self, []).agg(*aggs)

    def join(self, other: "DataFrame", on=None, how: str = "inner",
             condition: Expression | None = None) -> "DataFrame":
        left_on, right_on = [], []
        if on is not None:
            if isinstance(on, str):
                on = [on]
            for o in on:
                if isinstance(o, str):
                    left_on.append(col(o))
                    right_on.append(col(o))
                else:
                    l, r = o
                    left_on.append(col(l) if isinstance(l, str) else l)
                    right_on.append(col(r) if isinstance(r, str) else r)
        if how == "cross" or not left_on:
            return DataFrame(self._s, L.Join(
                self._plan, other._plan, "cross", [], [], condition))
        return DataFrame(self._s, L.Join(self._plan, other._plan, how,
                                         left_on, right_on, condition))

    def explode(self, expr, output_name: str = "col", pos: bool = False,
                outer: bool = False) -> "DataFrame":
        """explode(array_col): one output row per element, child columns
        repeated; ``pos`` adds the element index, ``outer`` keeps
        null/empty-array rows (reference GpuGenerateExec explode over
        LIST columns)."""
        from spark_rapids_tpu.exec.generate import Explode
        gen = Explode(self._col_or_expr(expr))
        names = (["pos", output_name] if pos else [output_name])
        return DataFrame(self._s, L.Generate(gen, self._plan, outer=outer,
                                             pos=pos, output_names=names))

    def explode_split(self, expr, delimiter: str, output_name: str = "col",
                      pos: bool = False, outer: bool = False) -> "DataFrame":
        """explode(split(expr, delimiter)): one output row per piece, child
        columns repeated; ``pos`` adds the piece index, ``outer`` keeps
        null-input rows (reference GpuGenerateExec explode/posexplode)."""
        from spark_rapids_tpu.exec.generate import SplitExplode
        gen = SplitExplode(self._col_or_expr(expr), delimiter)
        names = (["pos", output_name] if pos else [output_name])
        return DataFrame(self._s, L.Generate(gen, self._plan, outer=outer,
                                             pos=pos, output_names=names))

    def map_in_pandas(self, fn, schema: T.Schema) -> "DataFrame":
        """``fn`` receives an iterator of pandas DataFrames (one
        partition's batches) and yields DataFrames conforming to
        ``schema``; output row count is unconstrained (Spark
        mapInPandas; reference GpuMapInPandasExec)."""
        return DataFrame(self._s, L.MapInPandas(fn, schema, self._plan))

    def order_by(self, *orders) -> "DataFrame":
        return DataFrame(self._s, L.Sort(list(orders), self._plan))

    sort = order_by

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(self._s, L.Limit(n, self._plan))

    def distinct(self) -> "DataFrame":
        """Deduplicate rows — a group-by on every column, so nulls and
        NaNs compare equal the way Spark's set operations require."""
        return self.group_by(*self.columns).agg()

    def intersect(self, other: "DataFrame") -> "DataFrame":
        """Set intersection (distinct rows present in BOTH inputs).

        Implemented as union + marker max + group-by on all columns
        instead of a join: group-by keys are null-safe, matching Spark's
        INTERSECT semantics where NULL == NULL (a plain join would drop
        null-keyed rows)."""
        return self._set_op(other, want_a=True, want_b=True)

    def subtract(self, other: "DataFrame") -> "DataFrame":
        """Set difference (distinct rows of self not in other); Spark's
        ``EXCEPT [DISTINCT]`` / ``DataFrame.exceptAll``-less cousin."""
        return self._set_op(other, want_a=True, want_b=False)

    def _set_op(self, other: "DataFrame", want_a: bool,
                want_b: bool) -> "DataFrame":
        from spark_rapids_tpu.expr.aggregates import Max
        names = self.columns
        if len(names) != len(other.columns):
            raise ValueError(
                f"set operation arity mismatch: {len(names)} vs "
                f"{len(other.columns)} columns")

        def uniq(stem: str) -> str:
            nm, i = stem, 0
            while nm in names:
                nm, i = f"{stem}{i}", i + 1
            return nm

        ma, mb = uniq("_sop_a"), uniq("_sop_b")
        ia, ib = uniq("_sop_ia"), uniq("_sop_ib")
        a = self.select(*[col(n) for n in names],
                        lit(1).alias(ma), lit(0).alias(mb))
        b = other.select(*[col(bn).alias(an)
                           for an, bn in zip(names, other.columns)],
                         lit(0).alias(ma), lit(1).alias(mb))
        g = a.union(b).group_by(*names).agg(
            Max(col(ma)).alias(ia), Max(col(mb)).alias(ib))
        cond = (col(ia) == lit(1))
        cond = cond & ((col(ib) == lit(1)) if want_b
                       else (col(ib) == lit(0)))
        return g.where(cond).select(*[col(n) for n in names])

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(self._s, L.Union([self._plan, other._plan]))

    def cache(self) -> "DataFrame":
        """Materialized columnar caching (reference
        ParquetCachedBatchSerializer, SURVEY §5.4): the plan runs once
        on its tagged backend into codec-compressed Arrow blobs; every
        later execution scans the cache.  Lazy: materializes on first
        use.  Call ``unpersist()`` on the RETURNED frame to free it."""
        from spark_rapids_tpu.exec.cache_exec import CachedScanExec
        ov, meta = self._overridden(quiet=True)
        cached = CachedScanExec(meta.exec_node, meta.backend, self._s.conf)
        return DataFrame(self._s, L.Scan(cached))

    def unpersist(self) -> "DataFrame":
        """Free this frame's cache blobs (no-op unless the plan root is
        a cache scan)."""
        from spark_rapids_tpu.exec.cache_exec import CachedScanExec
        node = getattr(self._plan, "exec_node", None)
        if isinstance(node, CachedScanExec):
            node.unpersist()
        return self

    def repartition(self, num_partitions: int, *keys) -> "DataFrame":
        return DataFrame(self._s, L.Repartition(
            num_partitions, [self._col_or_expr(k) for k in keys],
            self._plan))

    # -- actions -------------------------------------------------------
    def collect(self, timeout: float | None = None,
                tenant: str | None = None) -> list[tuple]:
        """Run the query and return every row as a python tuple.

        ``timeout`` (seconds) sets a per-call deadline, combined with
        ``spark.rapids.sql.queryTimeout`` (the tighter wins): past it,
        the run unwinds at its next cancellation point and raises
        QueryDeadlineExceeded.  The run is registered with the session
        while in flight, so ``session.cancel(query_id)`` /
        ``cancel_all()`` raise QueryCancelled from here, and admission
        control (``spark.rapids.sql.admission.*``) may make this call
        wait its turn or raise QueryRejected under overload.

        ``tenant`` names the weighted-fair admission tenant this query
        runs under (default: ``spark.rapids.sql.tenant``).  A repeated
        identical query over unchanged inputs may be served from the
        process-wide result cache (``spark.rapids.sql.resultCache.*``)
        without touching the executor."""
        # control-plane routing: with the controller on, a repeated
        # plan may run under a history-learned conf (express lane /
        # best mesh shape); otherwise this is self._s.conf unchanged
        conf = self._s._routed_conf(self._plan)

        def plan():
            ov, meta = self._overridden(conf=conf)
            return meta.exec_node, \
                "device" if meta.backend == "device" else "host"
        return self._s._run_query(plan, timeout=timeout,
                                  logical=self._plan, tenant=tenant,
                                  conf=conf)

    def to_arrow(self):
        import pyarrow as pa
        ov, meta = self._overridden()
        backend = meta.backend
        ctx = ExecCtx(backend=backend, conf=self._s.conf)
        from spark_rapids_tpu.exec.core import device_to_host
        rbs = []
        for b in meta.exec_node.execute(ctx):
            hb = device_to_host(b) if backend == "device" else b
            rbs.append(hb.to_arrow())
        if not rbs:
            return pa.table([], schema=self.schema.to_arrow())
        return pa.Table.from_batches(rbs)

    def count(self) -> int:
        from spark_rapids_tpu.expr.aggregates import CountStar
        rows = self.agg(CountStar().alias("count")).collect()
        return rows[0][0]

    # -- ML interop (reference ColumnarRdd.scala:42-49) ----------------
    def device_batches(self):
        """Iterate device ColumnBatches without a final D2H — the
        ColumnarRdd analog for ML consumers (interop.py)."""
        from spark_rapids_tpu.interop import device_batches
        return device_batches(self)

    def to_jax(self, include_strings: bool = False) -> dict:
        """{name: (jax values, validity)} of the query result."""
        from spark_rapids_tpu.interop import to_jax
        return to_jax(self, include_strings=include_strings)

    def to_torch(self) -> dict:
        """{name: torch.Tensor} (CPU) of the numeric result columns."""
        from spark_rapids_tpu.interop import to_torch
        return to_torch(self)

    def explain(self) -> str:
        ov, meta = self._overridden(quiet=True)
        return ov.explain(meta)

    def explain_analyze(self) -> str:
        """EXECUTE the query and render the plan annotated with runtime
        metrics: per-node time/batches/rows plus spill, retry, and
        recovery counters recorded during the run (EXPLAIN ANALYZE; the
        reference surfaces the same GpuExec metrics in the SQL UI)."""
        from spark_rapids_tpu.plan.overrides import explain_analyze
        ov, meta = self._overridden(quiet=True)
        with ExecCtx(backend=meta.backend, conf=self._s.conf) as ctx:
            for _ in meta.exec_node.execute(ctx):
                pass
            return explain_analyze(meta.exec_node, ctx)

    def write_parquet(self, path: str, partition_by=None, **kw):
        """Directory write (Spark protocol).  ``partition_by`` enables
        hive-style dynamic-partition output; returns WriteStats.

        With ``spark.rapids.io.write.transactional.enabled`` (the
        default) the write runs as a planned :class:`CreateDataWriteExec`
        job under the two-phase task-attempt commit protocol — through
        the cluster runtime when one is attached — and the committed
        directory carries ``_MANIFEST.json`` + ``_SUCCESS``.  Off =
        the legacy direct in-place writer (no exactly-once guarantee
        under retries)."""
        from spark_rapids_tpu.io.writer import (WRITE_TRANSACTIONAL,
                                                WriteStats)
        if isinstance(partition_by, str):
            partition_by = [partition_by]
        if not self._s.conf.get(WRITE_TRANSACTIONAL):
            from spark_rapids_tpu.io import write_parquet
            ov, meta = self._overridden()
            stats = WriteStats()
            with ExecCtx(backend=meta.backend, conf=self._s.conf) as ctx:
                write_parquet(meta.exec_node, path, ctx=ctx,
                              partition_by=partition_by, stats=stats, **kw)
            return stats
        wdf = DataFrame(self._s, L.DataWrite(
            "parquet", path, list(partition_by or []), dict(kw),
            self._plan))
        ov, meta = wdf._overridden()
        # logical=None: a side-effecting job must execute — it never
        # serves from (or populates) the result cache
        self._s._run_query(lambda: (meta.exec_node, meta.backend),
                           logical=None)
        return meta.exec_node.stats

    # -- internals -----------------------------------------------------
    def _schema_names(self) -> list[str]:
        return self.schema.names

    def _col_or_expr(self, e):
        return col(e) if isinstance(e, str) else e

    def _planned(self, conf: "TpuConf | None" = None) -> PlannedNode:
        from spark_rapids_tpu.plan.maps import decompose_maps
        conf = self._s.conf if conf is None else conf
        return lower(decompose_maps(self._plan, conf), conf)

    def _overridden(self, quiet: bool = False,
                    conf: "TpuConf | None" = None):
        conf = self._s.conf if conf is None else conf
        meta = self._planned(conf=conf)
        ov = TpuOverrides(conf)
        ov.prepare(meta, explain=not quiet)
        return ov, meta


class GroupedData:
    def __init__(self, df: DataFrame, keys: list, grouping_sets=None):
        self._df = df
        self._keys = keys
        self._sets = grouping_sets  # list[set[int]] of ACTIVE key indices

    def _key_columns(self, what: str) -> list:
        """The grouped pandas ops hand ``fn`` the CHILD's columns, so
        their keys must be plain column references (Spark's
        applyInPandas has the same restriction in practice)."""
        from spark_rapids_tpu.expr.core import UnresolvedAttribute
        for k in self._keys:
            if not isinstance(k, UnresolvedAttribute):
                raise NotImplementedError(
                    f"{what} requires plain column keys, got {k!r}")
        return list(self._keys)

    def apply_in_pandas(self, fn, schema: T.Schema) -> DataFrame:
        """``fn`` receives each group as one pandas DataFrame (all child
        columns) and returns a DataFrame conforming to ``schema`` (Spark
        groupBy().applyInPandas; reference
        GpuFlatMapGroupsInPandasExec)."""
        if self._sets is not None:
            raise NotImplementedError(
                "apply_in_pandas with grouping sets is not supported")
        return DataFrame(self._df._s, L.FlatMapGroupsInPandas(
            self._key_columns("apply_in_pandas"), fn, schema,
            self._df._plan))

    def cogroup(self, other: "GroupedData") -> "CoGroupedData":
        """Pair two grouped frames by key for a joint pandas apply
        (Spark cogroup; reference GpuFlatMapCoGroupsInPandasExec)."""
        return CoGroupedData(self, other)

    def agg(self, *aggs) -> DataFrame:
        from spark_rapids_tpu.expr.aggregates import CountDistinct
        from spark_rapids_tpu.exec.python_exec import PandasAggUDF
        inners = [(a.children[0] if isinstance(a, Alias) else a)
                  for a in aggs]
        if any(isinstance(i, PandasAggUDF) for i in inners):
            if not all(isinstance(i, PandasAggUDF) for i in inners):
                raise NotImplementedError(
                    "mixing pandas_agg_udf with built-in aggregates in "
                    "one agg() is not supported")
            if self._sets is not None:
                raise NotImplementedError(
                    "pandas_agg_udf with grouping sets is not supported")
            udfs = [(output_name(a), i) for a, i in zip(aggs, inners)]
            return DataFrame(self._df._s, L.AggregateInPandas(
                self._key_columns("agg(pandas_agg_udf)"), udfs,
                self._df._plan))
        if any(isinstance(i, CountDistinct) for i in inners):
            return self._agg_with_distinct(list(aggs))
        if self._sets is None:
            exprs = list(self._keys) + list(aggs)
            return DataFrame(self._df._s, L.Aggregate(
                list(self._keys), exprs, self._df._plan))
        return self._agg_grouping_sets(list(aggs))

    def _agg_with_distinct(self, aggs: list) -> DataFrame:
        """Rewrite count(DISTINCT ...) into dedupe-then-count plans
        (Spark plans the same shape via Expand + two-phase aggregation;
        reference distinct-workaround projections, aggregate.scala).

        Supported: any number of CountDistinct aggs (a) with no group
        keys — each becomes a 1-row frame combined by cross join — or
        (b) grouped WITHOUT plain aggs alongside (dedupe on keys+value,
        then count per key).  Grouped mixing of distinct and plain aggs
        would need a null-safe key join; not yet implemented."""
        from spark_rapids_tpu.expr.aggregates import Count, CountDistinct
        from spark_rapids_tpu.expr.predicates import IsNotNull
        if self._sets is not None:
            raise NotImplementedError(
                "count(distinct) with grouping sets is not supported")
        plain, cds = [], []
        for a in aggs:
            inner = a.children[0] if isinstance(a, Alias) else a
            if isinstance(inner, CountDistinct):
                cds.append((output_name(a), inner))
            else:
                plain.append(a)
        base = self._df
        key_names = [output_name(k) for k in self._keys]

        def distinct_count_frame(name: str, cd: CountDistinct,
                                 keys: list) -> DataFrame:
            tmps = [f"_cdv_{name}_{j}" for j in range(len(cd.children))]
            dd = GroupedData(base, list(keys) + [
                Alias(c, t) for c, t in zip(cd.children, tmps)]).agg()
            # count the deduped tuples whose components are ALL non-null
            # WITHOUT filtering rows out first: a group whose values are
            # all null must still appear with count 0 (Spark semantics)
            if len(tmps) == 1:
                cnt_in = col(tmps[0])
            else:
                from spark_rapids_tpu.expr.conditional import If
                cond = None
                for t in tmps:
                    p = IsNotNull(col(t))
                    cond = p if cond is None else cond & p
                cnt_in = If(cond, lit(1),
                            Literal(None, T.LongType()))
            knames = [output_name(k) for k in keys]
            return GroupedData(dd, [col(k) for k in knames]).agg(
                Count(cnt_in).alias(name))

        if not key_names:
            frames = []
            if plain:
                frames.append(GroupedData(base, []).agg(*plain))
            frames.extend(distinct_count_frame(n, cd, []) for n, cd in cds)
            cur = frames[0]
            for f in frames[1:]:
                cur = cur.join(f, how="cross")
            order = [output_name(a) for a in aggs]
            return cur.select(*[col(n) for n in order])
        if plain:
            raise NotImplementedError(
                "grouped count(distinct) mixed with other aggregates "
                "needs a null-safe key join; split into separate "
                "aggregations and join explicitly")
        if len(cds) > 1:
            raise NotImplementedError(
                "one count(distinct) per grouped aggregation")
        name, cd = cds[0]
        return distinct_count_frame(name, cd, list(self._keys))

    def _agg_grouping_sets(self, aggs: list) -> DataFrame:
        """Rollup/cube/grouping-sets: Expand with nulled-out key columns +
        a spark_grouping_id literal per set, then a plain group-by over
        (keys..., spark_grouping_id) so rollup-nulls never merge with
        data-nulls (reference GpuExpandExec + Spark's Expand planning).

        When every aggregate is re-aggregable (sum/count/min/max/avg),
        the input is FIRST aggregated at full key granularity and the
        Expand runs over the (much smaller) group list, re-merging per
        set — N projections over |groups| rows instead of N x |input|
        (the classic rollup-as-reaggregation optimization; the
        reference's expand feeds the same partial-merge machinery,
        aggregate.scala:348-560)."""
        from spark_rapids_tpu.expr.core import Literal, UnresolvedAttribute
        user_names = [output_name(k) for k in self._keys]
        child_cols = self._df.columns
        pre_exprs = [col(n) for n in child_cols]
        key_names = []
        for k, name in zip(self._keys, user_names):
            inner = k.children[0] if isinstance(k, Alias) else k
            if isinstance(inner, UnresolvedAttribute) and \
                    inner.name in child_cols and name == inner.name:
                key_names.append(name)  # plain column key
                continue
            # computed key: project under a collision-proof name so an
            # existing child column of the same name can't shadow it
            resolved = name if name not in child_cols else f"_gs_{name}"
            pre_exprs.append(inner.alias(resolved))
            key_names.append(resolved)
        pre = self._df.select(*pre_exprs)
        decomposed = _decompose_reagg(aggs)
        if decomposed is not None:
            base_aggs, aggs = decomposed
            pre = DataFrame(self._df._s, L.Aggregate(
                [col(n) for n in key_names],
                [col(n) for n in key_names] + base_aggs, pre._plan))
        pre_schema = pre.schema
        nk = len(self._keys)
        projections = []
        for s in self._sets:
            proj = []
            for n in pre_schema.names:
                if n in key_names and key_names.index(n) not in s:
                    f = pre_schema.field(n)
                    proj.append(Literal(None, f.data_type).alias(n))
                else:
                    proj.append(col(n))
            gid = sum(1 << (nk - 1 - i) for i in range(nk) if i not in s)
            proj.append(Literal(gid, T.LongType()).alias("spark_grouping_id"))
            projections.append(proj)
        expanded = DataFrame(self._df._s, L.Expand(projections, pre._plan))
        group_exprs = [col(n) for n in key_names] + [col("spark_grouping_id")]
        result_exprs = [col(n) if n == u else col(n).alias(u)
                        for n, u in zip(key_names, user_names)] + aggs
        return DataFrame(self._df._s, L.Aggregate(
            group_exprs, result_exprs, expanded._plan))


def _decompose_reagg(aggs: list):
    """Split aggregate expressions for grouping-sets re-aggregation:
    base-level partial aggregates at full key granularity plus final
    expressions over the re-merged columns.  sum->sum-of-sums,
    count->sum-of-counts, min/max->min/max, avg->sum(sum)/sum(count).
    Returns (base_aggs, rewritten_aggs), or None when any aggregate is
    not re-aggregable (first/last/count-distinct) — the caller then
    expands the raw input instead."""
    from spark_rapids_tpu.expr.aggregates import (AggregateFunction,
                                                  Average, Count,
                                                  CountDistinct, CountStar,
                                                  Max, Min, Sum)
    base_aggs: list = []
    cache: dict[str, str] = {}
    bad: list = []

    def base_col(fn):
        key = repr(fn)
        if key not in cache:
            name = f"_ra_{len(base_aggs)}"
            base_aggs.append(Alias(fn, name))
            cache[key] = name
        return col(cache[key])

    def rewrite(node):
        if isinstance(node, CountDistinct):
            bad.append(node)
            return node
        if not isinstance(node, AggregateFunction):
            return node
        if isinstance(node, CountStar):
            return Sum(base_col(CountStar()))
        if isinstance(node, Count):
            return Sum(base_col(node))
        if isinstance(node, (Sum, Min, Max)):
            return type(node)(base_col(node))
        if isinstance(node, Average):
            x = node.children[0]
            s, c = base_col(Sum(x)), base_col(Count(x))
            return (Sum(s).cast(T.DoubleType())
                    / Sum(c).cast(T.DoubleType()))
        bad.append(node)
        return node

    rewritten = [a.transform_up(rewrite) for a in aggs]
    if bad:
        return None
    return base_aggs, rewritten


class CoGroupedData:
    """Two grouped frames paired by key; ``apply_in_pandas(fn, schema)``
    calls ``fn(left_group_pdf, right_group_pdf)`` once per key present
    on either side (Spark's cogroup; reference
    GpuFlatMapCoGroupsInPandasExec)."""

    def __init__(self, left: GroupedData, right: GroupedData):
        if len(left._keys) != len(right._keys):
            raise ValueError("cogroup requires the same number of keys "
                             "on both sides")
        self._left = left
        self._right = right

    def apply_in_pandas(self, fn, schema: T.Schema) -> DataFrame:
        lk = self._left._key_columns("cogroup.apply_in_pandas")
        rk = self._right._key_columns("cogroup.apply_in_pandas")
        # both sides are hash-partitioned independently with
        # dtype-width-sensitive murmur3: mismatched key types would
        # route equal values to DIFFERENT partitions and silently split
        # matching groups (review finding) — refuse up front
        ls, rs = self._left._df.schema, self._right._df.schema
        for a, b in zip(lk, rk):
            lt = ls.field(output_name(a)).data_type
            rt = rs.field(output_name(b)).data_type
            if lt != rt:
                raise TypeError(
                    f"cogroup key types must match: left "
                    f"{output_name(a)}:{lt!r} vs right "
                    f"{output_name(b)}:{rt!r} (hash routing is "
                    f"dtype-sensitive)")
        return DataFrame(self._left._df._s, L.FlatMapCoGroupsInPandas(
            lk, rk, fn, schema, self._left._df._plan,
            self._right._df._plan))
