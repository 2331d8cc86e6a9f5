"""Unified observability plane: tracer, metrics registry, EXPLAIN
ANALYZE, and failure diagnostics (spark_rapids_tpu/obs/).

Covers the satellite guarantees, not just happy paths:

* device-side ``numOutputRows`` is recorded exactly when a host-side
  count is already known (``ColumnBatch.known_rows``) and NEVER forces
  a D2H sync;
* repeated ``partition_iter_slice`` windows (the adaptive reader's
  re-reads) do not inflate operator metrics;
* OOM split-and-retry pieces carry exact host-side counts, so split
  outputs never double-count rows;
* stage recovery attributes recomputed map outputs to the recovery
  span and the affected exchange NODE, visible in EXPLAIN ANALYZE;
* a failed query emits a bounded diagnostic bundle;
* shuffle counters (retry ladder, circuit breaker, checksum failures)
  and fault injections land in the process metrics registry.

The import-discipline guarantee (obs.trace/obs.diag never imported on
the disabled path) needs a FRESH interpreter — these tests enable
tracing — and is tests/test_telemetry.py::test_disabled_path_never_imports.
"""
import json
import os
import sys
import threading
import time

import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.obs.registry import (MetricsRegistry, get_registry,
                                           query_metrics_snapshot)
from spark_rapids_tpu.obs.trace import Tracer, new_query_id

SCHEMA = T.Schema([
    T.StructField("k", T.IntegerType(), True),
    T.StructField("v", T.LongType(), True),
])


def _schema_errors(doc, name: str) -> list:
    """Violations of ``doc`` against ci/obs_schema.json's ``name``."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    "..", "scripts"))
    try:
        from validate_obs import load_schema, validate
        return validate(doc, load_schema(name))
    finally:
        sys.path.pop(0)
DATA = {"k": [i % 7 for i in range(400)], "v": list(range(400))}


def _session(extra=None):
    from spark_rapids_tpu.session import TpuSession
    return TpuSession(dict(extra or {}))


def _agg_df(s):
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.expr.core import col
    return s.from_pydict(DATA, SCHEMA, partitions=4) \
        .group_by("k").agg(Sum(col("v")))


def _run_device(df, conf):
    from spark_rapids_tpu.exec.core import (ExecCtx, _rows_from_host,
                                            device_to_host)
    ov, meta = df._overridden(quiet=True)
    with ExecCtx(backend="device", conf=conf) as ctx:
        rows = []
        for b in meta.exec_node.execute(ctx):
            rows.extend(_rows_from_host(device_to_host(b)))
        return sorted(rows), ctx, meta.exec_node


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_span_nesting_and_parent_ids():
    tr = Tracer(query_id="q1")
    with tr.span("query", "query") as root:
        with tr.span("stage", "stage") as st:
            tr.event("mark", "stage", detail="x")
        with tr.span("other", "stage"):
            pass
    evs = tr.events_snapshot()
    by_name = {e["name"]: e for e in evs}
    assert by_name["query"]["ph"] == "X"
    assert by_name["stage"]["args"]["parent_id"] == root.span_id
    assert by_name["mark"]["ph"] == "i"
    assert by_name["mark"]["args"]["parent_id"] == st.span_id
    assert all(e["args"]["query_id"] == "q1" for e in evs)
    assert all(e["args"]["trace_id"] == tr.trace_id for e in evs)


def test_out_of_order_span_close():
    """Suspended generators close spans out of LIFO order; the tracer
    must pop by identity, not by stack position."""
    tr = Tracer(query_id="q")

    def gen(name):
        with tr.span(name, "operator"):
            yield 1
            yield 2

    a, b = gen("a"), gen("b")
    next(a)
    next(b)          # stack now [a, b]
    a.close()        # closes a FIRST (out of order)
    b.close()
    names = [e["name"] for e in tr.events_snapshot()]
    assert sorted(names) == ["a", "b"]
    # a fresh span still parents correctly (stack not corrupted)
    with tr.span("c", "operator"):
        tr.event("inner", "operator")
    evs = {e["name"]: e for e in tr.events_snapshot()}
    assert evs["inner"]["args"]["parent_id"] == evs["c"]["args"]["span_id"]


def test_bounded_events_and_drop_count(tmp_path):
    tr = Tracer(query_id="q", max_events=8)
    for i in range(20):
        tr.event(f"e{i}", "query")
    evs = tr.events_snapshot()
    assert len(evs) == 8
    assert [e["name"] for e in evs] == [f"e{i}" for i in range(12, 20)]
    doc = json.load(open(tr.export(str(tmp_path / "t.json"))))
    assert doc["otherData"]["events_dropped"] == 12


def test_export_chrome_trace_format(tmp_path):
    tr = Tracer(query_id="q2")
    with tr.span("query", "query", root="X"):
        tr.event("i1", "shuffle")
    path = str(tmp_path / "t.json")
    tr.export(path)
    doc = json.load(open(path))
    assert set(doc) >= {"traceEvents", "displayTimeUnit", "otherData"}
    for e in doc["traceEvents"]:
        assert {"name", "cat", "ph", "ts", "pid", "tid", "args"} <= set(e)
        if e["ph"] == "X":
            assert "dur" in e and e["dur"] >= 0
    assert doc["otherData"]["query_id"] == "q2"


def test_trace_header_carries_current_span():
    tr = Tracer(query_id="q3")
    assert tr.trace_header() == {"query_id": "q3",
                                 "trace_id": tr.trace_id}
    with tr.span("s", "query") as sp:
        h = tr.trace_header()
        assert h["span_id"] == sp.span_id
        assert h["query_id"] == "q3"


def test_new_query_ids_unique():
    ids = {new_query_id() for _ in range(64)}
    assert len(ids) == 64


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_delta():
    r = MetricsRegistry()
    r.inc("a")
    r.inc("a", 2)
    r.set_gauge("g", 7.5)
    before = r.snapshot()
    assert before["counters"]["a"] == 3
    assert before["gauges"]["g"] == 7.5
    r.inc("a", 10)
    r.inc("b")
    d = r.delta(before)
    assert d["counters"] == {"a": 10, "b": 1}


def test_registry_object_source_weakref():
    r = MetricsRegistry()

    class Holder:
        def __init__(self):
            self.metrics = {"x": 1, "skip": "str"}

    h = Holder()
    r.register_object_source("h", h)
    snap = r.snapshot()["gauges"]
    assert snap["h.x"] == 1
    assert "h.skip" not in snap          # non-numeric values dropped
    del h
    import gc
    gc.collect()
    assert "h.x" not in r.snapshot()["gauges"]  # weakref: no leak


def test_registry_source_errors_skipped():
    r = MetricsRegistry()
    r.register_source("bad", lambda: 1 / 0)
    r.register_source("good", lambda: {"v": 2})
    snap = r.snapshot()["gauges"]
    assert snap["good.v"] == 2


def test_prometheus_exposition_sanitized():
    r = MetricsRegistry()
    r.inc("shuffle.peer.127.0.0.1:9999.bytes", 5)
    r.set_gauge("g-x", 1)
    text = r.to_prometheus()
    assert "# TYPE" in text
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        name = line.split()[0].split("{", 1)[0]
        assert all(c.isalnum() or c == "_" for c in name), line
    # the peer address lands in a label, not the metric name
    assert 'srt_shuffle_peer_bytes{peer="127.0.0.1:9999"} 5' in text


def test_prometheus_tenant_and_fault_labels():
    r = MetricsRegistry()
    r.inc("admission.tenant.alpha.admitted", 3)
    r.inc("admission.tenant.beta.rejected", 1)
    r.inc("faults.injected.cluster.rpc.drop", 2)
    r.inc("faults.injected", 2)
    text = r.to_prometheus()
    assert 'srt_admission_tenant_admitted{tenant="alpha"} 3' in text
    assert 'srt_admission_tenant_rejected{tenant="beta"} 1' in text
    assert 'srt_faults_injected{point="cluster.rpc.drop"} 2' in text
    # the plain aggregate coexists in the same family under ONE TYPE line
    assert text.count("# TYPE srt_faults_injected counter") == 1


def test_breaker_gauges_exported():
    from spark_rapids_tpu.shuffle.retry import (_breaker,
                                                reset_circuit_breakers)
    reset_circuit_breakers()
    before = get_registry().snapshot()["counters"].get(
        "shuffle.breaker.opens", 0)
    b = _breaker(("obs-test-host", 1234))
    try:
        for _ in range(3):
            b.record_failure(RuntimeError("x"), threshold=3)
        gauges = get_registry().snapshot()["gauges"]
        assert gauges["shuffle.breaker.obs-test-host:1234.open"] == 1
        assert gauges["shuffle.breaker.obs-test-host:1234.failures"] == 3
        after = get_registry().snapshot()["counters"]["shuffle.breaker.opens"]
        assert after == before + 1
        # half-open probe failure re-arms WITHOUT recounting an open
        b.record_failure(RuntimeError("y"), threshold=3)
        assert get_registry().snapshot()["counters"][
            "shuffle.breaker.opens"] == before + 1
    finally:
        reset_circuit_breakers()


def test_faults_injected_counter():
    from spark_rapids_tpu.faults import FaultRegistry
    before = get_registry().snapshot()["counters"].get("faults.injected", 0)
    fr = FaultRegistry("store.fetch:error", seed=0)
    assert fr.check("store.fetch", shuffle=1, part=0) is not None
    counters = get_registry().snapshot()["counters"]
    assert counters["faults.injected"] == before + 1
    assert counters.get("faults.injected.store.fetch", 0) >= 1


# ---------------------------------------------------------------------------
# device numOutputRows via known_rows (no D2H sync)
# ---------------------------------------------------------------------------

def test_split_half_preserves_known_rows():
    """Split pieces carry exact host-side counts WITHOUT a device sync
    — downstream metrics count each row exactly once."""
    from spark_rapids_tpu.host.batch import HostBatch
    from spark_rapids_tpu.memory import split_half
    b = HostBatch.from_pydict(
        {"k": list(range(101)), "v": list(range(101))}, SCHEMA).to_device()
    lo, hi = split_half(b)
    assert lo.known_rows == 51 and hi.known_rows == 50      # no sync needed
    assert lo.host_num_rows() == 51 and hi.host_num_rows() == 50


def test_with_columns_propagates_known_rows():
    from spark_rapids_tpu.host.batch import HostBatch
    b = HostBatch.from_pydict(
        {"k": [1, 2], "v": [3, 4]}, SCHEMA).to_device()
    b.known_rows = 2
    assert b.with_columns(list(b.columns), b.schema).known_rows == 2


def test_oom_split_storm_no_double_count():
    """Under a persistent simulated OOM, every emitted piece is a split
    product; the host-side counts must sum to EXACTLY the input rows."""
    from spark_rapids_tpu.faults import FaultRegistry
    from spark_rapids_tpu.host.batch import HostBatch
    from spark_rapids_tpu.memory import BufferCatalog, with_retry
    cat = BufferCatalog(device_limit=10 << 20, host_limit=1 << 24)
    cat.faults = FaultRegistry("memory.oom.until_rows:oom,until_rows=20",
                               seed=0)
    b = HostBatch.from_pydict(
        {"k": list(range(100)), "v": list(range(100))}, SCHEMA).to_device()
    out = with_retry(lambda x: x, cat, b, op="ident", min_split_rows=4)
    assert cat.metrics["oom_splits"] > 0
    assert all(p.known_rows is not None for p in out)
    assert sum(p.known_rows for p in out) == 100
    cat.close()


def test_device_num_output_rows_from_known_rows():
    """A device pipeline whose batches carry known_rows records exact
    numOutputRows on those operators; operators whose counts would
    require a sync record none (never a wrong value)."""
    rows, ctx, plan = _run_device(_agg_df(_session()), _session().conf)
    scans = {k: m for k, m in ctx.metrics.items()
             if k.startswith("LocalScanExec")}
    assert scans
    total = sum(m.values.get("numOutputRows", 0) for m in scans.values())
    assert total == len(DATA["k"])


# ---------------------------------------------------------------------------
# partition_iter_slice windows must not inflate metrics
# ---------------------------------------------------------------------------

def test_slice_windows_do_not_inflate_metrics():
    from spark_rapids_tpu.exec import (ExecCtx, HashPartitioning,
                                       LocalScanExec, ShuffleExchangeExec)
    from spark_rapids_tpu.expr.core import col
    scan = LocalScanExec.from_pydict(DATA, SCHEMA, partitions=2,
                                     rows_per_batch=64)
    ex = ShuffleExchangeExec(HashPartitioning([col("k")], 4), scan)
    conf = TpuConf({"spark.sql.adaptive.advisoryPartitionSizeInBytes": 0})
    with ExecCtx(backend="device", conf=conf) as ctx:
        # read every partition through THREE overlapping slice windows
        for _ in range(3):
            for pid in range(4):
                list(ex.partition_iter_slice(ctx, pid, 0, None))
        key = next(k for k in ctx.metrics if k.startswith("LocalScanExec"))
        m = ctx.metrics[key].values
        # the map side materialized ONCE; re-windows hit the transport,
        # never the child
        assert m["numOutputRows"] == len(DATA["k"])
        # the exchange's own instrumented iter never ran (slices use the
        # uninstrumented impl), so no exchange metrics were inflated
        assert not any(k.startswith("ShuffleExchangeExec")
                       and ctx.metrics[k].values.get("numOutputBatches")
                       for k in ctx.metrics)


# ---------------------------------------------------------------------------
# ExecCtx wiring: ids, tracer lifecycle, export
# ---------------------------------------------------------------------------

def test_ctx_ids_stable_and_tracer_disabled_by_default():
    from spark_rapids_tpu.exec.core import ExecCtx
    with ExecCtx(backend="host", conf=TpuConf({})) as ctx:
        assert ctx.query_id == ctx.query_id
        assert ctx.trace_id == ctx.query_id
        assert ctx.tracer is None
        # tracer off: the span is the registry's alone (profiler
        # annotation + span.<name> counters) and yields no tracer span
        from spark_rapids_tpu.obs.registry import get_registry
        before = get_registry().counters().get("span.x.count", 0)
        with ctx.trace_span("x") as sp:
            assert sp is None
        assert get_registry().counters()["span.x.count"] == before + 1


def test_ctx_trace_export_on_close(tmp_path):
    from spark_rapids_tpu.exec.core import ExecCtx
    conf = TpuConf({"spark.rapids.obs.trace.enabled": "true",
                    "spark.rapids.obs.trace.dir": str(tmp_path)})
    with ExecCtx(backend="host", conf=conf) as ctx:
        with ctx.trace_span("query", "query"):
            ctx.trace_event("mark", "query")
        qid = ctx.query_id
    files = list(tmp_path.glob("trace_*.json"))
    assert len(files) == 1 and qid in files[0].name
    doc = json.load(open(files[0]))
    assert {e["name"] for e in doc["traceEvents"]} == {"query", "mark"}


def test_query_execution_traced_end_to_end(tmp_path):
    """One device query -> one trace whose every event carries the
    SAME query_id/trace_id, with query/partition/operator/stage spans."""
    conf = TpuConf({"spark.rapids.obs.trace.enabled": "true",
                    "spark.rapids.obs.trace.dir": str(tmp_path)})
    rows, ctx, plan = _run_device(_agg_df(_session()), conf)
    files = list(tmp_path.glob("trace_*.json"))
    assert len(files) == 1
    doc = json.load(open(files[0]))
    assert _schema_errors(doc, "trace") == []
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"query", "partition", "stage.map", "shuffle.fetch"} <= names
    assert len({e["args"]["query_id"] for e in evs}) == 1
    assert len({e["args"]["trace_id"] for e in evs}) == 1
    # top-level partition spans parent onto the query root even when
    # drained from worker threads (map-side drains parent onto their
    # stage.map span instead)
    root = next(e for e in evs if e["name"] == "query")
    parts = [e for e in evs if e["name"] == "partition"]
    assert parts
    assert any(e["args"]["parent_id"] == root["args"]["span_id"]
               for e in parts)
    span_ids = {e["args"]["span_id"] for e in evs}
    assert all(e["args"]["parent_id"] in span_ids for e in parts)


# ---------------------------------------------------------------------------
# stage recovery: span + node attribution
# ---------------------------------------------------------------------------

_RECOVERY_CONF = {
    "spark.rapids.test.faults": "shuffle.peer.dead:dead,times=1",
    # pin map-side coalescing OFF so per-piece map_write events exist
    "spark.sql.adaptive.advisoryPartitionSizeInBytes": "0",
    "spark.rapids.obs.trace.enabled": "true",
}


def test_recovery_span_owns_recomputed_writes():
    """Recomputed map outputs are attributed to the stage.recovery span,
    NOT the original stage.map span — and both live in ONE trace."""
    s = _session(_RECOVERY_CONF)
    rows, ctx, plan = _run_device(_agg_df(s), s.conf)
    s0 = _session()
    from spark_rapids_tpu.exec.core import collect_host
    ov, meta = _agg_df(s0)._overridden(quiet=True)
    assert rows == sorted(collect_host(meta.exec_node, s0.conf))
    evs = ctx.cache["tracer"].events_snapshot()
    assert len({e["args"]["query_id"] for e in evs}) == 1
    rec = [e for e in evs if e["name"] == "stage.recovery"]
    assert rec and rec[0]["args"]["recomputed"] >= 1
    maps = [e for e in evs if e["name"] == "stage.map"]
    assert maps
    writes = [e for e in evs if e["name"] == "shuffle.map_write"]
    rec_ids = {e["args"]["span_id"] for e in rec}
    map_ids = {e["args"]["span_id"] for e in maps}
    recovered = [e for e in writes if e["args"]["parent_id"] in rec_ids]
    original = [e for e in writes if e["args"]["parent_id"] not in rec_ids]
    assert recovered, "no write attributed to the recovery span"
    assert original, "no write attributed to the original map stage"
    assert all(e["args"]["parent_id"] not in map_ids for e in recovered)


def test_recovery_metrics_on_exchange_node():
    s = _session(_RECOVERY_CONF)
    rows, ctx, plan = _run_device(_agg_df(s), s.conf)
    ex = {k: m.values for k, m in ctx.metrics.items()
          if k.startswith("ShuffleExchangeExec")}
    assert any(v.get("stageRecoveries", 0) >= 1 for v in ex.values()), ex
    assert any(v.get("mapOutputsRecomputed", 0) >= 1 for v in ex.values())
    assert any(v.get("recoveryTime", 0) > 0 for v in ex.values())


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE
# ---------------------------------------------------------------------------

def test_explain_analyze_renders_runtime_metrics():
    s = _session()
    txt = _agg_df(s).explain_analyze()
    assert "HashAggregateExec" in txt and "ShuffleExchangeExec" in txt
    assert "totalTime=" in txt and "numOutputRows=" in txt
    assert "query_id=" in txt and "trace_id=" in txt
    assert "catalog:" in txt


def test_explain_analyze_shows_recovery_on_affected_node():
    s = _session(_RECOVERY_CONF)
    rows, ctx, plan = _run_device(_agg_df(s), s.conf)
    from spark_rapids_tpu.plan.overrides import explain_analyze
    txt = explain_analyze(plan, ctx)
    line = next(ln for ln in txt.splitlines()
                if "ShuffleExchangeExec" in ln and "stageRecoveries" in ln)
    assert "stageRecoveries=1" in line or "stageRecoveries=" in line
    assert "mapOutputsRecomputed=" in line


def test_query_metrics_snapshot_shape():
    s = _session()
    rows, ctx, plan = _run_device(_agg_df(s), s.conf)
    snap = query_metrics_snapshot(ctx)
    assert "operators" in snap and "registry" in snap
    assert any(k.startswith("LocalScanExec") for k in snap["operators"])
    assert {"counters", "gauges"} <= set(snap["registry"])
    assert _schema_errors(snap, "metrics") == []


# ---------------------------------------------------------------------------
# failure diagnostics
# ---------------------------------------------------------------------------

def test_diagnostic_bundle_on_forced_failure(tmp_path):
    from spark_rapids_tpu.shuffle.errors import StageRecoveryExhausted
    d = tmp_path / "diag"
    s = _session({
        "spark.rapids.test.faults": "shuffle.peer.dead:dead,times=0",
        "spark.rapids.shuffle.recovery.maxStageAttempts": "1",
        "spark.rapids.obs.trace.enabled": "true",
        "spark.rapids.obs.diagnostics.dir": str(d),
    })
    with pytest.raises(StageRecoveryExhausted):
        _run_device(_agg_df(s), s.conf)
    bundles = list(d.glob("diag_*.json"))
    assert len(bundles) == 1
    doc = json.load(open(bundles[0]))
    assert doc["kind"] == "spark_rapids_tpu.diagnostic_bundle"
    assert doc["error"]["type"] == "StageRecoveryExhausted"
    assert doc["query_id"] and doc["trace_id"]
    assert isinstance(doc["plan_analyzed"], list) and doc["plan_analyzed"]
    assert any("ShuffleExchangeExec" in ln for ln in doc["plan_analyzed"])
    assert doc["span_events"], "span events missing from bundle"
    assert doc["faults"]["spec"].startswith("shuffle.peer.dead")
    assert doc["faults"]["fired"], "fault audit log missing"
    assert "tier_occupancy" in doc["catalog"]
    assert any(k.startswith("spark.rapids") for k in doc["conf"])
    assert doc["metrics"]["operators"]
    # the bundle validates against the checked-in CI schema
    assert _schema_errors(doc, "bundle") == []


def test_no_bundle_when_dir_unset(tmp_path):
    from spark_rapids_tpu.shuffle.errors import StageRecoveryExhausted
    s = _session({
        "spark.rapids.test.faults": "shuffle.peer.dead:dead,times=0",
        "spark.rapids.shuffle.recovery.maxStageAttempts": "1",
    })
    with pytest.raises(StageRecoveryExhausted):
        _run_device(_agg_df(s), s.conf)   # must not raise from diag path


def test_bundle_truncates_error_message(tmp_path):
    from spark_rapids_tpu.exec.core import ExecCtx
    from spark_rapids_tpu.obs.diag import maybe_emit_bundle

    class _Node:
        children = ()

        def node_desc(self):
            return "FakeExec"

    with ExecCtx(backend="host", conf=TpuConf({})) as ctx:
        err = RuntimeError("x" * 20000)
        path = maybe_emit_bundle(ctx, _Node(), err, str(tmp_path))
        assert path is not None
        doc = json.load(open(path))
        assert len(doc["error"]["message"]) <= 4096


# ---------------------------------------------------------------------------
# TCP shuffle: trace propagation + wire counters
# ---------------------------------------------------------------------------

def test_trace_header_crosses_tcp_wire():
    """The serving peer logs the ORIGINATING query's ids: a reduce-side
    fetch from another process lands in the right trace."""
    from spark_rapids_tpu.exec.core import ExecCtx, host_to_device
    from spark_rapids_tpu.host.batch import HostBatch
    from spark_rapids_tpu.shuffle.retry import fetch_remote_with_retry
    from spark_rapids_tpu.shuffle.tcp import TcpShuffleTransport
    conf = TpuConf({"spark.rapids.obs.trace.enabled": "true"})
    with ExecCtx(backend="device", conf=conf) as ctx:
        t = TcpShuffleTransport(conf, ctx)
        try:
            hb = HostBatch.from_pydict({"k": [1], "v": [2]}, SCHEMA)
            t.write_partition(1, 0, 0, host_to_device(hb))
            tracer = ctx.tracer
            with tracer.span("reduce", "query") as sp:
                got = list(fetch_remote_with_retry(
                    t.address, 1, 0, conf=conf, tracer=tracer,
                    trace=tracer.trace_header()))
            assert len(got) == 1
            assert t.server_metrics["traced_fetches"] == 1
            logged = t._server.trace_log[-1]
            assert logged["query_id"] == ctx.query_id
            assert logged["trace_id"] == ctx.trace_id
            assert logged["span_id"] == sp.span_id
        finally:
            t.close()


def test_untraced_fetch_interops():
    """No trace header -> old-client interop: served fine, not logged."""
    from spark_rapids_tpu.exec.core import ExecCtx, host_to_device
    from spark_rapids_tpu.host.batch import HostBatch
    from spark_rapids_tpu.shuffle.tcp import TcpShuffleTransport, fetch_remote
    conf = TpuConf({})
    with ExecCtx(backend="device", conf=conf) as ctx:
        t = TcpShuffleTransport(conf, ctx)
        try:
            hb = HostBatch.from_pydict({"k": [1], "v": [2]}, SCHEMA)
            t.write_partition(1, 0, 0, host_to_device(hb))
            got = list(fetch_remote(t.address, 1, 0))
            assert len(got) == 1
            assert t.server_metrics["traced_fetches"] == 0
            assert len(t._server.trace_log) == 0
        finally:
            t.close()


def test_retry_events_and_counters_share_trace():
    """A mid-stream reset: the retry event lands in the SAME trace as
    the query, and ladder counters move in the process registry."""
    from spark_rapids_tpu.exec.core import ExecCtx, host_to_device
    from spark_rapids_tpu.host.batch import HostBatch
    from spark_rapids_tpu.shuffle.retry import (fetch_remote_with_retry,
                                                reset_circuit_breakers)
    from spark_rapids_tpu.shuffle.tcp import TcpShuffleTransport
    reset_circuit_breakers()
    conf = TpuConf({"spark.rapids.test.faults":
                    "tcp.server.frame:reset,nth=2",
                    "spark.rapids.shuffle.tcp.retryWaitSeconds": "0.02",
                    "spark.rapids.obs.trace.enabled": "true"})
    before = get_registry().snapshot()
    with ExecCtx(backend="device", conf=conf) as ctx:
        t = TcpShuffleTransport(conf, ctx)
        try:
            hb = HostBatch.from_pydict({"k": [1, 2], "v": [3, 4]}, SCHEMA)
            for m in range(3):
                t.write_partition(1, m, 0, host_to_device(hb))
            tracer = ctx.tracer
            got = list(fetch_remote_with_retry(
                t.address, 1, 0, conf=conf,
                tracer=tracer, trace=tracer.trace_header()))
            assert len(got) == 3
            evs = tracer.events_snapshot()
            retries = [e for e in evs if e["name"] == "shuffle.fetch.retry"]
            assert len(retries) == 1
            assert retries[0]["args"]["query_id"] == ctx.query_id
            assert retries[0]["args"]["delivered"] >= 1
        finally:
            t.close()
    d = get_registry().delta(before)["counters"]
    assert d.get("shuffle.fetch.retries", 0) >= 1
    assert d.get("shuffle.fetch.attempts", 0) >= 2
    assert d.get("shuffle.fetch.bytes", 0) > 0
    assert any(k.startswith("shuffle.peer.") and k.endswith(".bytes_fetched")
               for k in d)


def test_checksum_failure_counter():
    from spark_rapids_tpu.exec.core import ExecCtx, host_to_device
    from spark_rapids_tpu.host.batch import HostBatch
    from spark_rapids_tpu.shuffle.tcp import (ShuffleTransportError,
                                              TcpShuffleTransport,
                                              fetch_remote)
    conf = TpuConf({"spark.rapids.test.faults":
                    "tcp.server.frame:corrupt,nth=1"})
    before = get_registry().snapshot()
    with ExecCtx(backend="device", conf=conf) as ctx:
        t = TcpShuffleTransport(conf, ctx)
        try:
            hb = HostBatch.from_pydict({"k": [1], "v": [2]}, SCHEMA)
            t.write_partition(1, 0, 0, host_to_device(hb))
            with pytest.raises(ShuffleTransportError):
                list(fetch_remote(t.address, 1, 0))
        finally:
            t.close()
    d = get_registry().delta(before)["counters"]
    assert d.get("shuffle.fetch.checksum_failures", 0) >= 1
