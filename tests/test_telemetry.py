"""Cluster-wide telemetry: latency histograms, the live HTTP endpoint,
merged worker traces, and the persistent query history log.

Covers the ISSUE-15 observability plane end to end at unit scale:
histogram merge across cluster worker snapshot deltas (a dead worker's
last snapshot still counts; an empty delta is inert), the 127.0.0.1
telemetry server's three routes, history-log rotation + torn-line
tolerance + CI-schema conformance, and the cross-process trace lane
machinery (stamp_for_shipping -> ingest_wall -> one export).
"""
import json
import os
import socket
import sys
import threading
import urllib.request

import pytest

from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.obs.registry import (MetricsRegistry,
                                           delta_histogram_snapshot,
                                           empty_histogram_snapshot,
                                           histogram_percentile,
                                           merge_histogram_snapshots)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
from validate_obs import load_schema, validate  # noqa: E402

sys.path.pop(0)


# ---------------------------------------------------------------------------
# histogram semantics
# ---------------------------------------------------------------------------

def _observe_all(reg, name, values):
    for v in values:
        reg.observe(name, v)


def test_histogram_percentiles_monotone_and_bounded():
    reg = MetricsRegistry()
    values = [0.0005, 0.003, 0.01, 0.05, 0.2, 0.2, 1.5, 7.0]
    _observe_all(reg, "h", values)
    snap = reg.snapshot()["histograms"]["h"]
    assert snap["count"] == len(values)
    assert snap["sum"] == pytest.approx(sum(values))
    ps = [histogram_percentile(snap, q) for q in (1, 25, 50, 75, 95, 99)]
    assert ps == sorted(ps), "percentiles must be non-decreasing"
    assert ps[0] >= 0.0
    # p99 of values all <= 7.0 must not exceed the containing bucket
    assert ps[-1] <= max(snap["le"]) * 2


def test_histogram_merge_equals_union():
    a, b = MetricsRegistry(), MetricsRegistry()
    u = MetricsRegistry()
    va = [0.001, 0.02, 0.4, 3.0]
    vb = [0.005, 0.005, 1.0]
    _observe_all(a, "h", va)
    _observe_all(b, "h", vb)
    _observe_all(u, "h", va + vb)
    merged = merge_histogram_snapshots(
        a.snapshot()["histograms"]["h"], b.snapshot()["histograms"]["h"])
    union = u.snapshot()["histograms"]["h"]
    assert merged["counts"] == union["counts"]
    assert merged["count"] == union["count"]
    assert merged["sum"] == pytest.approx(union["sum"])
    for q in (50, 95, 99):
        assert histogram_percentile(merged, q) == pytest.approx(
            histogram_percentile(union, q))


def test_histogram_delta_none_when_unmoved():
    reg = MetricsRegistry()
    _observe_all(reg, "h", [0.1, 0.2])
    snap = reg.snapshot()["histograms"]["h"]
    assert delta_histogram_snapshot(snap, snap) is None
    # vs a None/empty baseline the whole snapshot is the delta
    d = delta_histogram_snapshot(snap, None)
    assert d is not None and d["count"] == 2


def test_histogram_merge_across_worker_snapshot_deltas():
    """The driver-side cluster merge: each worker ships registry
    snapshots on heartbeats; the cluster-wide distribution is the merge
    of per-worker (current - baseline) deltas.  A worker that died
    mid-run still contributes its last shipped snapshot, and the merged
    percentiles stay monotone; a worker whose histogram never moved
    contributes nothing."""
    from spark_rapids_tpu.cluster.driver import ClusterDriver, WorkerHandle

    def handle(wid, alive, baseline, current):
        h = WorkerHandle.__new__(WorkerHandle)
        h.worker_id, h.alive = wid, alive
        h.baseline = {"histograms": baseline}
        h.metrics = {"histograms": current}
        return h

    r0, r1 = MetricsRegistry(), MetricsRegistry()
    _observe_all(r0, "query.wall_seconds", [0.01, 0.05, 0.2])
    base0 = r0.snapshot()["histograms"]
    _observe_all(r0, "query.wall_seconds", [0.5, 2.0])
    cur0 = r0.snapshot()["histograms"]
    _observe_all(r1, "query.wall_seconds", [0.002, 0.004])
    cur1 = r1.snapshot()["histograms"]

    class _Fake:
        def workers(self):
            return self._h

    fake = _Fake()
    # w0 alive with movement since baseline; w1 DEAD after shipping its
    # only snapshot (baseline empty); w2 alive but inert (cur == base)
    fake._h = [
        handle("w0", True, base0, cur0),
        handle("w1", False, {}, cur1),
        handle("w2", True, cur1, cur1),
    ]
    merged = ClusterDriver.merged_worker_histograms(fake)
    h = merged["query.wall_seconds"]
    # w0 delta (2 observations) + w1 full snapshot (2) = 4; w2 inert
    assert h["count"] == 4
    ps = [histogram_percentile(h, q) for q in (50, 90, 95, 99)]
    assert ps == sorted(ps)
    assert ps[0] > 0

    # dropping the dead worker entirely only removes ITS observations
    fake._h = fake._h[:1]
    alone = ClusterDriver.merged_worker_histograms(fake)
    assert alone["query.wall_seconds"]["count"] == 2

    # all-inert cluster merges to nothing at all
    fake._h = [handle("w2", True, cur1, cur1)]
    assert ClusterDriver.merged_worker_histograms(fake) == {}


def test_histogram_snapshot_matches_ci_schema():
    reg = MetricsRegistry()
    _observe_all(reg, "h", [0.1])
    snap = reg.snapshot()["histograms"]["h"]
    assert validate(snap, load_schema("histogram")) == []
    assert validate(empty_histogram_snapshot(),
                    load_schema("histogram")) == []


def test_prometheus_histogram_exposition_cumulative():
    reg = MetricsRegistry()
    _observe_all(reg, "query.wall_seconds", [0.001, 0.02, 0.5, 3.0])
    text = reg.to_prometheus()
    assert "# TYPE srt_query_wall_seconds histogram" in text
    bucket_lines = [ln for ln in text.splitlines()
                    if ln.startswith("srt_query_wall_seconds_bucket")]
    assert bucket_lines, "no _bucket series"
    counts = [float(ln.rsplit(" ", 1)[1]) for ln in bucket_lines]
    assert counts == sorted(counts), "buckets must be cumulative"
    assert bucket_lines[-1].split("{")[1].startswith('le="+Inf"')
    assert counts[-1] == 4
    assert "srt_query_wall_seconds_sum" in text
    assert "srt_query_wall_seconds_count 4" in text


# ---------------------------------------------------------------------------
# live HTTP endpoint
# ---------------------------------------------------------------------------

@pytest.fixture
def http_session():
    from spark_rapids_tpu.session import TpuSession
    s = TpuSession({})
    yield s
    s.shutdown()


def _get(url):
    with urllib.request.urlopen(url, timeout=5) as r:
        return r.status, dict(r.headers), r.read()


def test_http_endpoint_routes(http_session):
    from spark_rapids_tpu.obs.http import ObsHttpServer
    from spark_rapids_tpu.obs.registry import get_registry
    get_registry().observe("query.wall_seconds", 0.01)
    srv = ObsHttpServer(http_session, 0)   # ephemeral port
    try:
        assert srv.address.startswith("http://127.0.0.1:")
        st, hdrs, body = _get(srv.address + "/metrics")
        assert st == 200
        assert hdrs["Content-Type"].startswith("text/plain")
        assert b"# TYPE srt_query_wall_seconds histogram" in body
        assert b"srt_query_wall_seconds_bucket" in body

        st, _, body = _get(srv.address + "/healthz")
        assert st == 200
        health = json.loads(body)
        assert health["status"] == "ok"
        assert "admission" in health

        st, _, body = _get(srv.address + "/queries")
        assert st == 200
        q = json.loads(body)
        assert q["count"] == 0 and q["active"] == {}

        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.address + "/nope")
        assert ei.value.code == 404
    finally:
        srv.close()
    # port is actually released (TIME_WAIT from the scrape connections
    # is fine — REUSEADDR is exactly what a restarting server would use)
    with socket.socket() as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", srv.port))


def test_http_healthz_drains_on_shutdown(http_session):
    from spark_rapids_tpu.obs.http import ObsHttpServer
    srv = ObsHttpServer(http_session, 0)
    try:
        http_session._admission_controller().begin_shutdown()
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.address + "/healthz")
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["status"] == "draining"
    finally:
        srv.close()


def test_http_metrics_scrape_concurrent_with_observations(http_session):
    """Scrapes racing observers must never 500 or return torn text."""
    from spark_rapids_tpu.obs.http import ObsHttpServer
    from spark_rapids_tpu.obs.registry import get_registry
    srv = ObsHttpServer(http_session, 0)
    stop = threading.Event()

    def pound():
        reg = get_registry()
        i = 0
        while not stop.is_set():
            reg.observe("query.wall_seconds", 0.001 * (i % 50 + 1))
            reg.inc("queries_executed")
            i += 1

    t = threading.Thread(target=pound, daemon=True)
    t.start()
    try:
        for _ in range(20):
            st, _, body = _get(srv.address + "/metrics")
            assert st == 200
            text = body.decode()
            for ln in text.splitlines():
                if ln and not ln.startswith("#"):
                    float(ln.rsplit(" ", 1)[1])   # every sample parses
    finally:
        stop.set()
        t.join(timeout=5)
        srv.close()


def test_session_conf_port_zero_means_no_server():
    from spark_rapids_tpu.session import TpuSession
    s = TpuSession({})
    try:
        assert s._http is None
    finally:
        s.shutdown()


def test_session_conf_port_starts_and_stops_server():
    from spark_rapids_tpu.session import TpuSession
    s = TpuSession({"spark.rapids.obs.http.port": "0"})
    try:
        # "0" is falsy-as-int: still off — only a real port starts it
        assert s._http is None
    finally:
        s.shutdown()
    s = TpuSession({"spark.rapids.obs.http.port": _free_port()})
    try:
        assert s._http is not None
        st, _, _ = _get(s._http.address + "/healthz")
        assert st == 200
        addr = s._http.address
    finally:
        s.shutdown()
    assert s._http is None
    with pytest.raises(OSError):
        _get(addr + "/healthz")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# query history log
# ---------------------------------------------------------------------------

def test_history_log_rotation_keeps_newest(tmp_path):
    from spark_rapids_tpu.obs.history import QueryHistoryLog, read_entries
    log = QueryHistoryLog(str(tmp_path), max_entries=5)
    for i in range(12):
        log.append({"kind": "history", "query_id": f"q{i}"})
    entries = read_entries(log.path)
    assert len(entries) == 5
    assert [e["query_id"] for e in entries] == [f"q{i}" for i in
                                               range(7, 12)]
    # no stray temp file left behind
    assert sorted(os.listdir(tmp_path)) == ["query_history.jsonl"]


def test_history_reader_skips_torn_lines(tmp_path):
    from spark_rapids_tpu.obs.history import QueryHistoryLog, read_entries
    log = QueryHistoryLog(str(tmp_path))
    log.append({"query_id": "a"})
    with open(log.path, "a") as f:
        f.write('{"query_id": "torn-mid-cra')   # crash mid-append
    log.append({"query_id": "b"})
    ids = [e["query_id"] for e in read_entries(log.path)]
    assert ids == ["a", "b"]


def test_history_concurrent_appenders(tmp_path):
    from spark_rapids_tpu.obs.history import QueryHistoryLog, read_entries
    log = QueryHistoryLog(str(tmp_path), max_entries=1000)
    n_threads, per = 8, 25

    def appender(k):
        for i in range(per):
            log.append({"query_id": f"t{k}-{i}"})

    ts = [threading.Thread(target=appender, args=(k,))
          for k in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    entries = read_entries(log.path)
    assert len(entries) == n_threads * per
    assert len({e["query_id"] for e in entries}) == n_threads * per


def test_history_entry_written_at_terminal_state(tmp_path):
    """One entry per executed query after shutdown(drain=True), with
    terminal state, registry delta, analyzed plan — and it conforms to
    the checked-in CI schema."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expr.core import col, lit
    from spark_rapids_tpu.obs.history import HISTORY_FILE, read_entries
    from spark_rapids_tpu.session import TpuSession
    s = TpuSession({"spark.rapids.obs.history.dir": str(tmp_path)})
    schema = T.Schema([T.StructField("a", T.IntegerType())])
    df = s.from_pydict({"a": list(range(20))}, schema, partitions=2)
    df.where(col("a") > lit(3)).collect()
    df.where(col("a") > lit(10)).collect()
    s.shutdown(drain=True)
    entries = read_entries(os.path.join(str(tmp_path), HISTORY_FILE))
    assert len(entries) == 2
    hs = load_schema("history")
    for e in entries:
        assert validate(e, hs) == []
        assert e["state"] == "FINISHED"
        assert e["plan_fingerprint"]
        assert e["plan_analyzed"]
        assert e["registry_delta"]["counters"]
        assert e["wall_s"] is not None and e["wall_s"] >= 0
        assert e["executed"] is True


def test_history_records_failure_taxonomy(tmp_path):
    """A query that dies at runtime (injected shuffle-peer death with
    the recovery budget exhausted) lands in the history log as FAILED
    with the error taxonomy filled in."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.expr.core import col
    from spark_rapids_tpu.obs.history import HISTORY_FILE, read_entries
    from spark_rapids_tpu.session import TpuSession
    s = TpuSession({
        "spark.rapids.obs.history.dir": str(tmp_path),
        "spark.rapids.test.faults": "shuffle.peer.dead:dead,times=0",
        "spark.rapids.shuffle.recovery.maxStageAttempts": "1",
    })
    schema = T.Schema([T.StructField("k", T.IntegerType()),
                       T.StructField("v", T.DoubleType())])
    df = s.from_pydict({"k": [1, 2, 1, 2], "v": [1.0, 2.0, 3.0, 4.0]},
                       schema, partitions=2) \
        .group_by("k").agg(Sum(col("v")))
    with pytest.raises(Exception):
        df.collect()
    s.shutdown()
    entries = read_entries(os.path.join(str(tmp_path), HISTORY_FILE))
    assert len(entries) == 1
    e = entries[0]
    assert e["state"] == "FAILED"
    assert e["error"]["type"]
    assert e["error"]["message"]
    assert validate(e, load_schema("history")) == []


def test_history_tool_is_engine_free(tmp_path):
    """python -m tools.history must not import the engine: it has to
    work on a forensics box with no jax."""
    import subprocess
    from spark_rapids_tpu.obs.history import QueryHistoryLog
    log = QueryHistoryLog(str(tmp_path))
    log.append({"kind": "history", "version": 1, "query_id": "abc123",
                "tenant": "default", "state": "FINISHED",
                "submitted_unix_s": 1.0, "wall_s": 0.5,
                "registry_delta": {"counters": {}, "histograms": {}}})
    code = ("import sys, tools.history; "
            "bad = [m for m in sys.modules if m.startswith("
            "'spark_rapids_tpu') or m == 'jax']; "
            "sys.exit(1 if bad else 0)")
    r = subprocess.run([sys.executable, "-c", code],
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    r = subprocess.run(
        [sys.executable, "-m", "tools.history", "--dir", str(tmp_path),
         "list"],
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "abc123" in r.stdout


# ---------------------------------------------------------------------------
# cross-process trace lanes
# ---------------------------------------------------------------------------

def test_trace_ship_and_ingest_one_timeline(tmp_path):
    """Worker events drained, stamped to wall-clock, ingested by the
    driver tracer: ONE export with both pids on named lanes, worker ts
    rebased onto the driver origin."""
    from spark_rapids_tpu.obs.trace import Tracer, stamp_for_shipping
    driver = Tracer(query_id="q1")
    worker = Tracer(query_id="q1", trace_id=driver.trace_id)
    worker.pid = driver.pid + 1   # simulate a separate process

    with driver.span("cluster.map_stage", "cluster"):
        with worker.span("worker.fragment", "cluster"):
            pass
    shipped = stamp_for_shipping(worker.drain_events(),
                                 worker._wall_origin, worker.pid)
    assert shipped and all(ev["pid"] == worker.pid for ev in shipped)
    # drain is exactly-once
    assert worker.drain_events() == []

    driver.ensure_lane(driver.pid, "driver")
    driver.ensure_lane(worker.pid, "cluster worker w0")
    driver.ensure_lane(worker.pid, "dup ignored")   # idempotent
    driver.ingest_wall(shipped)

    path = driver.export(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    assert validate(doc, load_schema("trace")) == []
    lanes = {ev["pid"]: ev["args"]["name"] for ev in doc["traceEvents"]
             if ev["ph"] == "M" and ev["name"] == "process_name"}
    assert lanes == {driver.pid: "driver",
                     worker.pid: "cluster worker w0"}
    pids = {ev["pid"] for ev in doc["traceEvents"] if ev["ph"] == "X"}
    assert pids == {driver.pid, worker.pid}
    # the worker span's rebased ts must land within the driver span
    dspan = next(ev for ev in doc["traceEvents"]
                 if ev["name"] == "cluster.map_stage")
    wspan = next(ev for ev in doc["traceEvents"]
                 if ev["name"] == "worker.fragment")
    assert dspan["ts"] - 1e4 <= wspan["ts"] <= dspan["ts"] + dspan["dur"] \
        + 1e4


def test_local2_query_exports_one_trace_with_worker_lanes(tmp_path):
    """A real local[2] query: ONE exported trace, spans from the driver
    and from both worker processes, each pid on a named lane."""
    import glob

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.expr.core import col
    from spark_rapids_tpu.session import TpuSession
    schema = T.Schema([T.StructField("k", T.IntegerType(), True),
                       T.StructField("v", T.LongType(), True)])
    s = TpuSession({"spark.rapids.cluster.mode": "local[2]",
                    "spark.rapids.obs.trace.enabled": "true",
                    "spark.rapids.obs.trace.dir": str(tmp_path)})
    try:
        worker_pids = {h.pid for h in s._cluster().workers()}
        df = s.from_pydict({"k": [i % 13 for i in range(400)],
                            "v": list(range(400))}, schema,
                           partitions=4, rows_per_batch=64)
        assert len(df.group_by("k").agg(Sum(col("v"))).collect()) == 13
    finally:
        s.shutdown()
    traces = glob.glob(str(tmp_path / "trace_*.json"))
    assert len(traces) == 1, traces
    doc = json.load(open(traces[0]))
    assert validate(doc, load_schema("trace")) == []
    lanes = {ev["pid"]: ev["args"]["name"] for ev in doc["traceEvents"]
             if ev.get("ph") == "M" and ev["name"] == "process_name"}
    span_pids = {ev["pid"] for ev in doc["traceEvents"]
                 if ev.get("ph") == "X"}
    assert len(worker_pids) == 2 and worker_pids <= span_pids, \
        (worker_pids, span_pids)
    assert worker_pids <= set(lanes), (worker_pids, lanes)
    assert os.getpid() in span_pids and lanes.get(os.getpid()) == "driver"


def test_trace_lanes_survive_buffer_rotation(tmp_path):
    from spark_rapids_tpu.obs.trace import Tracer
    tr = Tracer(query_id="q2", max_events=4)
    tr.ensure_lane(tr.pid, "driver")
    for i in range(32):
        tr.event(f"e{i}")
    evs = tr.events_snapshot()
    assert evs[0]["ph"] == "M", "lane metadata must survive rotation"
    assert sum(1 for e in evs if e["ph"] == "i") == 4


def test_cluster_span_buffer_bounds():
    """Driver-side heartbeat span buffering is bounded per query and in
    query count, and drains exactly once."""
    import threading as _t
    from collections import deque

    from spark_rapids_tpu.cluster.driver import (_MAX_SPAN_QUERIES,
                                                 ClusterDriver)
    d = ClusterDriver.__new__(ClusterDriver)
    d._span_lock = _t.Lock()
    d._pending_spans = {}
    for qi in range(_MAX_SPAN_QUERIES + 3):
        d.buffer_spans([{"name": "x", "args": {"query_id": f"q{qi}"}}])
    assert len(d._pending_spans) == _MAX_SPAN_QUERIES
    assert "q0" not in d._pending_spans      # oldest evicted wholesale
    last = f"q{_MAX_SPAN_QUERIES + 2}"
    assert len(d.drain_query_spans(last)) == 1
    assert d.drain_query_spans(last) == []   # exactly-once
    assert all(isinstance(v, deque) for v in d._pending_spans.values())


# ---------------------------------------------------------------------------
# import discipline
# ---------------------------------------------------------------------------

_DISABLED_PATH_SCRIPT = """
import json, sys, threading
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.aggregates import Sum
from spark_rapids_tpu.expr.core import col
s = TpuSession({})
schema = T.Schema([T.StructField("k", T.IntegerType(), True),
                   T.StructField("v", T.LongType(), True)])
df = s.from_pydict({"k": [i % 5 for i in range(200)],
                    "v": list(range(200))}, schema, partitions=2)
assert len(df.group_by("k").agg(Sum(col("v"))).collect(tenant="t")) == 5
threads = sorted(t.name for t in threading.enumerate())
s.shutdown()
assert s._http is None
# nor may the cluster driver pull the journal in when it is imported
import spark_rapids_tpu.cluster.driver
mods = sorted(m for m in sys.modules if m.startswith("spark_rapids_tpu"))
print(json.dumps({"modules": mods, "threads": threads}))
"""

# module prefixes a default-conf session must leave unimported, and the
# background threads it must not start
_DISABLED_GROUPS = {
    "tracer": (("spark_rapids_tpu.obs.trace", "spark_rapids_tpu.obs.diag"),
               ()),
    "telemetry": (("spark_rapids_tpu.obs.http",
                   "spark_rapids_tpu.obs.history"), ()),
    "profiler": (("spark_rapids_tpu.obs.profile",
                  "spark_rapids_tpu.obs.metering"), ("obs-hbm-sampler",)),
    "control": (("spark_rapids_tpu.control",), ("control-loop",)),
    "journal": (("spark_rapids_tpu.cluster.journal",), ()),
}


@pytest.fixture(scope="module")
def disabled_path_record():
    """One fresh interpreter runs a shuffled group-by on a default-conf
    session and reports what it imported (this process has imported
    everything, so sys.modules here proves nothing)."""
    import subprocess
    r = subprocess.run([sys.executable, "-c", _DISABLED_PATH_SCRIPT],
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stdout + r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("group", sorted(_DISABLED_GROUPS))
def test_disabled_path_never_imports(disabled_path_record, group):
    """With every conf at its default a full query leaves the gated
    subsystem out of sys.modules and starts none of its threads — the
    disabled path costs nothing by construction."""
    prefixes, threads = _DISABLED_GROUPS[group]
    bad = [m for m in disabled_path_record["modules"]
           if any(m == p or m.startswith(p + ".") for p in prefixes)]
    assert not bad, f"{group} modules imported on the default path: {bad}"
    live = [t for t in disabled_path_record["threads"] if t in threads]
    assert not live, f"{group} threads running on the default path: {live}"


def test_obs_package_lazy_exports():
    import importlib

    import spark_rapids_tpu.obs as obs
    assert set(obs.__all__) >= {"ObsHttpServer", "QueryHistoryLog",
                                "history_log"}
    assert obs.QueryHistoryLog is not None
    mod = importlib.import_module("spark_rapids_tpu.obs.history")
    assert obs.history_log is mod.history_log
    with pytest.raises(AttributeError):
        obs.no_such_name


# ---------------------------------------------------------------------------
# conf surface
# ---------------------------------------------------------------------------

def test_telemetry_confs_registered():
    # importing the gated modules registers their entries
    import spark_rapids_tpu.obs.history  # noqa: F401
    import spark_rapids_tpu.obs.http  # noqa: F401
    from spark_rapids_tpu.conf import registered_entries
    names = set(registered_entries())
    assert "spark.rapids.obs.http.port" in names
    assert "spark.rapids.obs.history.dir" in names
    assert "spark.rapids.obs.history.maxEntries" in names
    conf = TpuConf({"spark.rapids.obs.history.maxEntries": "7"})
    from spark_rapids_tpu.obs.history import HISTORY_MAX
    assert HISTORY_MAX.get(conf.settings) == 7


# ---------------------------------------------------------------------------
# percentile edge cases (the control loop consumes these directly)
# ---------------------------------------------------------------------------

def test_histogram_percentile_empty_and_none_delta():
    reg = MetricsRegistry()
    _observe_all(reg, "h", [0.1, 0.2])
    snap = reg.snapshot()["histograms"]["h"]
    # an unmoved window collapses to None; the percentile of that must
    # be None, not 0.0 — "no signal" and "instant queries" are
    # different control inputs
    assert histogram_percentile(delta_histogram_snapshot(snap, snap),
                                99) is None
    assert histogram_percentile(None, 99) is None
    assert histogram_percentile({}, 50) is None


def test_histogram_percentile_single_bucket_interpolates():
    reg = MetricsRegistry()
    # every observation lands in ONE bucket: all percentiles must stay
    # inside that bucket's bounds and remain monotone in q
    _observe_all(reg, "h", [0.3] * 10)
    snap = reg.snapshot()["histograms"]["h"]
    le = snap["le"]
    i = next(i for i, c in enumerate(snap["counts"]) if c)
    lo = le[i - 1] if i > 0 else 0.0
    hi = le[i] if i < len(le) else le[-1]
    ps = [histogram_percentile(snap, q) for q in (1, 50, 99, 100)]
    assert ps == sorted(ps)
    for p in ps:
        assert lo <= p <= hi


def test_histogram_percentile_overflow_bucket_reports_edge():
    reg = MetricsRegistry()
    # beyond the largest bound: the +Inf bucket has no upper edge, so
    # the estimate must clamp to the largest finite bound, not invent
    # a number
    _observe_all(reg, "h", [1e9])
    snap = reg.snapshot()["histograms"]["h"]
    assert histogram_percentile(snap, 99) == max(snap["le"])


# ---------------------------------------------------------------------------
# history index (plan-routing feed)
# ---------------------------------------------------------------------------

def test_history_index_only_finished_runs_teach():
    from spark_rapids_tpu.obs.history import HistoryIndex
    idx = HistoryIndex()
    idx.note_entry({"plan_fingerprint": "fp", "state": "FAILED",
                    "wall_s": 9.0})
    idx.note_entry({"plan_fingerprint": "fp", "state": "CANCELLED",
                    "wall_s": 9.0})
    idx.note_entry({"plan_fingerprint": "fp", "state": "FINISHED",
                    "wall_s": "not-a-number"})
    idx.note_entry({"state": "FINISHED", "wall_s": 1.0})  # no fp
    assert idx.lookup("fp") is None
    idx.note_entry({"plan_fingerprint": "fp", "state": "FINISHED",
                    "wall_s": 0.5})
    got = idx.lookup("fp")
    assert got["samples"] == 1
    assert got["median_wall_s"] == pytest.approx(0.5)


def test_history_index_mesh_breakdown_and_bounds():
    from spark_rapids_tpu.obs.history import HistoryIndex
    idx = HistoryIndex(max_fingerprints=2, max_samples=3)
    for wall, mesh in [(1.0, 1), (2.0, 1), (0.2, 4), (0.4, 4)]:
        idx.note_entry({"plan_fingerprint": "a", "state": "FINISHED",
                        "wall_s": wall, "mesh_devices": mesh})
    got = idx.lookup("a")
    # max_samples=3 keeps only the newest 3 of the 4
    assert got["samples"] == 3
    assert got["by_mesh"][4]["samples"] == 2
    assert got["by_mesh"][4]["median_wall_s"] == pytest.approx(0.3)
    # LRU bound on fingerprints: touching "a" via lookup keeps it
    # alive while "b" then "c" arrive — "b" is the one evicted
    idx.note_entry({"plan_fingerprint": "b", "state": "FINISHED",
                    "wall_s": 1.0})
    idx.lookup("a")
    idx.note_entry({"plan_fingerprint": "c", "state": "FINISHED",
                    "wall_s": 1.0})
    assert len(idx) == 2
    assert idx.lookup("b") is None
    assert idx.lookup("a") is not None


def test_history_index_refresh_replaces_no_double_count(tmp_path):
    from spark_rapids_tpu.obs.history import (HistoryIndex,
                                              QueryHistoryLog)
    log = QueryHistoryLog(str(tmp_path))
    idx = HistoryIndex(min_refresh_s=0.0)
    entry = {"plan_fingerprint": "fp", "state": "FINISHED",
             "wall_s": 1.0, "query_id": "q0"}
    log.append(entry)
    idx.note_entry(entry)           # in-process fast path
    assert idx.refresh_from(log.path) is True   # file identity is new
    # the rebuild REPLACED the index — the entry fed both ways still
    # counts once
    assert idx.lookup("fp")["samples"] == 1
    # unchanged file: stat-gated, no rebuild
    assert idx.refresh_from(log.path) is False
    # a second process appends: identity moves, rebuild picks it up
    log.append({"plan_fingerprint": "fp", "state": "FINISHED",
                "wall_s": 3.0, "query_id": "q1"})
    assert idx.refresh_from(log.path) is True
    assert idx.lookup("fp")["samples"] == 2


def test_history_reader_retries_across_rotation(tmp_path, monkeypatch):
    """A read that straddles ``os.replace`` rotation must come back
    with one consistent generation of the file, never a torn mix: the
    reader compares the inode before/after and retries on the fresh
    file."""
    from spark_rapids_tpu.obs import history
    log = history.QueryHistoryLog(str(tmp_path), max_entries=100)
    for i in range(6):
        log.append({"query_id": f"old{i}"})
    real_open = open
    raced = {"done": False}

    def racing_open(path, *a, **kw):
        f = real_open(path, *a, **kw)
        if not raced["done"] and str(path) == log.path:
            raced["done"] = True
            # rotation swaps the file out while this reader holds the
            # old inode (rewrite + os.replace, same as _rotate_locked)
            tmp = log.path + ".tmp"
            with real_open(tmp, "w") as t:
                for i in range(3):
                    t.write(json.dumps({"query_id": f"new{i}"}) + "\n")
            os.replace(tmp, log.path)
        return f

    monkeypatch.setattr(history, "open", racing_open, raising=False)
    ids = [e["query_id"] for e in history.read_entries(log.path)]
    assert ids == ["new0", "new1", "new2"]
