"""Cluster runtime tests: driver/worker multi-process execution over the
DCN shuffle plane (spark_rapids_tpu/cluster/).

``cluster.mode=off`` must be inert (no tagging, no subprocesses, no
counter movement), and ``local[N]`` must return EXACTLY the rows the
single-process engine returns — proved here for a pydict group-by with
a hand-computed oracle and for TPC-H over split multi-file tables (a
single-file sf0.01 scan plans shuffle-free, so the tables are split
exactly like tests/test_recovery_chaos.py does).  Worker death mid-query
is seeded with the ``cluster.worker.dead`` fault (a REAL SIGKILL of the
worker subprocess, detected through the failed fetch like any crash)
and must recompute only the lost map outputs on survivors — same exact
rows, nonzero recovery counters.  Reference intent: executor loss feeds
FetchFailed -> DAGScheduler map-stage resubmission; here the control
plane is cluster/rpc.py and the data plane the existing TCP shuffle
servers.
"""
import os
import socket
import threading
import time

import numpy as np
import pytest

from spark_rapids_tpu import TpuSession
from spark_rapids_tpu import types as T
from spark_rapids_tpu.bench.runner import run_benchmark
from spark_rapids_tpu.bench.tpch_gen import generate_tpch
from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.expr.aggregates import CountStar, Sum
from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.obs.registry import get_registry

SCHEMA = T.Schema([
    T.StructField("k", T.IntegerType(), True),
    T.StructField("v", T.LongType(), True),
])


def _mkdata(n=400, seed=7):
    rng = np.random.default_rng(seed)
    return {"k": [int(x) for x in rng.integers(0, 13, n)],
            "v": [int(x) for x in rng.integers(-1000, 1000, n)]}


def _walk(node):
    yield node
    for c in node.children:
        yield from _walk(c)


# ---------------------------------------------------------------------------
# control-plane RPC (no subprocesses)
# ---------------------------------------------------------------------------

def _echo(payload, blob):
    return {"echo": payload}, blob[::-1]


def test_rpc_roundtrip_with_compressed_blob():
    from spark_rapids_tpu.cluster.rpc import RpcServer, rpc_call
    srv = RpcServer({"echo": _echo}, codec_name="lz4")
    try:
        conf = TpuConf(
            {"spark.rapids.cluster.rpc.compression.codec": "lz4"})
        blob = b"spark-rapids-tpu " * 4096  # compressible
        reply, rblob = rpc_call(srv.address, "echo", {"x": 1},
                                blob=blob, conf=conf)
        assert reply == {"echo": {"x": 1}}
        assert rblob == blob[::-1]
        assert srv.metrics["rpc_requests"] == 1
        # the wire carries COMPRESSED bytes (checksummed post-codec)
        from spark_rapids_tpu.cluster.rpc import _pack_blob
        wire, fields = _pack_blob(blob, "lz4")
        assert len(wire) < len(blob) and fields["codec"] == "lz4"
    finally:
        srv.close()


def test_rpc_handler_error_not_retried():
    from spark_rapids_tpu.cluster.rpc import (RpcHandlerError, RpcServer,
                                              rpc_call)

    def boom(payload, blob):
        raise ValueError("bad op arg")

    srv = RpcServer({"boom": boom})
    try:
        with pytest.raises(RpcHandlerError, match="bad op arg"):
            rpc_call(srv.address, "boom")
        assert srv.metrics["rpc_errors"] == 1
        with pytest.raises(RpcHandlerError, match="unknown rpc op"):
            rpc_call(srv.address, "nope")
    finally:
        srv.close()


def test_rpc_dead_peer_raises_after_retries():
    from spark_rapids_tpu.cluster.rpc import RpcError, rpc_call
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    before = get_registry().snapshot()
    with pytest.raises(RpcError, match="failed after 3 attempts"):
        rpc_call(("127.0.0.1", port), "ping", retries=2, timeout=2.0)
    d = get_registry().delta(before)["counters"]
    assert d.get("cluster.rpc.retries", 0) >= 3, d


def test_rpc_drop_fault_absorbed_by_retries():
    from spark_rapids_tpu.cluster.rpc import RpcServer, rpc_call
    from spark_rapids_tpu.faults import FaultRegistry
    srv = RpcServer({"echo": _echo})
    try:
        faults = FaultRegistry.from_conf(
            {"spark.rapids.test.faults": "cluster.rpc.drop:drop,times=2"})
        before = get_registry().snapshot()
        reply, _ = rpc_call(srv.address, "echo", {"ok": 1}, faults=faults)
        assert reply == {"echo": {"ok": 1}}
        d = get_registry().delta(before)["counters"]
        assert d.get("cluster.rpc.dropped", 0) == 2, d
    finally:
        srv.close()


def test_rpc_retry_with_same_idem_key_executes_once():
    """A dropped-then-retried non-idempotent op must execute ONCE.

    ``rpc_call`` mints one ``(caller, seq)`` key per LOGICAL call and
    reuses it across retry attempts; the server's replay cache answers
    the retry with the recorded reply instead of re-running the
    handler.  This drives ``_call_once`` directly with the same key —
    byte-for-byte what the retry loop sends after a reply is lost in
    flight — and then with a fresh key to prove dedup doesn't bleed
    across logical calls."""
    from spark_rapids_tpu.cluster.rpc import RpcServer, _call_once
    runs = {"n": 0}

    def run_fragment(payload, blob):
        runs["n"] += 1
        return {"ran": runs["n"], "frag": payload.get("frag")}, b"out"

    srv = RpcServer({"run_fragment": run_fragment})
    try:
        before = get_registry().snapshot()
        host, port = srv.address
        idem = {"caller": "test-caller.e1", "seq": 7}
        first, blob1 = _call_once(host, port, "run_fragment",
                                  {"frag": 3}, b"", None, 10.0,
                                  idem=idem)
        # the reply "was lost": the client retries the SAME logical call
        second, blob2 = _call_once(host, port, "run_fragment",
                                   {"frag": 3}, b"", None, 10.0,
                                   idem=idem)
        assert runs["n"] == 1, "retried run_fragment executed twice"
        assert second == first and blob2 == blob1 == b"out"
        assert srv.metrics["rpc_replays_deduped"] == 1
        d = get_registry().delta(before)["counters"]
        assert d.get("cluster.rpc.replays_deduped", 0) == 1, d
        # a NEW logical call (fresh seq) is not deduped
        third, _ = _call_once(host, port, "run_fragment", {"frag": 4},
                              b"", None, 10.0,
                              idem={"caller": "test-caller.e1",
                                    "seq": 8})
        assert runs["n"] == 2 and third["frag"] == 4
        # a retried call whose handler FAILED replays the error too —
        # the failure side effect also happened exactly once
        from spark_rapids_tpu.cluster.rpc import RpcHandlerError
        boom = {"caller": "test-caller.e1", "seq": 9}
        srv._handlers["kaboom"] = lambda p, b: (_ for _ in ()).throw(
            ValueError("no such fragment"))
        for _ in range(2):
            with pytest.raises(RpcHandlerError, match="no such fragment"):
                _call_once(host, port, "kaboom", {}, b"", None, 10.0,
                           idem=boom)
        assert srv.metrics["rpc_errors"] == 1
        assert srv.metrics["rpc_replays_deduped"] == 2
    finally:
        srv.close()


def test_parse_cluster_mode():
    from spark_rapids_tpu.cluster import parse_cluster_mode
    assert parse_cluster_mode(TpuConf({})) == 0
    assert parse_cluster_mode(
        TpuConf({"spark.rapids.cluster.mode": "local[3]"})) == 3


# ---------------------------------------------------------------------------
# off-mode inertness
# ---------------------------------------------------------------------------

def test_cluster_off_is_inert():
    s = TpuSession()
    df = s.from_pydict(_mkdata(), SCHEMA, partitions=3, rows_per_batch=64)
    agg = df.group_by("k").agg(Sum(col("v")).alias("sv"))
    before = get_registry().snapshot()
    rows = agg.collect()
    assert rows
    # no driver spawned, no plan node tagged, no cluster counter moved
    assert s._cluster() is None
    _, meta = agg._overridden(quiet=True)
    assert not [n for n in _walk(meta.exec_node)
                if getattr(n, "_cluster_ok", False)]
    d = get_registry().delta(before)["counters"]
    assert not [k for k in d if k.startswith("cluster")], d
    s.shutdown()


# ---------------------------------------------------------------------------
# local[2]: exactness, codec negotiation, clean teardown
# ---------------------------------------------------------------------------

def _cluster_threads():
    return [t.name for t in threading.enumerate()
            if t.name in ("tpu-cluster-monitor", "tpu-cluster-rpc")]


def test_local2_groupby_exact_lz4_and_clean_shutdown():
    """One worker pool proves three things: a sharded hash shuffle
    returns EXACTLY the single-process rows, the shuffle codec is
    negotiated across real process boundaries (driver fetches lz4
    frames from worker-owned stores), and ``shutdown(drain=True)``
    leaves zero orphan worker processes or cluster threads."""
    data = _mkdata()
    agg_cols = (Sum(col("v")).alias("sv"), CountStar().alias("c"))
    s0 = TpuSession()
    df0 = s0.from_pydict(data, SCHEMA, partitions=3, rows_per_batch=64)
    want = sorted(df0.group_by("k").agg(*agg_cols).collect())
    s0.shutdown()

    s = TpuSession({"spark.rapids.cluster.mode": "local[2]",
                    "spark.rapids.shuffle.compression.codec": "lz4"})
    df = s.from_pydict(data, SCHEMA, partitions=3, rows_per_batch=64)
    before = get_registry().snapshot()
    got = sorted(df.group_by("k").agg(*agg_cols).collect())
    assert got == want
    d = get_registry().delta(before)["counters"]
    assert d.get("cluster.shuffles_clustered", 0) >= 1, d
    assert d.get("cluster.fragments_dispatched", 0) >= 2, d
    # codec negotiation happened on the driver's reduce-side pulls
    assert d.get("shuffle.fetch.codec.lz4", 0) >= 1, d

    cluster = s._cluster()
    handles = cluster.workers()
    assert len(handles) == 2 and all(h.alive for h in handles)
    s.shutdown(drain=True)
    for h in handles:
        assert h.proc.poll() is not None, \
            f"worker {h.worker_id} still running after shutdown"
    deadline = time.monotonic() + 5.0
    while _cluster_threads() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not _cluster_threads()


# ---------------------------------------------------------------------------
# TPC-H over the worker pool (slow: worker pools recompile per query on a
# cold process; tier-1 runs the same paths on the in-memory group-by)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tpch_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("tpch_cluster") / "sf001")
    generate_tpch(d, sf=0.01)
    _split_tables(d, ("lineitem", "orders", "customer"), parts=4)
    return d


def _split_tables(data_dir: str, tables, parts: int) -> None:
    """Re-write each table as ``parts`` parquet files so its scan is
    multi-partition and aggregations above it get shuffle exchanges."""
    import pyarrow.parquet as pq
    for table in tables:
        path = os.path.join(data_dir, table, "part-0.parquet")
        t = pq.read_table(path)
        step = -(-t.num_rows // parts)
        for i in range(parts):
            pq.write_table(t.slice(i * step, step),
                           os.path.join(data_dir, table,
                                        f"part-{i}.parquet"))


@pytest.mark.slow
def test_tpch_local2_exact(tpch_dir):
    r = run_benchmark(tpch_dir, 0.01, ["q3"], verify=True, generate=False,
                      suite="tpch",
                      session_conf={
                          "spark.rapids.cluster.mode": "local[2]"})[0]
    assert "error" not in r, r
    assert r["ok"], r
    reg = (r["observability"].get("registry") or {}).get("counters") or {}
    assert reg.get("cluster.shuffles_clustered", 0) >= 1, reg
    assert reg.get("cluster.fragments_dispatched", 0) >= 2, reg


@pytest.mark.slow
@pytest.mark.parametrize("query", ["q6", "q12", "q18"])
def test_tpch_local2_exact_slow(tpch_dir, query):
    r = run_benchmark(tpch_dir, 0.01, [query], verify=True, generate=False,
                      suite="tpch",
                      session_conf={
                          "spark.rapids.cluster.mode": "local[2]"})[0]
    assert "error" not in r, r
    assert r["ok"], r


_CHAOS_CONF = {
    "spark.rapids.cluster.mode": "local[2]",
    # SIGKILL one worker on the driver's first reduce-side pull; the
    # death is DETECTED via the real refused reconnect, so keep the
    # transient ladder short or the test spends its time backing off
    "spark.rapids.test.faults": "cluster.worker.dead:dead,times=1",
    "spark.rapids.shuffle.tcp.maxRetries": 1,
    "spark.rapids.shuffle.tcp.retryWaitSeconds": 0.1,
}


def test_local2_worker_death_recovers_exact():
    """A worker SIGKILLed on the driver's first reduce-side pull:
    lineage recovery recomputes the lost map outputs on the survivor
    and the rows are still exactly the single-process rows."""
    data = _mkdata()

    def rows(s):
        df = s.from_pydict(data, SCHEMA, partitions=3, rows_per_batch=64)
        return sorted(df.group_by("k").agg(Sum(col("v")).alias("sv"))
                      .collect())
    s0 = TpuSession()
    want = rows(s0)
    s0.shutdown()
    s = TpuSession(dict(_CHAOS_CONF))
    try:
        before = get_registry().snapshot()
        got = rows(s)
        d = get_registry().delta(before)["counters"]
        assert got == want
        assert d.get("faults.injected.cluster.worker.dead", 0) >= 1, d
        assert d.get("cluster_workers_lost", 0) >= 1, d
        assert d.get("stage_recomputes", 0) > 0, d
        assert d.get("map_outputs_recomputed", 0) > 0, d
    finally:
        s.shutdown(drain=True)


# ---------------------------------------------------------------------------
# membership churn hygiene: repeated scale-up/down leaks nothing
# ---------------------------------------------------------------------------

def _open_fds():
    return len(os.listdir("/proc/self/fd"))


def test_membership_churn_leaks_nothing():
    """Three add/remove cycles on a live pool: every retired worker
    process is reaped, every per-worker io thread exits, and the
    driver's fd table returns to its pre-churn size (RPC sockets,
    stdio pipes, shuffle connections all closed)."""
    s = TpuSession({"spark.rapids.cluster.mode": "local[2]",
                    "spark.rapids.cluster.maxWorkers": "8"})
    df = s.from_pydict(_mkdata(), SCHEMA, partitions=3, rows_per_batch=64)
    agg = df.group_by("k").agg(Sum(col("v")).alias("sv"))
    want = sorted(agg.collect())
    drv = s._cluster()
    fds0 = _open_fds()
    retired = []
    for _ in range(3):
        wid = drv.add_worker()
        assert sorted(agg.collect()) == want
        drv.remove_worker(wid, drain=True)
        retired.append(drv.worker_by_id(wid))
    # processes reaped (no zombies), io threads joined
    for h in retired:
        assert h.proc.poll() is not None, \
            f"churned worker {h.worker_id} still running"
        assert h.io_thread is None or not h.io_thread.is_alive(), \
            f"io thread for {h.worker_id} leaked"
    assert not [t.name for t in threading.enumerate()
                if t.name.startswith("tpu-cluster-io-")
                and t.name.split("-")[-1] in
                [h.worker_id for h in retired]]
    # fd table settles back to the steady-state size (allow slack for
    # lazily-opened shuffle client connections to the LIVE workers)
    deadline = time.monotonic() + 5.0
    while _open_fds() > fds0 + 4 and time.monotonic() < deadline:
        time.sleep(0.1)
    assert _open_fds() <= fds0 + 4, \
        f"fd leak across churn: {fds0} -> {_open_fds()}"
    assert sorted(agg.collect()) == want
    handles = drv.workers()
    s.shutdown(drain=True)
    for h in handles:
        assert h.proc.poll() is not None, \
            f"worker {h.worker_id} still running after shutdown"


@pytest.mark.slow
def test_tpch_worker_death_recovers_exact(tpch_dir):
    """q18 with a worker SIGKILLed mid-query: lineage recovery must
    recompute the lost map outputs on the survivor and still return
    EXACT oracle rows."""
    r = run_benchmark(tpch_dir, 0.01, ["q18"], verify=True, generate=False,
                      suite="tpch", session_conf=_CHAOS_CONF)[0]
    assert "error" not in r, r
    assert r["ok"], r
    reg = (r["observability"].get("registry") or {}).get("counters") or {}
    assert reg.get("faults.injected.cluster.worker.dead", 0) >= 1, reg
    assert reg.get("cluster_workers_lost", 0) >= 1, reg
    assert reg.get("stage_recomputes", 0) > 0, reg
    assert reg.get("map_outputs_recomputed", 0) > 0, reg
