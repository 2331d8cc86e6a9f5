"""TCP shuffle plane: in-process, cross-process, compressed, throttled.

Reference: the UCX transport module (UCX.scala:192-328 management port +
tag protocol, UCXShuffleTransport.scala:365-391 inflight throttle,
RapidsShuffleServer/Client) — multi-peer behavior is tested without a
cluster, as the reference does with mocked transports
(RapidsShuffleTestHelper.scala:26-95); here the network is real
(loopback) and the peer is a real second process.
"""
import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.exec.core import ExecCtx, host_to_device
from spark_rapids_tpu.host.batch import HostBatch, HostColumn
from spark_rapids_tpu.shuffle.tcp import (TcpShuffleTransport, fetch_remote,
                                          remote_partition_sizes)

SCHEMA = T.Schema([T.StructField("x", T.IntegerType()),
                   T.StructField("s", T.StringType())])


def _hb(vals, tags):
    return HostBatch(
        [HostColumn(np.asarray(vals, np.int32), np.ones(len(vals), bool),
                    T.IntegerType()),
         HostColumn(np.asarray(tags, object), np.ones(len(tags), bool),
                    T.StringType())], SCHEMA)


def _rows(batches):
    from spark_rapids_tpu.exec.core import device_to_host
    out = []
    for b in batches:
        hb = device_to_host(b)
        out.extend(zip(*[c.to_list() for c in hb.columns]))
    return out


@pytest.mark.parametrize("codec", ["none", "lz4"])
def test_tcp_roundtrip_in_process(codec):
    conf = TpuConf({"spark.rapids.shuffle.compression.codec": codec})
    with ExecCtx(backend="device", conf=conf) as ctx:
        t = TcpShuffleTransport(conf, ctx)
        try:
            for m in range(3):
                t.write_partition(9, m, 0, host_to_device(
                    _hb([m, m + 10], [f"a{m}", f"b{m}"])))
            t.write_partition(9, 0, 1, host_to_device(_hb([99], ["z"])))
            sizes, batch_sizes = remote_partition_sizes(t.address, 9)
            assert set(sizes) == {0, 1} and len(batch_sizes[0]) == 3
            got = _rows(fetch_remote(t.address, 9, 0))
            assert sorted(got) == sorted(
                [(0, "a0"), (10, "b0"), (1, "a1"), (11, "b1"),
                 (2, "a2"), (12, "b2")])
            # sliced fetch: only map batches [1, 3)
            got = _rows(fetch_remote(t.address, 9, 0, lo=1, hi=3))
            assert sorted(got) == sorted(
                [(1, "a1"), (11, "b1"), (2, "a2"), (12, "b2")])
        finally:
            t.close()


def test_tcp_inflight_throttle():
    """A tiny window forces server/client acks mid-stream; every frame
    still arrives intact (reference inflight-bytes throttle)."""
    conf = TpuConf({"spark.rapids.shuffle.tcp.maxBytesInFlight": 512})
    with ExecCtx(backend="device", conf=conf) as ctx:
        t = TcpShuffleTransport(conf, ctx)
        try:
            for m in range(8):
                t.write_partition(1, m, 0, host_to_device(
                    _hb(list(range(m * 50, m * 50 + 50)), ["s"] * 50)))
            # conf-driven window via the transport's own client entry
            got = _rows(t.fetch_from(t.address, 1, 0))
            assert len(got) == 400
            assert sorted(r[0] for r in got) == list(range(400))
        finally:
            t.close()


CHILD_SCRIPT = textwrap.dedent("""
    import sys, json
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.conf import TpuConf
    from spark_rapids_tpu.exec.core import ExecCtx, host_to_device
    from spark_rapids_tpu.host.batch import HostBatch, HostColumn
    from spark_rapids_tpu.shuffle.tcp import TcpShuffleTransport

    SCHEMA = T.Schema([T.StructField("x", T.IntegerType()),
                       T.StructField("s", T.StringType())])
    conf = TpuConf({})
    ctx = ExecCtx(backend="device", conf=conf)
    t = TcpShuffleTransport(conf, ctx)
    for m in range(4):
        hb = HostBatch(
            [HostColumn(np.arange(m * 10, m * 10 + 10, dtype=np.int32),
                        np.ones(10, bool), T.IntegerType()),
             HostColumn(np.asarray([f"m{m}r{i}" for i in range(10)],
                                   object), np.ones(10, bool),
                        T.StringType())], SCHEMA)
        t.write_partition(5, m, m % 2, host_to_device(hb))
    print(json.dumps({"port": t.address[1]}), flush=True)
    sys.stdin.readline()   # parent closes stdin when done
    t.close()
""")


def test_tcp_cross_process_fetch():
    """A REAL second process serves its map output over the wire — the
    multi-host DCN-plane shape (map side stays resident at the producer,
    reduce side pulls, RapidsShuffleClient/Server)."""
    p = subprocess.Popen([sys.executable, "-c", CHILD_SCRIPT],
                         stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True)
    try:
        line = p.stdout.readline()
        port = json.loads(line)["port"]
        addr = ("127.0.0.1", port)
        sizes, _ = remote_partition_sizes(addr, 5)
        assert set(sizes) == {0, 1}
        even = _rows(fetch_remote(addr, 5, 0))
        odd = _rows(fetch_remote(addr, 5, 1))
        assert sorted(r[0] for r in even) == [x for m in (0, 2)
                                              for x in range(m * 10,
                                                             m * 10 + 10)]
        assert sorted(r[0] for r in odd) == [x for m in (1, 3)
                                             for x in range(m * 10,
                                                            m * 10 + 10)]
        assert ("m2r3" in [r[1] for r in even])
    finally:
        try:
            p.stdin.close()
        except OSError:
            pass
        p.wait(timeout=30)


def test_tcp_transport_via_reflection_conf():
    """The engine loads the TCP transport through transport.class and a
    shuffle query runs through it end to end."""
    from spark_rapids_tpu.exec.core import collect_host
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.expr.core import col
    from spark_rapids_tpu.session import TpuSession

    s = TpuSession({
        "spark.rapids.shuffle.transport.class":
            "spark_rapids_tpu.shuffle.tcp.TcpShuffleTransport"})
    schema = T.Schema([T.StructField("k", T.IntegerType()),
                       T.StructField("v", T.LongType())])
    rng = np.random.default_rng(11)
    df = s.from_pydict(
        {"k": [int(x) for x in rng.integers(0, 7, 300)],
         "v": list(range(300))}, schema, partitions=3, rows_per_batch=32)
    out = df.group_by("k").agg(Sum(col("v")).alias("sv"))
    dev = sorted(out.collect())
    ov, meta = out._overridden(quiet=True)
    assert dev == sorted(collect_host(meta.exec_node, s.conf))


def test_tcp_server_error_reaches_client():
    """A store failure mid-fetch surfaces as ShuffleFetchError with the
    real cause, not a connection reset (review finding)."""
    from spark_rapids_tpu.shuffle.tcp import ShuffleFetchError

    conf = TpuConf({})
    with ExecCtx(backend="device", conf=conf) as ctx:
        t = TcpShuffleTransport(conf, ctx)
        try:
            def boom(*a, **k):
                raise RuntimeError("store exploded")
                yield  # pragma: no cover - generator shape
            t.fetch_partition_serialized = boom
            with pytest.raises(ShuffleFetchError, match="store exploded"):
                list(fetch_remote(t.address, 1, 0))
        finally:
            t.close()


def test_tcp_window_negotiated_from_client():
    """Server throttles at the client-declared window even when its own
    conf differs (review finding: mismatch used to deadlock)."""
    conf = TpuConf({"spark.rapids.shuffle.tcp.maxBytesInFlight": 1 << 20})
    with ExecCtx(backend="device", conf=conf) as ctx:
        t = TcpShuffleTransport(conf, ctx)
        try:
            for m in range(8):
                t.write_partition(2, m, 0, host_to_device(
                    _hb(list(range(m * 30, m * 30 + 30)), ["t"] * 30)))
            # client asks for a much smaller window than the server conf
            got = _rows(fetch_remote(t.address, 2, 0, inflight_limit=256))
            assert sorted(r[0] for r in got) == list(range(240))
        finally:
            t.close()


def test_tcp_fetch_timeout_on_stalled_peer():
    """A peer that accepts the connection but never responds raises
    ShuffleFetchError within the timeout, not a forever-hang (reference
    fetch timeout, spark.network.timeout via RapidsShuffleIterator)."""
    import socket as _socket
    import time
    from spark_rapids_tpu.shuffle.tcp import ShuffleFetchError

    srv = _socket.socket(_socket.AF_INET, _socket.SOCK_STREAM)
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    addr = srv.getsockname()
    try:
        t0 = time.monotonic()
        with pytest.raises(ShuffleFetchError, match="stalled"):
            list(fetch_remote(addr, 1, 0, timeout=1.5))
        assert time.monotonic() - t0 < 30
    finally:
        srv.close()


MAP_SIDE_SCRIPT = textwrap.dedent("""
    import json, sys
    import numpy as np
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.conf import TpuConf
    from spark_rapids_tpu.exec.core import ExecCtx
    from spark_rapids_tpu.exec.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.exec.partitioning import HashPartitioning
    from spark_rapids_tpu.expr.core import col
    from spark_rapids_tpu.session import TpuSession

    # the MAP SIDE of a real plan: scan -> filter -> hash exchange,
    # executed here and SERVED to the remote reduce process
    s = TpuSession({"spark.rapids.shuffle.transport.class":
                    "spark_rapids_tpu.shuffle.tcp.TcpShuffleTransport"})
    schema = T.Schema([T.StructField("k", T.IntegerType(), True),
                       T.StructField("v", T.LongType(), True)])
    rng = np.random.default_rng(3)
    df = s.from_pydict({"k": rng.integers(0, 13, 500).astype(np.int32),
                        "v": rng.integers(0, 1000, 500).astype(np.int64)},
                       schema, partitions=3) \
        .where(col("v") >= 100)
    ov, meta = df._overridden(quiet=True)
    ex = ShuffleExchangeExec(HashPartitioning([col("k")], 4),
                             meta.exec_node, shuffle_id=777)
    ctx = ExecCtx(backend="device", conf=s.conf)
    transport = ex._shuffled(ctx)          # runs the map side
    print(json.dumps({"port": transport.address[1]}), flush=True)
    sys.stdin.readline()
    transport.close()
""")


def test_distributed_query_two_processes():
    """VERDICT r3 item 7: a full query executes distributed — map tasks
    (scan -> filter -> hash partition) in process A served over TCP,
    reduce tasks (group-by aggregate) in process B, equal to the
    single-process run of the same plan (reference
    RapidsShuffleInternalManager.scala:285-345 write/read split)."""
    import subprocess
    import sys as _sys

    import jax

    from spark_rapids_tpu.cluster.exec import WorkerShuffleReaderExec
    from spark_rapids_tpu.exec.aggregate import HashAggregateExec
    from spark_rapids_tpu.expr.aggregates import CountStar, Sum
    from spark_rapids_tpu.expr.core import col
    from spark_rapids_tpu.session import TpuSession

    schema = T.Schema([T.StructField("k", T.IntegerType(), True),
                       T.StructField("v", T.LongType(), True)])

    import tempfile
    # stderr goes to a FILE, not a pipe: XLA floods stderr with
    # multi-KB warnings (e.g. AOT-cache machine-feature mismatches)
    # and an unread 64KB stderr pipe blocks the child BEFORE it prints
    # the port line — deadlocking the whole test
    err = tempfile.TemporaryFile(mode="w+")
    p = subprocess.Popen([_sys.executable, "-c", MAP_SIDE_SCRIPT],
                         stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                         stderr=err, text=True)
    try:
        line = p.stdout.readline()
        err.seek(0)
        assert line, err.read()
        port = json.loads(line)["port"]

        # reduce side: remote scan of the peer's map output -> final agg
        s = TpuSession({})
        reader = WorkerShuffleReaderExec(
            777, schema, [[(("127.0.0.1", port), pid, 0, None)]
                          for pid in range(4)])
        agg = HashAggregateExec(
            [col("k")], [col("k"), Sum(col("v")).alias("sv"),
                         CountStar().alias("cnt")], reader)
        with ExecCtx(backend="device", conf=s.conf) as ctx:
            rows = []
            from spark_rapids_tpu.exec.core import device_to_host, \
                _rows_from_host
            for pid in range(agg.num_partitions(ctx)):
                for b in agg.partition_iter(ctx, pid):
                    rows.extend(_rows_from_host(device_to_host(b)))

        # oracle: same data + plan in ONE process
        import numpy as np
        rng = np.random.default_rng(3)
        want = TpuSession({}).from_pydict(
            {"k": rng.integers(0, 13, 500).astype(np.int32),
             "v": rng.integers(0, 1000, 500).astype(np.int64)},
            schema, partitions=3) \
            .where(col("v") >= 100).group_by("k") \
            .agg(Sum(col("v")).alias("sv"), CountStar().alias("cnt")) \
            .collect()
        assert sorted(rows) == sorted(want) and len(rows) == 13
    finally:
        try:
            p.stdin.close()
        except OSError:
            pass
        p.wait(timeout=30)
