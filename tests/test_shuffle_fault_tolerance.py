"""Fault-tolerant shuffle plane: seeded chaos through the REAL transport.

Drives spark_rapids_tpu/faults.py injection points end to end: resets,
stalls, corrupted frames, server error frames, store failures, and
simulated HBM OOM — all deterministic (seeded, conf-driven), all on CPU,
no mocks.  Reference intent: the UCX client survives transport failures
by surfacing them to stage retry (RapidsShuffleIterator); here the
transport-level retry ladder (shuffle/retry.py) must return EXACTLY the
oracle batches — no duplicates, no drops, no hang — under every fault.
"""
import socket
import threading
import time

import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.exec.core import ExecCtx, device_to_host, host_to_device
from spark_rapids_tpu.faults import FaultRegistry
from spark_rapids_tpu.host.batch import HostBatch, HostColumn
from spark_rapids_tpu.shuffle.retry import (fetch_remote_with_retry,
                                            remote_partition_sizes_with_retry,
                                            reset_circuit_breakers)
from spark_rapids_tpu.shuffle.tcp import (ShuffleFetchError,
                                          ShuffleTransportError,
                                          TcpShuffleServer,
                                          TcpShuffleTransport, fetch_remote,
                                          remote_partition_sizes)

SCHEMA = T.Schema([T.StructField("x", T.IntegerType())])


@pytest.fixture(autouse=True)
def _fresh_breakers():
    # per-peer circuit state is process-global by design; tests must not
    # inherit failures from each other
    reset_circuit_breakers()
    yield
    reset_circuit_breakers()


def _hb(vals):
    return HostBatch([HostColumn(np.asarray(vals, np.int32),
                                 np.ones(len(vals), bool),
                                 T.IntegerType())], SCHEMA)


def _rows(batches):
    out = []
    for b in batches:
        out.extend(device_to_host(b).columns[0].to_list())
    return out


def _fill(transport, shuffle_id=1, part_id=0, n_batches=6):
    """n map batches of 2 rows each; returns the oracle row multiset."""
    oracle = []
    for m in range(n_batches):
        transport.write_partition(shuffle_id, m, part_id,
                                  host_to_device(_hb([m, m + 100])))
        oracle += [m, m + 100]
    return sorted(oracle)


def _transport(ctx, conf):
    return TcpShuffleTransport(conf, ctx)


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_fault_registry_inert_when_unset():
    """With spark.rapids.test.faults unset nothing is built: every
    injection site is one is-None check."""
    assert FaultRegistry.from_conf(TpuConf({})) is None
    assert FaultRegistry.from_conf(None) is None
    assert FaultRegistry.from_conf({}) is None


def test_fault_registry_parse_and_triggers():
    reg = FaultRegistry("tcp.server.frame:corrupt,nth=2,times=2,part=0;"
                        "store.fetch:error", seed=7)
    # filter mismatch never consumes the trigger
    assert reg.check("tcp.server.frame", part=1, frame=0) is None
    assert reg.check("tcp.server.frame", part=0, frame=0) is None  # hit 1
    act = reg.check("tcp.server.frame", part=0, frame=1)           # hit 2
    assert act is not None and act.action == "corrupt"
    assert reg.check("tcp.server.frame", part=0, frame=2) is not None
    assert reg.check("tcp.server.frame", part=0, frame=3) is None  # spent
    assert reg.check("store.fetch", shuffle=9).action == "error"
    assert reg.fired_count() == 3
    with pytest.raises(ValueError):
        FaultRegistry("noaction")


def test_fault_registry_deterministic_seeding():
    a = FaultRegistry("tcp.server.frame:corrupt,p=0.5,times=0", seed=3)
    b = FaultRegistry("tcp.server.frame:corrupt,p=0.5,times=0", seed=3)
    fires_a = [a.check("tcp.server.frame", frame=i) is not None
               for i in range(64)]
    fires_b = [b.check("tcp.server.frame", frame=i) is not None
               for i in range(64)]
    assert fires_a == fires_b and any(fires_a) and not all(fires_a)


# ---------------------------------------------------------------------------
# wire hardening (satellites)
# ---------------------------------------------------------------------------

def test_raw_connection_errors_wrapped():
    """A dead peer surfaces as ShuffleFetchError with address context,
    never a raw ConnectionError/OSError (satellite bugfix)."""
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.bind(("127.0.0.1", 0))
    dead = srv.getsockname()
    srv.close()  # nothing listening now
    with pytest.raises(ShuffleTransportError, match=r"failed:"):
        list(fetch_remote(dead, 1, 0, timeout=2))
    with pytest.raises(ShuffleTransportError, match=r"failed:"):
        remote_partition_sizes(dead, 1, timeout=2)


def test_server_caps_request_frames():
    """A desynced peer declaring a multi-GiB *request* frame is dropped
    at the 64 KiB control-frame cap — the server neither allocates nor
    wedges, and keeps serving well-formed peers (satellite bugfix)."""
    conf = TpuConf({})
    with ExecCtx(backend="device", conf=conf) as ctx:
        t = _transport(ctx, conf)
        try:
            oracle = _fill(t)
            evil = socket.create_connection(t.address, timeout=5)
            evil.settimeout(5)
            evil.sendall((1 << 40).to_bytes(8, "big"))
            assert evil.recv(1) == b""  # server hung up, no allocation
            evil.close()
            assert sorted(_rows(fetch_remote(t.address, 1, 0))) == oracle
        finally:
            t.close()


def test_checksum_negotiation_interop():
    """Old-style clients that advertise no checksum still get the
    unprefixed frames they expect; new clients get verified frames."""
    conf = TpuConf({})
    with ExecCtx(backend="device", conf=conf) as ctx:
        t = _transport(ctx, conf)
        try:
            oracle = _fill(t)
            assert sorted(_rows(fetch_remote(t.address, 1, 0,
                                             checksum=False))) == oracle
            assert sorted(_rows(fetch_remote(t.address, 1, 0,
                                             checksum=True))) == oracle
        finally:
            t.close()


# ---------------------------------------------------------------------------
# chaos: the retrying fetch under injected faults
# ---------------------------------------------------------------------------

_FAST_RETRY = {"spark.rapids.shuffle.tcp.retryWaitSeconds": 0.02}


def test_reset_mid_stream_resumes_exactly():
    """Kill the connection mid-stream; the retrying fetch reconnects
    and RESUMES at the delivered offset: exact oracle rows AND the
    server never re-sends a delivered frame (no dup, no drop)."""
    conf = TpuConf({"spark.rapids.test.faults":
                    "tcp.server.frame:reset,nth=3", **_FAST_RETRY})
    with ExecCtx(backend="device", conf=conf) as ctx:
        t = _transport(ctx, conf)
        try:
            oracle = _fill(t, n_batches=6)
            got = _rows(fetch_remote_with_retry(t.address, 1, 0, conf=conf))
            assert sorted(got) == oracle
            assert t.server_metrics["faults_injected"] == 1
            assert t.server_metrics["fetch_requests"] == 2
            # perfect resume: 6 batches -> exactly 6 data frames total
            assert t.server_metrics["data_frames_sent"] == 6
        finally:
            t.close()


def test_corrupt_frame_detected_and_retried():
    """A bit-flipped frame fails its negotiated CRC and surfaces as a
    retryable error at the frame boundary — never a poisoned Arrow
    deserialize; the retry delivers the oracle."""
    spec = {"spark.rapids.test.faults": "tcp.server.frame:corrupt,nth=2",
            **_FAST_RETRY}
    conf = TpuConf(spec)
    with ExecCtx(backend="device", conf=conf) as ctx:
        t = _transport(ctx, conf)
        try:
            oracle = _fill(t)
            with pytest.raises(ShuffleTransportError, match="corrupted"):
                list(fetch_remote(t.address, 1, 0))
        finally:
            t.close()
    # fresh transport, same seeded plan: this time through the ladder
    conf = TpuConf(spec)
    with ExecCtx(backend="device", conf=conf) as ctx:
        t = _transport(ctx, conf)
        try:
            oracle = _fill(t)
            got = _rows(fetch_remote_with_retry(t.address, 1, 0, conf=conf))
            assert sorted(got) == oracle
            assert t.server_metrics["faults_injected"] == 1
        finally:
            t.close()


def test_stalled_peer_times_out_then_succeeds():
    """A stalled peer trips the fetch deadline (not a forever-hang);
    the retry finds it recovered and completes."""
    conf = TpuConf({"spark.rapids.test.faults":
                    "tcp.server.frame:stall,seconds=3",
                    "spark.rapids.shuffle.tcp.timeoutSeconds": 0.5,
                    **_FAST_RETRY})
    with ExecCtx(backend="device", conf=conf) as ctx:
        t = _transport(ctx, conf)
        try:
            oracle = _fill(t)
            t0 = time.monotonic()
            got = _rows(fetch_remote_with_retry(t.address, 1, 0, conf=conf))
            assert sorted(got) == oracle
            assert time.monotonic() - t0 < 30
        finally:
            t.close()


def test_server_error_frame_retried():
    """A transient server-side failure (here: injected at the store
    read) reaches the client as a diagnosable error frame and the next
    attempt succeeds."""
    conf = TpuConf({"spark.rapids.test.faults": "store.fetch:error",
                    **_FAST_RETRY})
    with ExecCtx(backend="device", conf=conf) as ctx:
        t = _transport(ctx, conf)
        try:
            oracle = _fill(t)
            got = _rows(fetch_remote_with_retry(t.address, 1, 0, conf=conf))
            assert sorted(got) == oracle
            assert t.faults.fired_count("store.fetch") == 1
        finally:
            t.close()


def test_deterministic_chaos_plan_exact_oracle():
    """Acceptance: one seeded plan that resets the connection
    mid-stream AND corrupts a later frame; the retrying pull returns
    exactly the oracle batches — no dup, no drop, no hang."""
    conf = TpuConf({"spark.rapids.test.faults":
                    "tcp.server.frame:reset,nth=3,times=1;"
                    "tcp.server.frame:corrupt,nth=6,times=1",
                    "spark.rapids.test.faults.seed": 42, **_FAST_RETRY})
    with ExecCtx(backend="device", conf=conf) as ctx:
        t = _transport(ctx, conf)
        try:
            oracle = _fill(t, n_batches=6)
            t0 = time.monotonic()
            got = _rows(fetch_remote_with_retry(t.address, 1, 0, conf=conf))
            assert sorted(got) == oracle          # exact multiset
            assert len(got) == len(oracle)        # no dups slipped in
            assert t.faults.fired_count() == 2
            assert t.server_metrics["fetch_requests"] == 3
            assert time.monotonic() - t0 < 30
        finally:
            t.close()


def test_no_faults_no_extra_round_trips():
    """Acceptance: with faults disabled the retry layer is pass-through
    — one fetch request, one data frame per batch, nothing re-sent."""
    conf = TpuConf({})
    with ExecCtx(backend="device", conf=conf) as ctx:
        t = _transport(ctx, conf)
        try:
            oracle = _fill(t, n_batches=5)
            assert t.faults is None
            got = _rows(fetch_remote_with_retry(t.address, 1, 0, conf=conf))
            assert sorted(got) == oracle
            assert t.server_metrics == {"meta_requests": 0,
                                        "fetch_requests": 1,
                                        "data_frames_sent": 5,
                                        "bytes_sent":
                                            t.server_metrics["bytes_sent"],
                                        "faults_injected": 0,
                                        "traced_fetches": 0}
        finally:
            t.close()


def test_peer_restart_fetch_recovers():
    """The peer dies and comes back on the same port while the client
    backs off; the retrying fetch and metadata plane both recover."""
    conf = TpuConf({"spark.rapids.shuffle.tcp.retryWaitSeconds": 0.3,
                    "spark.rapids.shuffle.tcp.maxRetries": 6})
    with ExecCtx(backend="device", conf=conf) as ctx:
        t = _transport(ctx, conf)
        replacement = []
        try:
            oracle = _fill(t)
            host, port = t.address
            t._server.close()  # peer dies; its map output store survives

            def revive():
                time.sleep(0.6)
                replacement.append(TcpShuffleServer(t, bind=host, port=port))

            threading.Thread(target=revive, daemon=True).start()
            sizes, _ = remote_partition_sizes_with_retry(
                (host, port), 1, conf=conf)
            assert set(sizes) == {0}
            got = _rows(fetch_remote_with_retry((host, port), 1, 0,
                                                conf=conf))
            assert sorted(got) == oracle
        finally:
            for srv in replacement:
                srv.close()
            t.close()


def test_circuit_breaker_opens_and_fails_fast():
    """Repeated failures against one peer trip its breaker: the next
    fetch fails immediately with a diagnosable error instead of
    burning a fresh backoff ladder."""
    conf = TpuConf({"spark.rapids.test.faults":
                    "tcp.client.connect:reset,times=0",
                    "spark.rapids.shuffle.tcp.maxRetries": 2,
                    "spark.rapids.shuffle.tcp.circuitBreaker.maxFailures": 3,
                    "spark.rapids.shuffle.tcp.retryWaitSeconds": 0.01})
    faults = FaultRegistry.from_conf(conf)
    peer = ("127.0.0.1", 59999)  # never dialed: connect fault fires first
    with pytest.raises(ShuffleFetchError, match="giving up"):
        list(fetch_remote_with_retry(peer, 1, 0, conf=conf, faults=faults))
    t0 = time.monotonic()
    with pytest.raises(ShuffleFetchError, match="circuit breaker open"):
        list(fetch_remote_with_retry(peer, 1, 0, conf=conf, faults=faults))
    assert time.monotonic() - t0 < 1.0  # failed fast, no ladder
    # the metadata plane shares the same breaker
    with pytest.raises(ShuffleFetchError, match="circuit breaker open"):
        remote_partition_sizes_with_retry(peer, 1, conf=conf, faults=faults)


def test_circuit_breaker_half_open_probe_recovers():
    """After the cooldown one probe goes through; a healthy peer closes
    the breaker again."""
    conf = TpuConf({
        "spark.rapids.shuffle.tcp.maxRetries": 0,
        "spark.rapids.shuffle.tcp.circuitBreaker.maxFailures": 1,
        "spark.rapids.shuffle.tcp.circuitBreaker.resetSeconds": 0.2,
        "spark.rapids.shuffle.tcp.retryWaitSeconds": 0.01})
    with ExecCtx(backend="device", conf=conf) as ctx:
        t = _transport(ctx, conf)
        try:
            oracle = _fill(t)
            # one failure against THIS peer's breaker trips it
            bad = FaultRegistry("tcp.client.connect:reset,times=1")
            with pytest.raises(ShuffleFetchError):
                list(fetch_remote_with_retry(t.address, 1, 0, conf=conf,
                                             faults=bad))
            with pytest.raises(ShuffleFetchError, match="circuit breaker"):
                list(fetch_remote_with_retry(t.address, 1, 0, conf=conf))
            time.sleep(0.25)  # cooldown -> half-open probe succeeds
            got = _rows(fetch_remote_with_retry(t.address, 1, 0, conf=conf))
            assert sorted(got) == oracle
        finally:
            t.close()


# ---------------------------------------------------------------------------
# spill-path OOM injection
# ---------------------------------------------------------------------------

def test_injected_oom_recovered_by_spill_retry():
    """A simulated HBM OOM at dispatch drives the spill-retry loop:
    the catalog spills registered buffers and the dispatch succeeds on
    the retry (reference DeviceMemoryEventHandler.onAllocFailure)."""
    import jax.numpy as jnp
    from spark_rapids_tpu.memory.catalog import (BufferCatalog,
                                                 SpillPriority,
                                                 run_with_spill_retry)

    conf = TpuConf({"spark.rapids.test.faults": "memory.oom:oom"})
    cat = BufferCatalog(conf=conf)
    try:
        assert cat.faults is not None
        bid = cat.add_batch(host_to_device(_hb(list(range(64)))),
                            SpillPriority.SHUFFLE_OUTPUT)
        out = run_with_spill_retry(lambda a: jnp.sum(a),
                                   cat, jnp.arange(100))
        assert int(out) == 4950
        assert cat.faults.fired_count("memory.oom") == 1
        assert cat.metrics["device_spills"] >= 1
        assert cat.tier_of(bid) != "device"  # it really spilled
    finally:
        cat.close()


def test_injected_oom_exhausting_retries_raises():
    """An OOM that never clears (times=0) still terminates: the loop
    gives up after max_retries instead of spinning."""
    import jax.numpy as jnp
    from spark_rapids_tpu.memory.catalog import (BufferCatalog,
                                                 SpillPriority,
                                                 run_with_spill_retry)

    conf = TpuConf({"spark.rapids.test.faults": "memory.oom:oom,times=0"})
    cat = BufferCatalog(conf=conf)
    try:
        for i in range(8):
            cat.add_batch(host_to_device(_hb([i])),
                          SpillPriority.SHUFFLE_OUTPUT)
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            run_with_spill_retry(lambda a: jnp.sum(a), cat,
                                 jnp.arange(10), max_retries=2)
    finally:
        cat.close()


# ---------------------------------------------------------------------------
# end to end: a remote reduce-side pull through the exec layer
# ---------------------------------------------------------------------------

def test_remote_reader_exec_survives_chaos():
    """WorkerShuffleReaderExec (the reduce-side exec of the cluster
    path) pulls through the retrying fetch: a chaos plan on the serving
    transport is invisible to the query result."""
    from spark_rapids_tpu.cluster.exec import WorkerShuffleReaderExec

    serve_conf = TpuConf({"spark.rapids.test.faults":
                          "tcp.server.frame:reset,nth=2,times=1"})
    read_conf = TpuConf(_FAST_RETRY)
    with ExecCtx(backend="device", conf=serve_conf) as sctx:
        t = _transport(sctx, serve_conf)
        try:
            oracle = _fill(t, shuffle_id=7, n_batches=4)
            reader = WorkerShuffleReaderExec(
                7, SCHEMA, [[(t.address, 0, 0, None)]])
            with ExecCtx(backend="device", conf=read_conf) as rctx:
                got = []
                for b in reader.partition_iter(rctx, 0):
                    got.extend(device_to_host(b).columns[0].to_list())
            assert sorted(got) == oracle
            assert t.server_metrics["faults_injected"] == 1
        finally:
            t.close()
