"""Query lifecycle control plane suite: deadlines, cooperative
cancellation, admission control, graceful shutdown.

The invariant under test is the one the reference gets from Spark's
task-kill machinery (TaskContext.isInterrupted + GpuSemaphore releasing
the device for killed tasks): a cancelled or deadline-exceeded query
unwinds through the SAME finally blocks as a successful one, so nothing
leaks — the DeviceSemaphore returns to full capacity, spilled files are
unlinked, parked spillable batches are closed, and the terminal
QueryCancelled / QueryDeadlineExceeded is never swallowed by the OOM
split-and-retry scope, the shuffle fetch ladder, or stage recovery.

The integration half cancels TPC-H q3 mid-flight under the PR-1/PR-3
chaos storm (peer death + spilled-output corruption + tiny budgets), so
cancellation lands while retries, recovery and spill I/O are all in
motion — the worst case for a leak, not the best.
"""
import os
import socket
import threading
import time

import pytest

from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.exec.lifecycle import (ADMITTED, CANCELLED,
                                             DEADLINE_EXCEEDED, FINISHED,
                                             RUNNING, AdmissionController,
                                             QueryCancelled,
                                             QueryDeadlineExceeded,
                                             QueryLifecycle, QueryRejected)
from spark_rapids_tpu.obs.registry import get_registry


def _counter_delta(before: dict, name: str) -> float:
    return get_registry().delta(before)["counters"].get(name, 0)


# ---------------------------------------------------------------------------
# QueryLifecycle state machine
# ---------------------------------------------------------------------------

def test_state_machine_happy_path():
    lc = QueryLifecycle("q1")
    assert lc.state == ADMITTED
    lc.start()
    assert lc.state == RUNNING
    lc.check()  # no deadline, not cancelled: no-op
    assert lc.finish()
    assert lc.state == FINISHED
    # terminal is sticky: neither fail nor cancel moves it
    assert not lc.fail()
    assert not lc.cancel()
    assert lc.state == FINISHED


def test_cancel_idempotent_counts_once():
    before = get_registry().snapshot()
    lc = QueryLifecycle("q2")
    lc.start()
    assert lc.cancel("test")
    assert not lc.cancel("again")
    assert not lc.cancel("and again")
    assert lc.state == CANCELLED
    assert lc.cancel_event.is_set()
    assert _counter_delta(before, "queries_cancelled") == 1
    with pytest.raises(QueryCancelled, match="test"):
        lc.check()


def test_deadline_expires_at_check():
    before = get_registry().snapshot()
    lc = QueryLifecycle("q3", timeout=0.02)
    lc.start()
    time.sleep(0.05)
    with pytest.raises(QueryDeadlineExceeded):
        lc.check()
    assert lc.state == DEADLINE_EXCEEDED
    assert lc.cancel_event.is_set()
    # a cancel after expiry is a no-op and must not double-count
    assert not lc.cancel()
    assert _counter_delta(before, "queries_deadline_exceeded") == 1
    assert _counter_delta(before, "queries_cancelled") == 0


def test_deadline_clock_starts_at_start_not_admission():
    lc = QueryLifecycle("q4", timeout=5.0)
    assert lc.remaining() is None      # not started: no deadline yet
    lc.start()
    rem = lc.remaining()
    assert rem is not None and 4.0 < rem <= 5.0


def test_from_conf_tighter_of_conf_and_call():
    conf = TpuConf({"spark.rapids.sql.queryTimeout": 5.0})
    assert QueryLifecycle.from_conf("a", conf).timeout == 5.0
    assert QueryLifecycle.from_conf("b", conf, timeout=1.0).timeout == 1.0
    assert QueryLifecycle.from_conf("c", conf, timeout=9.0).timeout == 5.0
    assert QueryLifecycle.from_conf("d", TpuConf({})).timeout is None


def test_wait_interrupted_by_cancel():
    lc = QueryLifecycle("q5")
    lc.start()
    t = threading.Timer(0.15, lc.cancel)
    t.start()
    t0 = time.monotonic()
    with pytest.raises(QueryCancelled):
        lc.wait(30.0)
    assert time.monotonic() - t0 < 5.0   # woke at the cancel, not 30s
    t.join()


def test_wait_capped_by_deadline():
    lc = QueryLifecycle("q6", timeout=0.1)
    lc.start()
    t0 = time.monotonic()
    with pytest.raises(QueryDeadlineExceeded):
        lc.wait(30.0)
    assert time.monotonic() - t0 < 5.0


# ---------------------------------------------------------------------------
# terminal taxonomy vs the retry ladders
# ---------------------------------------------------------------------------

def test_is_oom_refuses_terminal_errors():
    from spark_rapids_tpu.memory.retry import is_oom
    # message LOOKS like an OOM; terminal=True must win
    e = QueryCancelled("q", "RESOURCE_EXHAUSTED: not really")
    assert not is_oom(e)
    assert not is_oom(QueryDeadlineExceeded("q", 1.0))
    assert is_oom(RuntimeError("RESOURCE_EXHAUSTED: real"))


def test_with_retry_does_not_swallow_cancel():
    from spark_rapids_tpu.memory.retry import with_retry

    calls = []

    def fn(_b):
        calls.append(1)
        raise QueryCancelled("q", "RESOURCE_EXHAUSTED: disguised")

    class _Cat:
        pass

    with pytest.raises(QueryCancelled):
        with_retry(fn, _Cat(), object())
    assert len(calls) == 1   # no second attempt, no split


def test_dispatch_entry_is_a_cancellation_point():
    from spark_rapids_tpu.exec.core import ExecCtx
    with ExecCtx(backend="device", conf=TpuConf({})) as ctx:
        ctx.lifecycle.cancel("test")
        with pytest.raises(QueryCancelled):
            ctx.check_cancel()
        with pytest.raises(QueryCancelled):
            ctx.dispatch(lambda: 1)


def test_udf_slot_acquire_is_a_cancellation_point():
    from spark_rapids_tpu.exec.python_exec import _udf_slot
    sem = threading.BoundedSemaphore(1)
    lc = QueryLifecycle("qudf")
    lc.start()
    assert sem.acquire()   # saturate: the slot is unavailable
    errs = []

    def worker():
        try:
            with _udf_slot(sem, lc):
                pass
        except BaseException as e:  # noqa: BLE001 - recorded for asserts
            errs.append(e)

    t = threading.Thread(target=worker)
    t.start()
    time.sleep(0.15)       # worker is polling for the slot
    lc.cancel("test")
    t.join(timeout=5.0)
    assert not t.is_alive()
    assert errs and isinstance(errs[0], QueryCancelled)
    sem.release()
    # the cancelled waiter must NOT have consumed the permit
    assert sem.acquire(blocking=False)
    sem.release()


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

def test_admission_unbounded_by_default():
    ac = AdmissionController(max_concurrent=0)
    for i in range(32):
        ac.admit(f"q{i}")
    assert ac.active == 32


def test_admission_queue_overflow_rejected():
    before = get_registry().snapshot()
    ac = AdmissionController(max_concurrent=1, max_queued=1,
                             queue_timeout=30.0)
    ac.admit("holder")

    queued = threading.Thread(target=ac.admit, args=("waiter",))
    queued.start()
    deadline = time.monotonic() + 5.0
    while ac.queued < 1 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert ac.queued == 1

    with pytest.raises(QueryRejected, match="queue full"):
        ac.admit("overflow")
    assert _counter_delta(before, "queries_rejected") == 1

    ac.release()           # holder done -> waiter admitted
    queued.join(timeout=5.0)
    assert not queued.is_alive()
    assert ac.active == 1 and ac.queued == 0
    assert _counter_delta(before, "queries_admitted") == 2


def test_admission_is_fifo():
    ac = AdmissionController(max_concurrent=1, max_queued=8,
                             queue_timeout=30.0)
    ac.admit("holder")
    order: list = []

    def wait_in(name):
        ac.admit(name)
        order.append(name)

    threads = []
    for i in range(3):
        t = threading.Thread(target=wait_in, args=(f"w{i}",))
        t.start()
        threads.append(t)
        deadline = time.monotonic() + 5.0
        while ac.queued < i + 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert ac.queued == i + 1   # arrival order is pinned

    for i in range(3):
        ac.release()
        deadline = time.monotonic() + 5.0
        while len(order) < i + 1 and time.monotonic() < deadline:
            time.sleep(0.005)
    for t in threads:
        t.join(timeout=5.0)
    assert order == ["w0", "w1", "w2"]


def test_admission_queue_timeout_rejects():
    ac = AdmissionController(max_concurrent=1, max_queued=4,
                             queue_timeout=0.15)
    ac.admit("holder")
    t0 = time.monotonic()
    with pytest.raises(QueryRejected, match="queueTimeoutSeconds"):
        ac.admit("late")
    assert 0.1 <= time.monotonic() - t0 < 5.0
    assert ac.queued == 0   # the timed-out token was removed


def test_admission_shutdown_rejects_new_and_queued():
    ac = AdmissionController(max_concurrent=1, max_queued=4,
                             queue_timeout=30.0)
    ac.admit("holder")
    errs = []

    def waiter():
        try:
            ac.admit("queued")
        except QueryRejected as e:
            errs.append(e)

    t = threading.Thread(target=waiter)
    t.start()
    deadline = time.monotonic() + 5.0
    while ac.queued < 1 and time.monotonic() < deadline:
        time.sleep(0.005)
    ac.begin_shutdown()
    t.join(timeout=5.0)
    assert not t.is_alive()
    assert errs and "shutting down" in str(errs[0])
    with pytest.raises(QueryRejected, match="shutting down"):
        ac.admit("new")
    # already-admitted queries are unaffected
    assert ac.active == 1


# ---------------------------------------------------------------------------
# weighted-fair multi-tenant admission + cancel-while-queued
# ---------------------------------------------------------------------------

def test_parse_tenant_map():
    from spark_rapids_tpu.exec.lifecycle import parse_tenant_map
    assert parse_tenant_map("") == {}
    assert parse_tenant_map("etl:3,dash:1") == {"etl": 3.0, "dash": 1.0}
    assert parse_tenant_map("a:2", conv=int) == {"a": 2}
    with pytest.raises(ValueError):
        parse_tenant_map("no-colon")
    with pytest.raises(ValueError):
        parse_tenant_map("a:notanumber")


def _queue_waiters(ac, specs):
    """Start one admit-then-release thread per (tenant, name), pinning
    arrival order by waiting for the queue to grow between starts."""
    threads = []
    for i, (tenant, name) in enumerate(specs):
        def wait_in(t=tenant, n=name):
            ac.admit(n, tenant=t)
            ac.release(tenant=t)

        th = threading.Thread(target=wait_in)
        th.start()
        threads.append(th)
        deadline = time.monotonic() + 5.0
        while ac.queued < i + 1 and time.monotonic() < deadline:
            time.sleep(0.002)
        assert ac.queued == i + 1
    return threads


def test_weighted_fair_admission_order():
    from spark_rapids_tpu.exec.lifecycle import AdmissionController
    ac = AdmissionController(max_concurrent=1, max_queued=16,
                             queue_timeout=30.0,
                             tenant_weights={"etl": 3.0, "dash": 1.0})
    ac.admit("holder")
    specs = [("etl", f"e{i}") for i in range(6)] + \
            [("dash", f"d{i}") for i in range(2)]
    threads = _queue_waiters(ac, specs)
    ac.release()           # holder done -> the cascade drains the queue
    for t in threads:
        t.join(timeout=10.0)
        assert not t.is_alive()
    log = [tenant for tenant, _q in ac.admission_log
           if tenant != "default"]
    assert len(log) == 8
    # stride scheduling: a weight-3 tenant gets 3 of every 4 slots
    # while both are backlogged — assert the share over the window
    # where dash was still queued, not one exact interleaving
    assert log.count("etl") == 6 and log.count("dash") == 2
    last_dash = max(i for i, t in enumerate(log) if t == "dash")
    window = log[:last_dash + 1]
    assert window.count("etl") >= 2 * window.count("dash"), log
    # and no tenant was starved: the first 4 admissions include dash
    assert "dash" in log[:4], log


def test_single_tenant_stays_fifo_with_weights_configured():
    from spark_rapids_tpu.exec.lifecycle import AdmissionController
    ac = AdmissionController(max_concurrent=1, max_queued=8,
                             queue_timeout=30.0,
                             tenant_weights={"etl": 3.0})
    ac.admit("holder")
    threads = _queue_waiters(ac, [("default", f"w{i}") for i in range(3)])
    ac.release()
    for t in threads:
        t.join(timeout=10.0)
    assert [q for t, q in ac.admission_log if t == "default"] == \
        ["holder", "w0", "w1", "w2"]


def test_tenant_cap_does_not_block_neighbors():
    from spark_rapids_tpu.exec.lifecycle import AdmissionController
    ac = AdmissionController(max_concurrent=4, max_queued=8,
                             queue_timeout=30.0,
                             tenant_max_concurrent={"capped": 1})
    ac.admit("c1", tenant="capped")      # capped tenant at its cap
    done = []

    def capped_waiter():
        ac.admit("c2", tenant="capped")  # must queue behind the cap
        done.append("c2")

    t = threading.Thread(target=capped_waiter)
    t.start()
    deadline = time.monotonic() + 5.0
    while ac.queued < 1 and time.monotonic() < deadline:
        time.sleep(0.002)
    assert ac.queued == 1
    # global capacity exists: another tenant must sail past the
    # capped tenant's backlog
    ac.admit("o1", tenant="other")
    assert ac.active == 2 and not done
    ac.release(tenant="capped")          # c1 done -> c2 admits
    t.join(timeout=5.0)
    assert done == ["c2"]


def test_deadline_ordering_admits_tightest_first():
    from spark_rapids_tpu.exec.lifecycle import AdmissionController
    ac = AdmissionController(max_concurrent=1, max_queued=8,
                             queue_timeout=30.0, deadline_ordering=True)
    ac.admit("holder")
    lc_loose = QueryLifecycle("loose", timeout=60.0)
    lc_tight = QueryLifecycle("tight", timeout=0.8)
    order: list = []

    def wait_in(name, lc):
        ac.admit(name, lifecycle=lc)
        order.append(name)
        ac.release()

    threads = []
    for name, lc in (("loose", lc_loose), ("tight", lc_tight)):
        t = threading.Thread(target=wait_in, args=(name, lc))
        t.start()
        threads.append(t)
        deadline = time.monotonic() + 5.0
        while ac.queued < len(threads) and time.monotonic() < deadline:
            time.sleep(0.002)
    ac.release()
    for t in threads:
        t.join(timeout=10.0)
    # EDF within the tenant: the tight deadline overtakes the earlier
    # arrival instead of missing its deadline behind it
    assert order == ["tight", "loose"]


def test_cancel_while_queued_releases_slot_counts_once():
    from spark_rapids_tpu.exec.lifecycle import AdmissionController
    before = get_registry().snapshot()
    ac = AdmissionController(max_concurrent=1, max_queued=4,
                             queue_timeout=30.0)
    ac.admit("holder")
    lc = QueryLifecycle("queued")
    errs: list = []

    def waiter():
        try:
            ac.admit("queued", lifecycle=lc)
        except BaseException as e:  # noqa: BLE001 - recorded for asserts
            errs.append(e)

    t = threading.Thread(target=waiter)
    t.start()
    deadline = time.monotonic() + 5.0
    while ac.queued < 1 and time.monotonic() < deadline:
        time.sleep(0.002)
    assert ac.queued == 1
    assert lc.cancel("user abort")
    t.join(timeout=5.0)
    assert not t.is_alive()
    assert errs and isinstance(errs[0], QueryCancelled)
    # the queue token was released and the accounting is exact:
    # one cancellation, ZERO rejections (idempotent-cancel extended
    # to the queued state)
    assert ac.queued == 0
    assert not lc.cancel("again")
    assert _counter_delta(before, "queries_cancelled") == 1
    assert _counter_delta(before, "queries_rejected") == 0
    # the slot still works: the next arrival flows normally
    ac.release()
    ac.admit("next")
    assert ac.active == 1


def test_session_cancel_reaches_queued_query(data_dir):
    """A collect still waiting in the admission queue is visible in
    active_queries() and cancellable — the session registers the
    lifecycle BEFORE admission."""
    from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
    from spark_rapids_tpu.session import TpuSession
    session = TpuSession({
        "spark.rapids.sql.admission.maxConcurrentQueries": 1,
        "spark.rapids.sql.resultCache.enabled": "false",
    })
    ac = session._admission_controller()
    ac.admit("blocker")            # saturate the only slot
    before = get_registry().snapshot()
    df = build_tpch_query("q6", session, data_dir)
    outcome: list = []

    def run():
        try:
            outcome.append(("ok", df.collect()))
        except BaseException as e:  # noqa: BLE001 - recorded for asserts
            outcome.append(("err", e))

    t = threading.Thread(target=run)
    t.start()
    deadline = time.monotonic() + 10.0
    while ac.queued < 1 and time.monotonic() < deadline:
        time.sleep(0.002)
    assert ac.queued == 1
    qids = session.active_queries()
    assert len(qids) == 1          # queued, not yet admitted — but live
    assert session.cancel(qids[0])
    t.join(timeout=10.0)
    assert not t.is_alive()
    kind, val = outcome[0]
    assert kind == "err" and isinstance(val, QueryCancelled), outcome
    assert ac.queued == 0
    assert _counter_delta(before, "queries_cancelled") == 1
    assert _counter_delta(before, "queries_rejected") == 0
    assert session.active_queries() == []
    ac.release()                   # the manual blocker


def test_pressure_shed_hits_over_share_tenant_only():
    from spark_rapids_tpu.exec.lifecycle import (AdmissionController,
                                                 QueryRejected)
    before = get_registry().snapshot()
    ac = AdmissionController(max_concurrent=0)
    for i in range(3):
        ac.admit(f"h{i}", tenant="hog")
    ac.admit("q0", tenant="quiet")
    ac.pressure_hook = lambda tenant: "memory pressure: test"
    # hog holds 3 of 4 slots at equal weight: over its share -> shed
    with pytest.raises(QueryRejected, match="memory pressure"):
        ac.admit("h3", tenant="hog")
    # quiet is under its share: spared, admitted, counted
    ac.admit("q1", tenant="quiet")
    d = get_registry().delta(before)["counters"]
    assert d.get("admission_pressure_spared") == 1
    assert d.get("admission.tenant.hog.rejected") == 1
    assert d.get("admission.tenant.quiet.rejected", 0) == 0
    # single-tenant degenerate case: the only tenant is always at its
    # share, so pressure sheds it — identical to the pre-tenant gate
    ac2 = AdmissionController(max_concurrent=0)
    ac2.admit("a", tenant="default")
    ac2.pressure_hook = lambda tenant: "memory pressure: test"
    with pytest.raises(QueryRejected):
        ac2.admit("b", tenant="default")


def test_admission_tenant_storm_fault_sheds_only_that_tenant():
    from spark_rapids_tpu.exec.lifecycle import (AdmissionController,
                                                 QueryRejected)
    from spark_rapids_tpu.faults import FaultRegistry
    before = get_registry().snapshot()
    ac = AdmissionController(max_concurrent=0)
    ac.faults = FaultRegistry(
        "admission.tenant.storm:storm,tenant=noisy,times=2")
    with pytest.raises(QueryRejected, match="admission storm"):
        ac.admit("n1", tenant="noisy")
    ac.admit("c1", tenant="calm")          # unaffected tenant flows
    with pytest.raises(QueryRejected):
        ac.admit("n2", tenant="noisy")
    ac.admit("n3", tenant="noisy")         # times=2 exhausted
    d = get_registry().delta(before)["counters"]
    assert d.get("admission.tenant.noisy.rejected") == 2
    assert d.get("admission.tenant.calm.admitted") == 1
    assert d.get("faults.injected.admission.tenant.storm") == 2


# ---------------------------------------------------------------------------
# early consumer exit stops drain workers (exec/core.py stop flag)
# ---------------------------------------------------------------------------

class _FakeBatch:
    def device_size_bytes(self) -> int:
        return 64


def test_early_consumer_exit_stops_drain_workers():
    from spark_rapids_tpu.exec.core import (ExecCtx, PlanNode,
                                            drain_partitions_indexed)

    full = 40          # batches a slow partition would produce if drained
    step = 0.1         # seconds per slow batch
    counts = [0, 0, 0, 0]

    class SlowNode(PlanNode):
        def __init__(self):
            super().__init__(())

        def num_partitions(self, ctx):
            return 4

        def partition_iter(self, ctx, pid):
            if pid == 0:
                yield _FakeBatch()
                return
            for _ in range(full):
                time.sleep(step)
                counts[pid] += 1
                yield _FakeBatch()

    conf = TpuConf({"spark.rapids.sql.concurrentTpuTasks": 4,
                    "spark.rapids.sql.metrics.enabled": "false"})
    with ExecCtx(backend="device", conf=conf) as ctx:
        it = drain_partitions_indexed(ctx, SlowNode())
        t0 = time.monotonic()
        pid, first = next(it)
        assert pid == 0 and isinstance(first, _FakeBatch)
        it.close()     # LIMIT satisfied / consumer gone
        elapsed = time.monotonic() - t0
        # without the stop flag the close would block for the FULL drain
        # of three slow partitions (~4s each); with it, workers stop at
        # their next batch boundary
        assert elapsed < full * step / 2, elapsed
        assert max(counts[1:]) < full, counts
        # every parked spillable batch was closed on the way out
        assert not ctx.cache["catalog"]._entries


# ---------------------------------------------------------------------------
# shuffle retry ladder: deadline aborts mid-backoff
# ---------------------------------------------------------------------------

def test_deadline_aborts_shuffle_backoff_mid_pause():
    from spark_rapids_tpu.shuffle.retry import fetch_remote_with_retry
    # a port nothing listens on: every connect fails fast (refused),
    # so elapsed time is dominated by the backoff pause
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    before = get_registry().snapshot()
    lc = QueryLifecycle("qdl", timeout=0.3)
    lc.start()
    retry_wait = 2.0
    t0 = time.monotonic()
    with pytest.raises(QueryDeadlineExceeded):
        list(fetch_remote_with_retry(("127.0.0.1", port), "s1", 0,
                                     device=False, timeout=1.0,
                                     retry_wait=retry_wait, backoff=1.0,
                                     max_retries=8, lifecycle=lc))
    elapsed = time.monotonic() - t0
    # the deadline fired DURING the first backoff pause: abort well
    # under one full (jittered up to 1.5x) backoff step, not after it
    assert elapsed < 2 * retry_wait, elapsed
    assert _counter_delta(before, "queries_deadline_exceeded") == 1


# ---------------------------------------------------------------------------
# session integration: cancel / deadline / shutdown on real TPC-H plans
# ---------------------------------------------------------------------------

# same storm as tests/test_recovery_chaos.py: peer death + corrupted
# spilled shuffle output + tiny budgets, so cancellation lands while
# retries, recovery and spill I/O are all active
_STORM = ("shuffle.peer.dead:dead,times=4;"
          "spill.disk.corrupt:corrupt,priority=0,times=2")


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    from spark_rapids_tpu.bench.tpch_gen import generate_tpch
    d = str(tmp_path_factory.mktemp("tpch_lifecycle") / "sf001")
    generate_tpch(d, sf=0.01)
    _split_tables(d, ("lineitem", "orders", "customer"), parts=4)
    return d


def _split_tables(data_dir: str, tables, parts: int) -> None:
    """Re-write each table as ``parts`` parquet files so scans are
    multi-partition and the plans actually contain shuffle exchanges."""
    import pyarrow.parquet as pq
    for table in tables:
        path = os.path.join(data_dir, table, "part-0.parquet")
        t = pq.read_table(path)
        step = -(-t.num_rows // parts)
        for i in range(parts):
            pq.write_table(t.slice(i * step, step),
                           os.path.join(data_dir, table,
                                        f"part-{i}.parquet"))


def test_cancel_mid_query_under_storm(data_dir, tmp_path, monkeypatch):
    from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
    from spark_rapids_tpu.memory import catalog as cat_mod
    from spark_rapids_tpu.session import TpuSession

    spill_dir = tmp_path / "spill"
    spill_dir.mkdir()

    # capture every DeviceSemaphore minted during the run so the
    # post-cancel capacity invariant can be asserted after ctx close
    sems = []
    orig_init = cat_mod.DeviceSemaphore.__init__

    def capture_init(self, concurrency):
        orig_init(self, concurrency)
        sems.append(self)

    monkeypatch.setattr(cat_mod.DeviceSemaphore, "__init__", capture_init)

    session = TpuSession({
        "spark.rapids.test.faults": _STORM,
        "spark.rapids.memory.tpu.spillStoreSize": 1 << 16,
        "spark.rapids.memory.host.spillStorageSize": 4096,
        "spark.rapids.memory.spill.dir": str(spill_dir),
    })
    df = build_tpch_query("q3", session, data_dir)
    outcome: list = []

    def run():
        try:
            outcome.append(("ok", df.collect()))
        except BaseException as e:  # noqa: BLE001 - recorded for asserts
            outcome.append(("err", e))

    started = get_registry().snapshot()
    t = threading.Thread(target=run)
    t.start()
    deadline = time.monotonic() + 60.0
    while not session.active_queries() and t.is_alive() \
            and time.monotonic() < deadline:
        time.sleep(0.002)
    qids = session.active_queries()
    assert qids, "query never became active"
    # cancel once the query is seen IN the storm (on the device, first
    # fault fired), not a guessed interval after it was admitted: under
    # load it can still be planning long after, and a cancel that lands
    # there unwinds nothing this test is about
    while not (sems and _counter_delta(started, "faults.injected") >= 1) \
            and t.is_alive() and time.monotonic() < deadline:
        time.sleep(0.002)
    before = get_registry().snapshot()
    cancelled = session.cancel(qids[0])
    if not cancelled:
        t.join(timeout=60.0)
        pytest.skip("query finished before the cancel landed")

    t.join(timeout=60.0)   # bounded unwind, not a full run
    assert not t.is_alive(), "cancelled query did not unwind in time"
    kind, val = outcome[0]
    assert kind == "err" and isinstance(val, QueryCancelled), outcome
    # exactly one queries_cancelled no matter how many checkpoints fired;
    # post-run cancels are no-ops (the query is no longer live)
    assert not session.cancel(qids[0])
    assert session.cancel_all() == 0
    assert _counter_delta(before, "queries_cancelled") == 1
    # the unwind released the device in full and unlinked every spill file
    assert sems, "no DeviceSemaphore was ever minted"
    for sem in sems:
        assert sem._sem._value == sem.concurrency
    leftover = [os.path.join(r, f)
                for r, _d, fs in os.walk(spill_dir) for f in fs]
    assert not leftover, leftover
    assert session.active_queries() == []


def test_query_timeout_conf_enforced(data_dir):
    from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
    from spark_rapids_tpu.session import TpuSession
    session = TpuSession({"spark.rapids.sql.queryTimeout": 0.001})
    df = build_tpch_query("q6", session, data_dir)
    with pytest.raises(QueryDeadlineExceeded):
        df.collect()
    assert session.active_queries() == []


def test_hang_fault_broken_by_socket_timeout():
    """A peer that accepts the fetch then sends nothing (the
    ``shuffle.peer.hang`` fault) must be broken by the client's
    ``socketTimeout`` read deadline and retried to an EXACT result —
    not wedge the fetch for the full tcp.timeoutSeconds (120s)."""
    import numpy as np

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.exec.core import (ExecCtx, device_to_host,
                                            host_to_device)
    from spark_rapids_tpu.host.batch import HostBatch, HostColumn
    from spark_rapids_tpu.shuffle.retry import fetch_remote_with_retry
    from spark_rapids_tpu.shuffle.tcp import TcpShuffleTransport

    schema = T.Schema([T.StructField("x", T.IntegerType())])
    conf = TpuConf({
        "spark.rapids.test.faults":
            "shuffle.peer.hang:hang,times=1,seconds=30",
        "spark.rapids.shuffle.socketTimeout": 0.5,
    })
    before = get_registry().snapshot()
    with ExecCtx(backend="device", conf=conf) as ctx:
        t = TcpShuffleTransport(conf, ctx)
        try:
            oracle = []
            for m in range(4):
                vals = [m, m + 100]
                hb = HostBatch([HostColumn(np.asarray(vals, np.int32),
                                           np.ones(2, bool),
                                           T.IntegerType())], schema)
                t.write_partition(1, m, 0, host_to_device(hb))
                oracle += vals
            t0 = time.monotonic()
            got = []
            for b in fetch_remote_with_retry(t.address, 1, 0, conf=conf):
                got.extend(device_to_host(b).columns[0].to_list())
            elapsed = time.monotonic() - t0
            assert sorted(got) == sorted(oracle)
            # the stall really happened (>= the 0.5s read deadline) and
            # was broken by socketTimeout, nowhere near the hang window
            assert 0.4 <= elapsed < 15.0, elapsed
            assert _counter_delta(before, "shuffle.fetch.retries") >= 1
            assert t.server_metrics["faults_injected"] >= 1
        finally:
            t.close()


def test_shutdown_drain_finishes_inflight_then_rejects(data_dir):
    from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
    from spark_rapids_tpu.session import TpuSession
    expected = build_tpch_query(
        "q6", TpuSession({}), data_dir).collect()

    session = TpuSession({})
    df = build_tpch_query("q6", session, data_dir)
    outcome: list = []

    def run():
        try:
            outcome.append(("ok", df.collect()))
        except BaseException as e:  # noqa: BLE001 - recorded for asserts
            outcome.append(("err", e))

    t = threading.Thread(target=run)
    t.start()
    deadline = time.monotonic() + 30.0
    while not session.active_queries() and t.is_alive() \
            and time.monotonic() < deadline:
        time.sleep(0.002)
    session.shutdown(drain=True, timeout=120.0)
    t.join(timeout=10.0)
    assert not t.is_alive()
    kind, val = outcome[0]
    assert kind == "ok", outcome
    assert val == expected          # drained to the EXACT result
    with pytest.raises(QueryRejected, match="shutting down"):
        df.collect()
    assert session.active_queries() == []


def test_shutdown_leaves_no_engine_threads(data_dir):
    """After shutdown(drain=True) none of the threads a query started
    (partition tasks, the TCP shuffle server) is still alive."""
    from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
    from spark_rapids_tpu.session import TpuSession

    def engine_threads():
        return {t for t in threading.enumerate()
                if t.name.startswith(("tpu-task", "tpu-shuffle-srv"))}

    # threads other tests of this process left behind are not this
    # session's to stop
    inherited = engine_threads()
    seen: set = set()
    stop = threading.Event()

    def sample():
        while not stop.is_set():
            seen.update(t.name for t in engine_threads() - inherited)
            time.sleep(0.002)

    sampler = threading.Thread(target=sample, daemon=True)
    sampler.start()
    session = TpuSession({
        "spark.rapids.sql.resultCache.enabled": "false",
        "spark.rapids.shuffle.transport.class":
            "spark_rapids_tpu.shuffle.tcp.TcpShuffleTransport"})
    try:
        assert build_tpch_query("q3", session, data_dir).collect()
    finally:
        session.shutdown(drain=True, timeout=60.0)
        stop.set()
        sampler.join(5.0)
    assert any(n.startswith("tpu-shuffle-srv") for n in seen), seen
    deadline = time.monotonic() + 5.0
    while engine_threads() - inherited and time.monotonic() < deadline:
        time.sleep(0.05)
    leaked = sorted(t.name for t in engine_threads() - inherited)
    assert not leaked, f"engine threads alive after shutdown: {leaked}"


def test_shutdown_no_drain_cancels_inflight(data_dir):
    from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
    from spark_rapids_tpu.session import TpuSession
    session = TpuSession({
        "spark.rapids.test.faults": _STORM,
        "spark.rapids.memory.tpu.spillStoreSize": 1 << 16,
        "spark.rapids.memory.host.spillStorageSize": 4096,
    })
    df = build_tpch_query("q3", session, data_dir)
    outcome: list = []

    def run():
        try:
            outcome.append(("ok", df.collect()))
        except BaseException as e:  # noqa: BLE001 - recorded for asserts
            outcome.append(("err", e))

    t = threading.Thread(target=run)
    t.start()
    deadline = time.monotonic() + 30.0
    while not session.active_queries() and t.is_alive() \
            and time.monotonic() < deadline:
        time.sleep(0.002)
    time.sleep(0.2)
    session.shutdown(drain=False)
    t.join(timeout=60.0)
    assert not t.is_alive()
    kind, val = outcome[0]
    # either the cancel landed (the common case) or the query won the
    # race and finished; both leave the session idle and closed to
    # new work
    assert kind == "ok" or isinstance(val, QueryCancelled), outcome
    assert session.active_queries() == []
    with pytest.raises(QueryRejected):
        df.collect()
