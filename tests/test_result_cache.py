"""Serving-tier result/fragment cache suite (exec/result_cache.py).

The contract under test is the acceptance criteria's reuse-with-proof
shape: a repeated identical query at an unchanged input snapshot is
served from the cache with ZERO executor dispatches (``queries_executed``
delta 0) and zero compiles; mutating an input file, changing a
fingerprinted conf, or switching backend forces a full recompute with
no stale rows; concurrent identical queries coalesce onto one
computation whose waiters — never the owner — abort on their own
cancel; corruption is a verified miss, not wrong rows; and with
``spark.rapids.sql.resultCache.enabled=false`` nothing in the cache
plane runs at all (gate-off reversibility).
"""
import os
import threading
import time

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.exec.result_cache import (ResultCache,
                                                get_result_cache,
                                                maybe_cache)
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.session import TpuSession


def _delta(before: dict, name: str) -> float:
    return get_registry().delta(before)["counters"].get(name, 0)


@pytest.fixture()
def table(tmp_path):
    p = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"a": list(range(200)),
                             "b": [float(i) / 7 for i in range(200)]}), p)
    return p


def _df(session, path):
    return session.read_parquet(path).filter(col("a") > lit(20)) \
        .select("a", "b")


# ---------------------------------------------------------------------------
# whole-query result caching through the session
# ---------------------------------------------------------------------------

def test_repeat_query_hits_zero_executor_dispatches(table):
    s = TpuSession()
    df = _df(s, table)
    r1 = df.collect()
    before = get_registry().snapshot()
    r2 = df.collect()
    assert r2 == r1
    assert _delta(before, "result_cache_hits") == 1
    # the executor-entry chokepoint and the compile plane never moved:
    # the hit was served without minting an ExecCtx
    assert _delta(before, "queries_executed") == 0
    assert _delta(before, "compile_count") == 0
    s.shutdown()


def test_mtime_bump_invalidates(table):
    s = TpuSession()
    df = _df(s, table)
    r1 = df.collect()
    before = get_registry().snapshot()
    os.utime(table, ns=(time.time_ns(), time.time_ns()))
    r2 = df.collect()
    assert r2 == r1                       # same bytes, recomputed
    assert _delta(before, "result_cache_hits") == 0
    assert _delta(before, "result_cache_misses") == 1
    assert _delta(before, "queries_executed") == 1
    s.shutdown()


def test_content_change_serves_fresh_rows(table):
    s = TpuSession()
    df = _df(s, table)
    r1 = df.collect()
    pq.write_table(pa.table({"a": list(range(300)),
                             "b": [float(i) for i in range(300)]}), table)
    r2 = _df(s, table).collect()
    assert len(r2) == 279 and len(r1) == 179   # fresh rows, not stale
    s.shutdown()


def test_conf_change_invalidates(table):
    s1 = TpuSession()
    r1 = _df(s1, table).collect()
    before = get_registry().snapshot()
    s2 = TpuSession({"spark.rapids.sql.batchSizeBytes": 1 << 20})
    r2 = _df(s2, table).collect()
    assert r2 == r1
    assert _delta(before, "result_cache_hits") == 0
    assert _delta(before, "queries_executed") == 1
    s1.shutdown()
    s2.shutdown()


def test_backend_is_part_of_the_key(table):
    """The host oracle must NEVER be served a device-computed entry —
    that would destroy differential testing."""
    cache = get_result_cache()
    s = TpuSession()
    df = _df(s, table)
    kd = cache.result_key(df._plan, "device", s.conf)
    kh = cache.result_key(df._plan, "host", s.conf)
    assert kd is not None and kh is not None and kd != kh
    s.shutdown()


def test_in_memory_plan_is_never_cached(table):
    from spark_rapids_tpu import types as T
    s = TpuSession()
    schema = T.Schema([T.StructField("x", T.IntegerType())])
    df = s.from_pydict({"x": [1, 2, 3]}, schema)
    before = get_registry().snapshot()
    assert df.collect() == df.collect()
    # no provable snapshot -> result_key None -> no cache traffic
    assert _delta(before, "result_cache_hits") == 0
    assert _delta(before, "result_cache_misses") == 0
    assert _delta(before, "queries_executed") == 2
    s.shutdown()


def test_gate_off_is_byte_identical(table):
    s = TpuSession({"spark.rapids.sql.resultCache.enabled": "false"})
    assert maybe_cache(s.conf) is None
    df = _df(s, table)
    r1 = df.collect()
    before = get_registry().snapshot()
    r2 = df.collect()
    assert r2 == r1
    # execute-every-time, and the cache plane never even counted a miss
    moved = get_registry().delta(before)["counters"]
    assert moved.get("queries_executed") == 1, moved
    assert not [k for k in moved if k.startswith("result_cache")], moved
    s.shutdown()


# ---------------------------------------------------------------------------
# corruption: verified miss, never wrong rows
# ---------------------------------------------------------------------------

def test_corrupt_hit_drops_recomputes_exact(table):
    s = TpuSession({"spark.rapids.test.faults":
                    "cache.result.corrupt:corrupt,times=1"})
    df = _df(s, table)
    r1 = df.collect()
    before = get_registry().snapshot()
    r2 = df.collect()                     # poisoned hit -> CRC fail
    assert r2 == r1                       # recomputed, exact
    d = get_registry().delta(before)["counters"]
    assert d.get("result_cache_corrupt") == 1
    assert d.get("queries_executed") == 1
    assert d.get("faults.injected.cache.result.corrupt") == 1
    # the re-stored entry is clean: next repeat is a real hit
    before = get_registry().snapshot()
    assert df.collect() == r1
    assert _delta(before, "result_cache_hits") == 1
    s.shutdown()


# ---------------------------------------------------------------------------
# single-flight: coalesce, waiter cancel, owner takeover
# ---------------------------------------------------------------------------

def test_concurrent_identical_queries_coalesce():
    cache = ResultCache()
    gate = threading.Event()
    computes = []

    def compute():
        computes.append(1)
        gate.wait(10.0)
        return [(1, 2)]

    results = []
    threads = [threading.Thread(
        target=lambda: results.append(cache.get_or_compute(("k",), compute)))
        for _ in range(4)]
    before = get_registry().snapshot()
    for t in threads:
        t.start()
    deadline = time.monotonic() + 5.0
    while len(computes) < 1 and time.monotonic() < deadline:
        time.sleep(0.002)
    gate.set()
    for t in threads:
        t.join(timeout=10.0)
        assert not t.is_alive()
    assert len(computes) == 1             # ONE computation for four calls
    assert results == [[(1, 2)]] * 4
    assert _delta(before, "result_cache_coalesced") == 3


def test_waiter_cancel_aborts_wait_not_owner():
    from spark_rapids_tpu.exec.lifecycle import (QueryCancelled,
                                                 QueryLifecycle)
    cache = ResultCache()
    gate = threading.Event()

    def owner_compute():
        gate.wait(10.0)
        return ["rows"]

    owner_out, waiter_err = [], []
    to = threading.Thread(target=lambda: owner_out.append(
        cache.get_or_compute(("kc",), owner_compute)))
    to.start()
    time.sleep(0.05)                      # owner is in flight
    lc = QueryLifecycle("waiter")

    def waiter():
        try:
            cache.get_or_compute(("kc",), owner_compute, lifecycle=lc)
        except BaseException as e:  # noqa: BLE001 - recorded for asserts
            waiter_err.append(e)

    tw = threading.Thread(target=waiter)
    tw.start()
    time.sleep(0.1)
    lc.cancel("user")
    tw.join(timeout=5.0)
    assert not tw.is_alive()
    assert waiter_err and isinstance(waiter_err[0], QueryCancelled)
    # the owner was untouched by the waiter's cancel
    gate.set()
    to.join(timeout=5.0)
    assert owner_out == [["rows"]]


def test_owner_failure_waiter_takes_over():
    cache = ResultCache()
    gate = threading.Event()
    calls = []

    def failing_then_ok():
        calls.append(1)
        if len(calls) == 1:
            gate.wait(5.0)
            raise RuntimeError("owner died")
        return ["recovered"]

    errs, out = [], []

    def first():
        try:
            cache.get_or_compute(("kf",), failing_then_ok)
        except RuntimeError as e:
            errs.append(e)

    t1 = threading.Thread(target=first)
    t1.start()
    time.sleep(0.05)
    t2 = threading.Thread(target=lambda: out.append(
        cache.get_or_compute(("kf",), failing_then_ok)))
    t2.start()
    time.sleep(0.05)
    gate.set()                            # owner raises now
    t1.join(timeout=5.0)
    t2.join(timeout=5.0)
    assert errs and "owner died" in str(errs[0])
    assert out == [["recovered"]]         # waiter computed for itself
    assert len(calls) == 2


# ---------------------------------------------------------------------------
# memory: LRU, consumer pins, governor eviction
# ---------------------------------------------------------------------------

class _FakeBatch:
    def __init__(self, n):
        self.n = n

    def device_size_bytes(self):
        return self.n


def test_lru_eviction_respects_consumer_pins():
    before = get_registry().snapshot()
    cache = ResultCache(max_bytes=250)
    e1 = cache.fragment_entry(("f1",), lambda: [_FakeBatch(100)])
    e2 = cache.fragment_entry(("f2",), lambda: [_FakeBatch(100)])
    cache.fragment_release(e2)            # f2 idle, f1 still consumed
    e3 = cache.fragment_entry(("f3",), lambda: [_FakeBatch(100)])
    # f2 (idle, oldest idle) was evicted; pinned f1 survived
    assert _delta(before, "result_cache_evictions") == 1
    assert cache.cached_bytes() == 200
    cache.fragment_release(e1)
    cache.fragment_release(e3)
    assert cache.device_bytes() == 200


def test_oversized_result_served_never_cached():
    cache = ResultCache(max_bytes=64)
    rows = [("x" * 1000,)]
    assert cache.get_or_compute(("big",), lambda: rows) == rows
    assert cache.cached_bytes() == 0      # returned, not cached


def test_governor_evicts_cache_fragments_before_spilling():
    from spark_rapids_tpu.memory.governor import MemoryGovernor
    gov = MemoryGovernor()
    cache = ResultCache()
    gov.register_cache(cache)
    e = cache.fragment_entry(("gf",), lambda: [_FakeBatch(1 << 20)])
    cache.fragment_release(e)
    before = get_registry().snapshot()
    freed = gov._evict_cache(1 << 10, kind="fragment")
    assert freed == 1 << 20               # device bytes actually freed
    assert cache.device_bytes() == 0
    d = get_registry().delta(before)["counters"]
    assert d.get("governor_cache_evict_bytes") == 1 << 20
    assert d.get("result_cache_evictions") == 1


def test_evict_kind_filter_skips_result_blobs():
    cache = ResultCache()
    cache.get_or_compute(("r",), lambda: [(1,)])
    e = cache.fragment_entry(("f",), lambda: [_FakeBatch(64)])
    cache.fragment_release(e)
    assert cache.evict(kind="fragment") == 64
    assert cache.cached_bytes() > 0       # the result blob survived
    assert cache.evict() > 0              # unfiltered sweep takes it
    assert cache.cached_bytes() == 0


# ---------------------------------------------------------------------------
# cross-query shared-scan fragments (io/scan.py share_output routing)
# ---------------------------------------------------------------------------

def test_self_join_shares_one_scan_materialization(table):
    s = TpuSession()
    a = s.read_parquet(table)
    b = s.read_parquet(table)
    before = get_registry().snapshot()
    rows = a.join(b, on="a").collect()
    assert rows
    d = get_registry().delta(before)["counters"]
    # the planner marked the scan shared; both consumers drained ONE
    # materialization through the process-wide cache
    assert d.get("result_cache_fragment_misses", 0) >= 1
    assert d.get("result_cache_fragment_hits", 0) >= 1
    # nothing left pinned after the drain
    cache = get_result_cache()
    with cache._lock:
        assert all(e.consumers == 0 for e in cache._entries.values())
    s.shutdown()


def test_fragment_cache_disabled_falls_back_to_query_local(table):
    s = TpuSession({"spark.rapids.sql.resultCache.enabled": "false"})
    a = s.read_parquet(table)
    b = s.read_parquet(table)
    before = get_registry().snapshot()
    rows = a.join(b, on="a").collect()
    assert rows
    d = get_registry().delta(before)["counters"]
    assert d.get("result_cache_fragment_misses", 0) == 0
    assert d.get("result_cache_fragment_hits", 0) == 0
    s.shutdown()
