"""Concurrent-query memory chaos: TPC-H racing under the governor.

The single-query OOM chaos suite (test_oom_chaos.py) proves the
split-and-retry ladder; this suite adds the cross-query dimension the
memory governor exists for: several TPC-H queries share ONE session —
one process-wide governor, one admission controller — under a tiny
spill store and a deterministic HBM-exhaustion storm.  Required
outcomes: every query stays EXACT against its host oracle, wall time
stays bounded (no eviction livelock between concurrent retry ladders),
the governor's per-query ledgers stay internally consistent while the
race runs, and nothing — bytes or grant reservations — leaks once the
queries drain.
"""
import threading
import time

import pytest

from spark_rapids_tpu.bench.runner import _rows_match
from spark_rapids_tpu.bench.tpch_gen import generate_tpch
from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
from spark_rapids_tpu.memory.governor import get_governor
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.session import TpuSession

# storm threshold low enough that even the smaller customer/orders
# scans (q13) split, not just lineitem; 32-row minSplitRows floor keeps
# convergence guaranteed
_STORM = "memory.oom.until_rows:oom,until_rows=8192"
_CHAOS_CONF = {
    "spark.rapids.test.faults": _STORM,
    "spark.rapids.memory.host.spillStorageSize": 64 << 20,
    "spark.rapids.sql.admission.maxConcurrentQueries": 4,
}

#: must include the build-heavy join queries (q13 customer⟕orders,
#: q18 large IN-subquery join) alongside the wide aggregate q1
_QUERIES = ["q1", "q13", "q18"]

_WALL_LIMIT_S = 420.0


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("tpch_gov_chaos") / "sf001")
    generate_tpch(d, sf=0.01)
    return d


def _oracle(df):
    from spark_rapids_tpu.exec.core import collect_host
    ov, meta = df._overridden(quiet=True)
    return collect_host(meta.exec_node, df._s.conf)


def test_concurrent_queries_exact_under_storm(data_dir):
    session = TpuSession(dict(_CHAOS_CONF))
    gov = get_governor()
    # the governor is a process singleton: earlier test files may have
    # leaked still-referenced ledgers of their own — leak checks below
    # are scoped to what THIS test registers
    pre_registered = set(gov.query_stats())
    before = get_registry().snapshot()["counters"]
    dfs = {q: build_tpch_query(q, session, data_dir) for q in _QUERIES}
    oracles = {q: _oracle(df) for q, df in dfs.items()}

    results: dict = {}
    errors: dict = {}

    def run(q):
        try:
            results[q] = dfs[q].collect()
        except Exception as ex:  # noqa: BLE001 - recorded and asserted below
            errors[q] = ex

    # ledger sampler: while the race runs, every registered query's
    # ledger must stay internally consistent (device/pinned/peak
    # relations) — grant reservations are legitimate mid-run, so only
    # the per-query invariants are checked here
    stop = threading.Event()
    max_registered = [0]
    ledger_violations: list = []

    def sample():
        while not stop.is_set():
            stats = gov.query_stats()
            max_registered[0] = max(max_registered[0], len(stats))
            for qid, s in stats.items():
                if (s["device_bytes"] < 0 or s["pinned_bytes"] < 0
                        or s["pinned_bytes"] > s["device_bytes"]
                        or s["peak_bytes"] < s["device_bytes"]):
                    ledger_violations.append((qid, dict(s)))
            time.sleep(0.01)

    sampler = threading.Thread(target=sample, daemon=True)
    threads = [threading.Thread(target=run, args=(q,), daemon=True)
               for q in _QUERIES]
    t0 = time.monotonic()
    sampler.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join(_WALL_LIMIT_S - (time.monotonic() - t0))
    wall = time.monotonic() - t0
    stuck = [t for t in threads if t.is_alive()]
    stop.set()
    sampler.join(5.0)
    assert not stuck, (f"livelock: {len(stuck)} queries still running "
                       f"after {wall:.0f}s")
    assert wall < _WALL_LIMIT_S
    assert not errors, errors
    assert not ledger_violations, ledger_violations[:3]
    assert max_registered[0] >= 2, \
        "queries never actually overlapped; chaos was vacuous"
    for q in _QUERIES:
        assert _rows_match(results[q], oracles[q]), f"{q} inexact"

    # the storm actually fired and the governed reclaim path ran
    moved = get_registry().delta({"counters": before})["counters"]
    assert moved.get("faults.injected.memory.oom.until_rows", 0) > 0
    assert moved.get("governor_reclaims", 0) > 0

    # nothing leaks once the queries drain: no registered ledgers, no
    # outstanding reservations (verifier also covers the relations)
    session.shutdown(drain=True)
    import gc
    gc.collect()    # unclosed-but-unreferenced catalogs drop their ledgers
    from spark_rapids_tpu.plan.verify import verify_governor_ledger
    assert set(gov.query_stats()) <= pre_registered, \
        "this test's queries leaked governor ledgers after drain"
    assert gov.reserved_bytes() == 0
    verify_governor_ledger(gov)


def test_oom_storm_denial_converges(data_dir):
    """memory.governor.oom_storm makes every arbitration report zero
    bytes freed — spilling 'cannot keep up' — so correctness must come
    from the split ladder alone, still exact and bounded."""
    conf = dict(_CHAOS_CONF)
    conf["spark.rapids.test.faults"] = (
        _STORM + ";memory.governor.oom_storm:oom,times=0")
    session = TpuSession(conf)
    df = build_tpch_query("q1", session, data_dir)
    want = _oracle(df)
    before = get_registry().snapshot()["counters"]
    t0 = time.monotonic()
    got = df.collect()
    assert time.monotonic() - t0 < _WALL_LIMIT_S
    assert _rows_match(got, want)
    moved = get_registry().delta({"counters": before})["counters"]
    assert moved.get("governor_storm_denials", 0) > 0
    session.shutdown(drain=True)


def test_cancel_during_grant_stall_releases_reservation():
    """memory.grant.stall holds a reclaim in the grant-wait window; a
    cancel landing there must unwind with the terminal error, leaving
    no reservation behind."""
    from spark_rapids_tpu.conf import TpuConf
    from spark_rapids_tpu.exec.lifecycle import QueryCancelled, QueryLifecycle
    from spark_rapids_tpu.memory import BufferCatalog
    from spark_rapids_tpu.memory.governor import MemoryGovernor

    gov = MemoryGovernor()
    try:
        conf = TpuConf({"spark.rapids.test.faults":
                        "memory.grant.stall:stall,seconds=30"})
        older = BufferCatalog(device_limit=1000, host_limit=1 << 20)
        younger = BufferCatalog(device_limit=1000, host_limit=1 << 20,
                                conf=conf)
        lc = QueryLifecycle("young")
        lc.start()
        # tiny minSpill floor: with the default 16m floor the need could
        # never fit under the toy 1000-byte budget and the wait would be
        # (correctly) skipped instead of parking in the stall window
        knobs = {"spark.rapids.memory.governor.minSpillBytes": 1}
        gov.register(older, "old", None, knobs)
        gov.register(younger, "young", lc, knobs)
        # over-commit the ledger so the reclaim genuinely parks: the
        # OLDER query holds nearly everything and is off-limits to the
        # younger requester (wound-wait), whose own catalog is empty
        gov.account(older, 990)
        err = []

        def run():
            try:
                gov.reclaim(younger, 500)
            except QueryCancelled as ex:
                err.append(ex)

        t = threading.Thread(target=run, daemon=True)
        t0 = time.monotonic()
        t.start()
        deadline = time.monotonic() + 5.0
        while (younger.faults.fired_count("memory.grant.stall") == 0
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert younger.faults.fired_count("memory.grant.stall") == 1, \
            "stall fault never fired; the wait window was not entered"
        lc.cancel("chaos cancel")
        t.join(10.0)
        assert not t.is_alive(), "cancel did not break the stalled wait"
        assert time.monotonic() - t0 < 31.0, "waited out the full stall"
        assert err, "terminal error swallowed by the grant wait"
        assert gov.reserved_bytes() == 0, "reservation leaked"
        gov.account(older, -990)
        older.close()
        younger.close()
    finally:
        with gov._cond:
            gov._stop_bg_locked()
        # hand the shared source name back to the process singleton so
        # later suite files still see governor.* gauges
        from spark_rapids_tpu.memory import governor as gov_mod
        if gov_mod._GOVERNOR is not None:
            get_registry().register_source(
                "governor", gov_mod._GOVERNOR._source)
        else:
            get_registry().unregister_source("governor")
