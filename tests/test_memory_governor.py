"""Cross-query HBM memory governor tests (memory/governor.py).

Covers the four tentpole behaviors — per-query accounting that sums to
catalog occupancy, need-sized ownership-aware arbitration with
wound-wait ordering, bounded lifecycle-integrated grant waits, and
pressure-shed admission — plus gate-off reversibility: with
``spark.rapids.memory.governor.enabled=false`` nothing registers and
plans / results are identical to the ungoverned engine.
"""
import threading
import time

import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.host.batch import HostBatch
from spark_rapids_tpu.memory import BufferCatalog
from spark_rapids_tpu.memory.governor import MemoryGovernor
from spark_rapids_tpu.obs.registry import get_registry

SCHEMA = T.Schema([
    T.StructField("a", T.LongType(), True),
    T.StructField("s", T.StringType(), True),
])


def _batch(rng, n=256):
    return HostBatch.from_pydict({
        "a": [int(x) for x in rng.integers(-1000, 1000, n)],
        "s": [f"str{x}" if x % 7 else None for x in rng.integers(0, 99, n)],
    }, SCHEMA).to_device()


class _SpillCat:
    """Fake catalog recording the spill sizes the governor asks for."""

    def __init__(self, device_limit=1 << 20, yields=None):
        self.device_limit = device_limit
        self.governor = None
        self.query_id = None
        self.requests: list[int] = []
        self._yields = yields  # None: free exactly what was asked

    def spill_device(self, n):
        self.requests.append(n)
        if self._yields is None:
            return n
        return self._yields.pop(0) if self._yields else 0


@pytest.fixture
def gov():
    """A private governor instance (not the process singleton) so tests
    never leak registered state into each other."""
    g = MemoryGovernor()
    yield g
    with g._cond:
        g._stop_bg_locked()
    _restore_singleton_source()


def _restore_singleton_source():
    """A private governor registered itself under the shared source
    name; hand the slot back to the process singleton (if one exists)
    instead of leaving the registry blind for the rest of the suite."""
    from spark_rapids_tpu.memory import governor as gov_mod
    if gov_mod._GOVERNOR is not None:
        get_registry().register_source("governor", gov_mod._GOVERNOR._source)
    else:
        get_registry().unregister_source("governor")


# ---------------------------------------------------------------------------
# accounting
# ---------------------------------------------------------------------------

def test_accounting_sums_to_catalog_occupancy(gov, rng):
    cat = BufferCatalog(device_limit=64 << 20, host_limit=1 << 24)
    gov.register(cat, "q1", None, {})
    ids = [cat.add_batch(_batch(rng), priority=i) for i in range(4)]
    st = gov.query_stats("q1")["q1"]
    assert st["device_bytes"] == cat.device_used > 0
    assert st["peak_bytes"] == cat.device_used
    # pin one entry: pinned ledger mirrors the refcount 0->1 edge
    b = cat.acquire(ids[0])
    st = gov.query_stats("q1")["q1"]
    assert st["pinned_bytes"] == b.device_size_bytes()
    cat.release(ids[0])
    assert gov.query_stats("q1")["q1"]["pinned_bytes"] == 0
    # spill moves bytes OUT of the ledger, unspill back IN
    peak = st["peak_bytes"]
    freed = cat.spill_device(cat.device_used)
    assert freed > 0
    st = gov.query_stats("q1")["q1"]
    assert st["device_bytes"] == cat.device_used
    assert st["peak_bytes"] == peak  # monotone high-water mark
    cat.acquire(ids[1])  # unspill back onto the device
    cat.release(ids[1])
    assert gov.query_stats("q1")["q1"]["device_bytes"] == cat.device_used
    # close() drains everything and unregisters
    cat.close()
    assert gov.query_stats() == {}
    assert cat.governor is None


def test_registry_source_and_ledger_verifier(gov):
    cat = _SpillCat()
    gov.register(cat, "qx", None, {})
    gov.account(cat, 1000)
    snap = get_registry().snapshot()["gauges"]
    assert snap["governor.device_bytes_total"] == 1000
    assert snap["governor.q.qx.device_bytes"] == 1000
    assert snap["governor.queries_registered"] == 1

    from spark_rapids_tpu.plan.verify import (PlanInvariantError,
                                              verify_governor_ledger)
    verify_governor_ledger(gov)  # consistent ledger passes
    st = gov._states[id(cat)]
    st.pinned_bytes = 2000  # pinned > device: impossible
    with pytest.raises(PlanInvariantError, match="pinned_bytes"):
        verify_governor_ledger(gov)
    st.pinned_bytes = 0
    st.device_bytes = -5
    with pytest.raises(PlanInvariantError, match="negative ledger"):
        verify_governor_ledger(gov)
    st.device_bytes = 100
    st.peak_bytes = 0
    with pytest.raises(PlanInvariantError, match="peak_bytes"):
        verify_governor_ledger(gov)


# ---------------------------------------------------------------------------
# arbitration: need-sized, own-first, wound-wait
# ---------------------------------------------------------------------------

def test_reclaim_is_need_sized_not_quarter_budget(gov):
    cat = _SpillCat(device_limit=1 << 30)
    gov.register(cat, "q1", None, {
        "spark.rapids.memory.governor.minSpillBytes": 4096})
    freed = gov.reclaim(cat, 100_000)
    assert freed == 100_000
    # sized to the failed allocation, NOT device_limit // 4 (256 MiB)
    assert cat.requests == [100_000]
    # tiny request hits the conf'd floor instead
    cat.requests.clear()
    gov.reclaim(cat, 1)
    assert cat.requests == [4096]


def test_ungoverned_reclaim_keeps_legacy_quarter_sweep():
    from spark_rapids_tpu.memory.retry import _reclaim
    cat = _SpillCat(device_limit=1 << 20)
    assert cat.governor is None
    _reclaim(cat, 12345)
    assert cat.requests == [(1 << 20) // 4]


def test_wound_wait_ordering(gov):
    older, younger = _SpillCat(), _SpillCat()
    gov.register(older, "old", None, {})
    gov.register(younger, "young", None, {})
    st_old = gov._states[id(older)]
    st_young = gov._states[id(younger)]
    # younger requester: the older peer is off limits
    assert gov._reclaim_from_peers(st_young, 100) == 0
    assert older.requests == []
    # older requester: the younger peer is a victim
    assert gov._reclaim_from_peers(st_old, 100) == 100
    assert younger.requests == [100]


def test_peers_pinned_working_set_never_spilled(gov, rng):
    """Real catalogs: the victim's pinned entry survives a peer
    reclaim; only its refcount==0 buffers move."""
    req = BufferCatalog(device_limit=64 << 20, host_limit=1 << 24)
    vic = BufferCatalog(device_limit=64 << 20, host_limit=1 << 24)
    gov.register(req, "older", None, {})
    gov.register(vic, "younger", None, {})
    pinned_id = vic.add_batch(_batch(rng), priority=0)
    vic.acquire(pinned_id)  # pin: the victim's working set
    idle_id = vic.add_batch(_batch(rng), priority=1)
    st_req = gov._states[id(req)]
    freed = gov._reclaim_from_peers(st_req, 1 << 20)
    assert freed > 0
    assert vic.tier_of(pinned_id) == "device"   # untouched
    assert vic.tier_of(idle_id) != "device"     # spilled
    vic.release(pinned_id)
    req.close()
    vic.close()


def test_victim_error_never_kills_requester(gov):
    class _BadCat(_SpillCat):
        def spill_device(self, n):
            raise RuntimeError("victim exploded")

    older, bad = _SpillCat(), _BadCat()
    gov.register(older, "old", None, {})
    gov.register(bad, "young", None, {})
    before = get_registry().snapshot()["counters"].get(
        "governor_victim_errors", 0)
    st_old = gov._states[id(older)]
    assert gov._reclaim_from_peers(st_old, 100) == 0  # skipped, no raise
    after = get_registry().snapshot()["counters"]["governor_victim_errors"]
    assert after == before + 1


# ---------------------------------------------------------------------------
# grant waits
# ---------------------------------------------------------------------------

def test_grant_wait_blocks_until_peer_release(gov):
    a, b = _SpillCat(device_limit=1000), _SpillCat(device_limit=1000)
    gov.register(a, "qa", None, {})
    gov.register(b, "qb", None, {})
    gov._grant_timeout = 5.0
    gov.account(a, 900)
    gov.account(b, 90)
    st_b = gov._states[id(b)]
    got = []
    t = threading.Thread(
        target=lambda: got.append(gov._wait_for_grant(b, st_b, 500)))
    t.start()
    deadline = time.monotonic() + 2.0
    while gov.reserved_bytes() != 500 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert gov.reserved_bytes() == 500  # reservation visible mid-wait
    gov.account(a, -800)                # peer releases -> grant
    t.join(3.0)
    assert not t.is_alive() and got == [500]
    assert gov.reserved_bytes() == 0


def test_grant_wait_headroom_short_circuit(gov):
    """With ledger headroom already covering the need, the OOM is
    outside the ledger's model — no wait, 0 so the split ladder runs."""
    cat = _SpillCat(device_limit=1 << 20)
    gov.register(cat, "qa", None, {})
    st = gov._states[id(cat)]
    t0 = time.monotonic()
    assert gov._wait_for_grant(cat, st, 4096) == 0
    assert time.monotonic() - t0 < 1.0


def test_grant_wait_skips_when_no_live_peer(gov):
    """A solo query has nobody to wait on: however over-committed its
    ledger, the wait returns 0 immediately so the split ladder runs
    instead of stalling out the full grant timeout."""
    cat = _SpillCat(device_limit=1000)
    gov.register(cat, "qa", None, {})
    gov._grant_timeout = 30.0
    gov.account(cat, 990)          # pinned working set over budget
    st = gov._states[id(cat)]
    t0 = time.monotonic()
    assert gov._wait_for_grant(cat, st, 500) == 0
    assert time.monotonic() - t0 < 1.0


def test_leaked_catalog_ledger_dropped_on_gc(gov):
    """A governed catalog garbage-collected without close() must not
    pin its ledger: leaked bytes would inflate aggregate occupancy for
    every later query in the process."""
    import gc
    cat = _SpillCat(device_limit=1000)
    gov.register(cat, "leaky", None, {})
    gov.account(cat, 500)
    assert "leaky" in gov.query_stats()
    del cat
    gc.collect()
    assert "leaky" not in gov.query_stats()


def test_grant_wait_times_out_bounded(gov):
    a, b = _SpillCat(device_limit=1000), _SpillCat(device_limit=1000)
    gov.register(a, "qa", None, {})
    gov.register(b, "qb", None, {})
    gov._grant_timeout = 0.2
    gov.account(a, 990)
    st_b = gov._states[id(b)]
    before = get_registry().snapshot()["counters"].get(
        "governor_grant_timeouts", 0)
    t0 = time.monotonic()
    assert gov._wait_for_grant(b, st_b, 500) == 0
    assert 0.15 < time.monotonic() - t0 < 2.0
    assert gov.reserved_bytes() == 0
    after = get_registry().snapshot()["counters"]["governor_grant_timeouts"]
    assert after == before + 1


def test_grant_wait_cancellation_releases_reservation(gov):
    """A cancel landing mid-grant-wait aborts the wait with the
    terminal error and ALWAYS releases the reservation."""
    from spark_rapids_tpu.exec.lifecycle import QueryCancelled, QueryLifecycle
    a, b = _SpillCat(device_limit=1000), _SpillCat(device_limit=1000)
    lc = QueryLifecycle("qb")
    lc.start()
    gov.register(a, "qa", None, {})
    gov.register(b, "qb", lc, {})
    gov._grant_timeout = 30.0
    gov.account(a, 990)
    st_b = gov._states[id(b)]
    err = []
    def run():
        try:
            gov._wait_for_grant(b, st_b, 500)
        except QueryCancelled as ex:
            err.append(ex)
    t = threading.Thread(target=run)
    t.start()
    deadline = time.monotonic() + 2.0
    while gov.reserved_bytes() != 500 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert gov.reserved_bytes() == 500
    lc.cancel("test cancel")
    t.join(3.0)
    assert not t.is_alive(), "grant wait must abort on cancellation"
    assert err, "terminal error must propagate, never be swallowed"
    assert gov.reserved_bytes() == 0, "reservation leaked on cancel"


# ---------------------------------------------------------------------------
# watermarks + pressure shed
# ---------------------------------------------------------------------------

def test_background_watermark_spill(gov):
    cat = _SpillCat(device_limit=1000)
    gov.register(cat, "qa", None, {})
    gov._poll_s = 0.02
    before = get_registry().snapshot()["counters"].get(
        "governor_background_spills", 0)
    gov.account(cat, 900)  # 90% > high watermark 0.85
    deadline = time.monotonic() + 3.0
    while not cat.requests and time.monotonic() < deadline:
        time.sleep(0.02)
    assert cat.requests, "background thread never spilled"
    # asked to get back under the LOW watermark: 900 - 0.65*1000
    assert cat.requests[0] == 900 - 650
    after = get_registry().snapshot()["counters"][
        "governor_background_spills"]
    assert after > before


def test_background_thread_exits_with_last_catalog(gov):
    """The watermark daemon lives exactly as long as a catalog is
    registered: a drained session leaves no tpu-mem-governor thread."""
    gov._poll_s = 0.02
    a, b = _SpillCat(), _SpillCat()
    gov.register(a, "qa", None, {})
    gov.register(b, "qb", None, {})
    t = gov._bg_thread
    assert t is not None and t.is_alive() and t.name == "tpu-mem-governor"
    gov.unregister(a)
    assert gov._bg_thread is t and t.is_alive()
    gov.unregister(b)
    t.join(5.0)
    assert not t.is_alive() and gov._bg_thread is None


def test_pressure_shed_pauses_admissions(gov):
    from spark_rapids_tpu.exec.lifecycle import (AdmissionController,
                                                 QueryRejected)
    cat = _SpillCat(device_limit=1000)
    gov.register(cat, "qa", None, {})
    gov._shed_hold = 0.05
    gov.account(cat, 990)  # 99% > shed watermark 0.95
    time.sleep(0.15)       # sustain past the hold
    ac = AdmissionController(max_concurrent=4)
    ac.pressure_hook = gov.admission_pressure
    with pytest.raises(QueryRejected, match="shedWatermark"):
        ac.admit("qNew")
    # pressure relief resumes admissions
    gov.account(cat, -990)
    assert gov.admission_pressure() is None
    tok = ac.admit("qNew2")
    ac.release()


def test_transient_spike_does_not_shed(gov):
    cat = _SpillCat(device_limit=1000)
    gov.register(cat, "qa", None, {})
    gov._shed_hold = 10.0
    gov.account(cat, 990)
    assert gov.admission_pressure() is None  # spike shorter than hold


# ---------------------------------------------------------------------------
# fault points
# ---------------------------------------------------------------------------

def test_governor_fault_points_registered():
    from spark_rapids_tpu.faults import KNOWN_POINTS
    assert "memory.grant.stall" in KNOWN_POINTS
    assert "memory.governor.oom_storm" in KNOWN_POINTS


def test_oom_storm_fault_denies_reclaim(gov):
    from spark_rapids_tpu.faults import FaultRegistry
    cat = _SpillCat(device_limit=1 << 20)
    cat.faults = FaultRegistry("memory.governor.oom_storm:oom,times=0")
    gov.register(cat, "qa", None, {})
    assert gov.reclaim(cat, 4096) == 0
    assert cat.requests == []  # arbitration bypassed entirely


# ---------------------------------------------------------------------------
# end-to-end wiring + gate-off reversibility
# ---------------------------------------------------------------------------

def _toy_query(session, rows=2000):
    from spark_rapids_tpu.expr.aggregates import CountStar, Sum
    from spark_rapids_tpu.expr.core import col
    schema = T.Schema([T.StructField("k", T.IntegerType(), True),
                       T.StructField("v", T.LongType(), True)])
    df = session.from_pydict({"k": [i % 7 for i in range(rows)],
                              "v": list(range(rows))}, schema, partitions=2)
    return df.group_by("k").agg(Sum(col("v")), CountStar())


def test_execctx_registers_and_explain_carries_governor_line():
    from spark_rapids_tpu.exec.core import (ExecCtx, _rows_from_host,
                                            device_to_host)
    from spark_rapids_tpu.memory.governor import get_governor
    from spark_rapids_tpu.plan.overrides import explain_analyze
    from spark_rapids_tpu.session import TpuSession
    s = TpuSession({})
    dfa = _toy_query(s)
    ov, meta = dfa._overridden(quiet=True)
    with ExecCtx(backend="device", conf=s.conf) as ctx:
        rows = []
        for b in meta.exec_node.execute(ctx):
            rows.extend(_rows_from_host(device_to_host(b)))
        gov = get_governor()
        stats = gov.query_stats(ctx.query_id)
        assert ctx.query_id in stats
        cat = ctx.cache.get("catalog")
        assert cat.governor is gov
        assert stats[ctx.query_id]["device_bytes"] == cat.device_used
        assert stats[ctx.query_id]["peak_bytes"] > 0
        txt = explain_analyze(meta.exec_node, ctx)
        assert any(l.startswith("governor: ") for l in txt.splitlines())
    # close() unregistered the ledger
    assert ctx.query_id not in get_governor().query_stats()
    assert len(rows) == 7
    s.shutdown(drain=True)


def test_gate_off_is_byte_identical():
    """enabled=false: no registration, legacy spill paths, identical
    plans and results to the governed run of the same query."""
    from spark_rapids_tpu.exec.core import (ExecCtx, _rows_from_host,
                                            device_to_host)
    from spark_rapids_tpu.session import TpuSession

    def run(conf):
        s = TpuSession(conf)
        dfa = _toy_query(s)
        ov, meta = dfa._overridden(quiet=True)
        plan_str = meta.exec_node.tree_string()
        with ExecCtx(backend="device", conf=s.conf) as ctx:
            rows = []
            for b in meta.exec_node.execute(ctx):
                rows.extend(_rows_from_host(device_to_host(b)))
            gov_attr = ctx.cache.get("catalog").governor
        s.shutdown(drain=True)
        return sorted(rows), plan_str, gov_attr

    rows_on, plan_on, gov_on = run({})
    rows_off, plan_off, gov_off = run(
        {"spark.rapids.memory.governor.enabled": "false"})
    assert gov_on is not None
    assert gov_off is None, "gate-off must not register a governor"
    assert rows_on == rows_off
    assert plan_on == plan_off
