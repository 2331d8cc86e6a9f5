"""Self-driving control plane (ISSUE 18): pure rules, loop wiring, and
chaos behavior.

The rule tests drive control/rules.py against synthetic signals with no
engine, no threads, and no jax — AIMD up/down/bounds, SLO
shed-and-restore hysteresis, watermark retreat-and-heal, fleet
hysteresis + cooldown.  The loop tests run a real control-enabled
TpuSession (tiny pydict queries) and assert actuation, reversibility
(disabled = byte-identical plans + untouched counters; stop() restores
every knob), thread lifecycle (no leak after shutdown), and the two
chaos points: frozen signals decay to no-ops (control.signal.stale)
and dropped actuations re-derive next tick (control.actuate.drop).
"""
import threading
import time

import pytest

import spark_rapids_tpu.types as T
from spark_rapids_tpu.control.rules import (Decision, FleetRule,
                                            SloTracker, WatermarkRule,
                                            aimd_admission)
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.session import TpuSession

SCHEMA = T.Schema([T.StructField("a", T.LongType())])


def _session(extra=None, interval="0.05"):
    conf = {"spark.rapids.control.enabled": "true",
            "spark.rapids.control.intervalSeconds": interval}
    conf.update(extra or {})
    return TpuSession(conf)


def _df(s, n=64):
    return s.from_pydict({"a": list(range(n))}, SCHEMA)


# ---------------------------------------------------------------------------
# AIMD admission rule
# ---------------------------------------------------------------------------

def test_aimd_decreases_multiplicatively_on_congestion():
    d = aimd_admission(8, queue_wait_p99=0.01, congested=True, active=8,
                       min_cap=1, max_cap=16, queue_wait_target=0.25)
    assert d.action == "decrease" and d.detail["to"] == 4
    # and again: 4 -> 2 -> 1, clamped at min_cap
    d = aimd_admission(2, queue_wait_p99=None, congested=True, active=2,
                       min_cap=1, max_cap=16, queue_wait_target=0.25)
    assert d.detail["to"] == 1
    assert aimd_admission(1, queue_wait_p99=None, congested=True,
                          active=1, min_cap=1, max_cap=16,
                          queue_wait_target=0.25) is None


def test_aimd_increases_additively_when_healthy_but_queued():
    d = aimd_admission(4, queue_wait_p99=0.5, congested=False, active=4,
                       min_cap=1, max_cap=16, queue_wait_target=0.25)
    assert d.action == "increase" and d.detail["to"] == 5
    # at max_cap: no further increase
    assert aimd_admission(16, queue_wait_p99=0.5, congested=False,
                          active=16, min_cap=1, max_cap=16,
                          queue_wait_target=0.25) is None
    # healthy and fast: no decision at all
    assert aimd_admission(4, queue_wait_p99=0.01, congested=False,
                          active=1, min_cap=1, max_cap=16,
                          queue_wait_target=0.25) is None
    # no traffic (None p99): no decision
    assert aimd_admission(4, queue_wait_p99=None, congested=False,
                          active=0, min_cap=1, max_cap=16,
                          queue_wait_target=0.25) is None


def test_aimd_bounds_an_unbounded_cap_only_on_congestion():
    assert aimd_admission(0, queue_wait_p99=2.0, congested=False,
                          active=9, min_cap=1, max_cap=16,
                          queue_wait_target=0.25) is None
    d = aimd_admission(0, queue_wait_p99=None, congested=True, active=9,
                       min_cap=1, max_cap=16, queue_wait_target=0.25)
    assert d.action == "bound" and 1 <= d.detail["to"] <= 16


def test_aimd_idempotent_rederivation():
    """The control.actuate.drop recovery story: deriving twice from the
    same signals yields the same decision (no internal state)."""
    kw = dict(queue_wait_p99=0.4, congested=True, active=8, min_cap=1,
              max_cap=16, queue_wait_target=0.25)
    d1, d2 = aimd_admission(8, **kw), aimd_admission(8, **kw)
    assert d1.detail == d2.detail and d1.action == d2.action


# ---------------------------------------------------------------------------
# SLO shed/restore hysteresis
# ---------------------------------------------------------------------------

def test_slo_shed_requires_consecutive_violations():
    t = SloTracker({"web": 1.0}, violation_ticks=3, recovery_ticks=2)
    assert t.observe({"web": 5.0}) == []
    assert t.observe({"web": 0.5}) == []        # streak broken
    assert t.observe({"web": 5.0}) == []
    assert t.observe({"web": 5.0}) == []
    out = t.observe({"web": 5.0})               # third consecutive
    assert [d.action for d in out] == ["shed"]
    assert "web" in t.shed and t.any_violating()


def test_slo_restore_requires_consecutive_health():
    t = SloTracker({"web": 1.0}, violation_ticks=1, recovery_ticks=3)
    assert [d.action for d in t.observe({"web": 2.0})] == ["shed"]
    t.observe({"web": 0.1})
    t.observe({"web": 2.0})                     # relapse resets streak
    t.observe({"web": 0.1})
    t.observe({"web": 0.1})
    out = t.observe({"web": 0.1})
    assert [d.action for d in out] == ["restore"]
    assert t.shed == {} and not t.any_violating()


def test_slo_silence_counts_as_healthy():
    """A shed tenant that stops sending traffic (p99=None) must still
    recover — otherwise a shed is a permanent ban."""
    t = SloTracker({"web": 1.0}, violation_ticks=1, recovery_ticks=2)
    t.observe({"web": 9.0})
    assert "web" in t.shed
    t.observe({"web": None})
    out = t.observe({"web": None})
    assert [d.action for d in out] == ["restore"]


def test_slo_only_configured_tenants_tracked():
    t = SloTracker({"web": 1.0}, violation_ticks=1)
    t.observe({"web": 0.1, "batch": 99.0})      # batch has no SLO
    assert t.shed == {} and t.status().keys() == {"web"}


# ---------------------------------------------------------------------------
# watermark adaptation
# ---------------------------------------------------------------------------

def test_watermark_steps_down_on_slow_spill_and_heals_back():
    r = WatermarkRule(base_high=0.85, base_low=0.70,
                      spill_p99_target=0.25, step=0.05, min_high=0.50,
                      heal_ticks=2)
    d = r.observe(spill_p99=1.0, grant_timeouts=0, grant_waits=3)
    assert d.action == "lower" and r.high == pytest.approx(0.80)
    assert r.low == pytest.approx(0.65)          # conf gap preserved
    # grant timeout alone is also a slow-tier signal
    d = r.observe(spill_p99=None, grant_timeouts=1, grant_waits=0)
    assert d.action == "lower" and r.high == pytest.approx(0.75)
    # healthy for heal_ticks: one step back up, never above base
    assert r.observe(spill_p99=0.01, grant_timeouts=0,
                     grant_waits=0) is None
    d = r.observe(spill_p99=0.01, grant_timeouts=0, grant_waits=0)
    assert d.action == "raise" and r.high == pytest.approx(0.80)
    for _ in range(10):
        r.observe(spill_p99=0.01, grant_timeouts=0, grant_waits=0)
    assert r.high == pytest.approx(0.85) and r.at_base()


def test_watermark_clamped_at_min_high():
    r = WatermarkRule(base_high=0.85, base_low=0.70, min_high=0.75,
                      step=0.2)
    assert r.observe(spill_p99=9.0, grant_timeouts=1,
                     grant_waits=0).detail["high_to"] == 0.75
    # already at the clamp: a worse signal produces NO decision (the
    # rule never oscillates against its own bound)
    assert r.observe(spill_p99=99.0, grant_timeouts=5,
                     grant_waits=9) is None


# ---------------------------------------------------------------------------
# fleet sizing
# ---------------------------------------------------------------------------

def test_fleet_scale_up_needs_sustained_overload_and_respects_max():
    r = FleetRule(min_workers=1, max_workers=3, up_ticks=2,
                  down_ticks=4, cooldown_s=0.0)
    assert r.observe(worker_count=1, overloaded=True, idle=False) is None
    d = r.observe(worker_count=1, overloaded=True, idle=False)
    assert d.action == "add_worker"
    # at max: no scale-up however overloaded
    for _ in range(5):
        assert r.observe(worker_count=3, overloaded=True,
                         idle=False) is None or False


def test_fleet_scale_down_slower_and_respects_min():
    r = FleetRule(min_workers=1, max_workers=0, up_ticks=2,
                  down_ticks=3, cooldown_s=0.0)
    for _ in range(2):
        assert r.observe(worker_count=2, overloaded=False,
                         idle=True) is None
    d = r.observe(worker_count=2, overloaded=False, idle=True)
    assert d.action == "remove_worker"
    for _ in range(10):
        assert r.observe(worker_count=1, overloaded=False,
                         idle=True) is None    # at minWorkers


def test_fleet_cooldown_blocks_flapping():
    r = FleetRule(min_workers=1, max_workers=0, up_ticks=1,
                  down_ticks=1, cooldown_s=100.0)
    now = 1000.0
    d = r.observe(worker_count=1, overloaded=True, idle=False, now=now)
    assert d.action == "add_worker"
    # immediately idle: inside the cooldown nothing fires either way
    assert r.observe(worker_count=2, overloaded=False, idle=True,
                     now=now + 1) is None
    assert r.observe(worker_count=2, overloaded=True, idle=False,
                     now=now + 2) is None
    # past the cooldown the idle streak fires again
    d = r.observe(worker_count=2, overloaded=False, idle=True,
                  now=now + 101)
    assert d is not None and d.action == "remove_worker"


def test_decision_to_dict_round_trip():
    d = Decision("admission", "decrease", "why", {"from": 8, "to": 4})
    out = d.to_dict()
    assert out["rule"] == "admission" and out["detail"]["to"] == 4
    assert out["applied"] is False and out["dropped"] is False


# ---------------------------------------------------------------------------
# loop wiring against a live session (no cluster, tiny queries)
# ---------------------------------------------------------------------------

def test_loop_thread_lifecycle_and_no_leak():
    s = _session()
    try:
        assert s._control.running
        assert any(t.name == "control-loop"
                   for t in threading.enumerate())
    finally:
        s.shutdown()
    assert s._control is None
    assert not any(t.name == "control-loop"
                   for t in threading.enumerate())


def test_stop_restores_cap_hook_and_sheds():
    s = _session({"spark.rapids.sql.admission.maxConcurrentQueries": "4"})
    try:
        control = s._control
        adm = s._admission_controller()
        prev_hook = control._prev_hook
        # simulate learned state
        adm.set_max_concurrent(2)
        control.slo.shed["web"] = "test shed"
        control.stop()
        assert adm.max_concurrent == 4, "cap not restored to conf"
        assert adm.pressure_hook is prev_hook
        assert control.slo.shed == {}
    finally:
        s.shutdown()


def test_slo_shed_targets_only_violating_tenant():
    """The composed pressure hook returns a reason for the shed tenant
    and defers (None) for everyone else — admission's over-share gate
    then sheds only the violator; neighbors are never even 'spared'."""
    s = _session({"spark.rapids.control.slo.batch.p99Seconds": "0.001",
                  "spark.rapids.control.slo.web.p99Seconds": "60"})
    try:
        control = s._control
        control.slo.shed["batch"] = "p99 over SLO (test)"
        assert control._pressure_hook("batch")
        assert control._pressure_hook("web") is None
        assert control._pressure_hook("default") is None
        from spark_rapids_tpu.exec.lifecycle import QueryRejected
        # batch dominates the running set BEFORE the shed lands (a
        # just-shed idle tenant is also rejected — total=0 counts as
        # over-share — but the interesting property is mid-traffic)
        control.slo.shed.clear()
        adm = s._admission_controller()
        for i in range(3):
            adm.admit(f"b{i}", tenant="batch")
        adm.admit("w-warm", tenant="web")
        before = get_registry().snapshot()
        control.slo.shed["batch"] = "p99 over SLO (test)"
        with pytest.raises(QueryRejected, match="over SLO"):
            adm.admit("b3", tenant="batch")
        # web flows untouched, and is NOT counted as pressure-spared
        # (the hook returned None for it, not a reason)
        adm.admit("w0", tenant="web")
        d = get_registry().delta(before)["counters"]
        assert d.get("admission.tenant.batch.rejected") == 1
        assert d.get("admission.tenant.web.rejected", 0) == 0
        assert d.get("admission.tenant.web.pressure_spared", 0) == 0
    finally:
        s.shutdown()


def test_tick_derives_aimd_from_real_histograms():
    """Synthetic congestion: a governor grant timeout in the window
    halves the cap; the decision is traced and recorded."""
    s = _session({"spark.rapids.sql.admission.maxConcurrentQueries": "8",
                  "spark.rapids.control.intervalSeconds": "999"})
    try:
        control = s._control
        control.tick()                          # baseline snapshot
        get_registry().inc("governor_grant_timeouts")
        applied = control.tick()
        acts = [(d.rule, d.action) for d in applied]
        assert ("admission", "decrease") in acts, acts
        assert s._admission_controller().max_concurrent == 4
        assert any(d["rule"] == "admission"
                   for d in control.status()["decisions"])
    finally:
        s.shutdown()


def test_e2e_histogram_feeds_slo_and_sheds_then_restores():
    """End-to-end: slow observed walls for a tenant with a tiny SLO
    shed it after violationTicks; silence restores it."""
    s = _session({"spark.rapids.control.slo.batch.p99Seconds": "0.0001",
                  "spark.rapids.control.slo.violationTicks": "2",
                  "spark.rapids.control.slo.recoveryTicks": "2",
                  "spark.rapids.control.intervalSeconds": "999"})
    try:
        control = s._control
        control.tick()
        reg = get_registry()
        for _ in range(2):
            reg.observe("query.tenant.batch.e2e_seconds", 0.5)
            control.tick()
        assert "batch" in control.slo.shed
        st = control.status()
        assert st["slo"]["batch"]["shed"] is True
        # window drains (windowTicks of silence) -> healthy -> restore
        for _ in range(2 + control.window_ticks):
            control.tick()
        assert control.slo.shed == {}
    finally:
        s.shutdown()


# ---------------------------------------------------------------------------
# chaos: frozen signals and dropped actuations
# ---------------------------------------------------------------------------

def test_stale_signal_fault_decays_to_noops_no_oscillation():
    """With the registry snapshot frozen (control.signal.stale firing
    every tick), deltas are empty: the loop must settle — no decision
    churn, no deadlock — and the staleness is counted."""
    s = _session({"spark.rapids.control.intervalSeconds": "999",
                  "spark.rapids.test.faults":
                      "control.signal.stale:stale,times=0"})
    try:
        control = s._control
        control.tick()
        get_registry().inc("governor_grant_timeouts")   # invisible: frozen
        before = get_registry().snapshot()
        decisions = []
        for _ in range(8):
            decisions.extend(control.tick())
        assert decisions == [], [d.to_dict() for d in decisions]
        d = get_registry().delta(before)["counters"]
        assert d.get("control_signal_stale", 0) >= 8
        assert s._admission_controller().max_concurrent == \
            control._base_cap
    finally:
        s.shutdown()


def test_dropped_actuation_rederives_next_tick():
    """control.actuate.drop loses the first decision in flight; the
    SAME decision re-derives from fresh signals next tick and lands.
    Dropped decisions are recorded as dropped, never applied."""
    s = _session({"spark.rapids.sql.admission.maxConcurrentQueries": "8",
                  "spark.rapids.control.intervalSeconds": "999",
                  "spark.rapids.test.faults":
                      "control.actuate.drop:drop,times=1,rule=admission"})
    try:
        control = s._control
        control.tick()
        adm = s._admission_controller()
        get_registry().inc("governor_grant_timeouts")
        applied = control.tick()          # the admission decision drops
        assert "admission" not in [d.rule for d in applied]
        assert adm.max_concurrent == 8, "dropped decision must not act"
        dropped = [d for d in control.decisions if d.dropped]
        assert [d.rule for d in dropped] == ["admission"]
        assert not dropped[0].applied
        # congestion persists in the sliding window: re-derived + applied
        applied = control.tick()
        assert ("admission", "decrease") in [(d.rule, d.action)
                                             for d in applied]
        assert adm.max_concurrent == 4
    finally:
        s.shutdown()


def test_loop_survives_a_bad_tick():
    """A tick that raises is counted and the thread keeps ticking."""
    s = _session(interval="0.02")
    try:
        control = s._control
        original = control._signals
        calls = {"n": 0}

        def boom():
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected signal failure")
            return original()

        control._signals = boom
        deadline = time.monotonic() + 5.0
        while calls["n"] < 3 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert calls["n"] >= 3, "loop died after a bad tick"
        assert control.running
    finally:
        s.shutdown()


# ---------------------------------------------------------------------------
# reversibility: disabled = byte-identical
# ---------------------------------------------------------------------------

def test_disabled_is_byte_identical_to_static(monkeypatch):
    import sys
    # this file imported the control package; drop it so that a
    # disabled session re-importing it shows (the whole-interpreter
    # half is tests/test_telemetry.py::test_disabled_path_never_imports)
    for m in [m for m in sys.modules
              if m.startswith("spark_rapids_tpu.control")]:
        monkeypatch.delitem(sys.modules, m)
    s = TpuSession({"spark.rapids.control.enabled": "false"})
    try:
        assert s._control is None
        assert not [m for m in sys.modules
                    if m.startswith("spark_rapids_tpu.control")]
        assert dict(s.conf.settings) == \
            {"spark.rapids.control.enabled": "false"}
    finally:
        s.shutdown()
    s = TpuSession({})
    try:
        df = _df(s)
        ov, meta = df._overridden(quiet=True)
        plan_off = ov.explain(meta)
        before = get_registry().snapshot()
        rows = df.collect()
        assert len(rows) == 64
        d = get_registry().delta(before)["counters"]
        assert not any(k.startswith("control") for k in d), d
        # the conf object itself is untouched by planning
        assert "spark.rapids.control.enabled" not in s.conf.settings
    finally:
        s.shutdown()
    # same plan text as a control-enabled session whose router has
    # learned nothing (no history dir): routing must be a strict no-op
    s2 = _session()
    try:
        df2 = _df(s2)
        conf = s2._routed_conf(df2._plan)
        assert conf is s2.conf, "no-history routing must not fork conf"
        ov2, meta2 = df2._overridden(quiet=True)
        assert ov2.explain(meta2) == plan_off
    finally:
        s2.shutdown()


# ---------------------------------------------------------------------------
# history-driven routing
# ---------------------------------------------------------------------------

def test_route_express_after_min_samples(tmp_path):
    s = _session({"spark.rapids.obs.history.dir": str(tmp_path),
                  "spark.rapids.control.route.expressWallSeconds": "10",
                  "spark.rapids.control.route.minSamples": "3",
                  "spark.rapids.control.intervalSeconds": "999"})
    try:
        df = _df(s)
        # below minSamples: unrouted
        for _ in range(2):
            df.collect()
        assert s._routed_conf(df._plan) is s.conf
        df.collect()
        conf = s._routed_conf(df._plan)
        assert conf is not s.conf
        assert conf.settings["spark.rapids.control.express"] == "true"
        assert conf.settings["spark.rapids.tpu.mesh.deviceCount"] == "1"
        assert conf.settings["spark.sql.adaptive.enabled"] == "false"
        # the routed run still returns correct rows
        assert len(df.collect()) == 64
        # route decisions: audited once (on change), counted per query
        kinds = [d["action"] for d in s._control.status()["decisions"]
                 if d["rule"] == "route"]
        assert kinds == ["express"]
        assert s._control.status()["route"]["indexed_fingerprints"] >= 1
    finally:
        s.shutdown()


def test_route_learns_from_history_file_of_other_process(tmp_path):
    """Entries written by another process (simulated: direct file
    append) are picked up via the stat-gated refresh."""
    import json as _json

    from spark_rapids_tpu.obs.history import HISTORY_FILE
    s = _session({"spark.rapids.obs.history.dir": str(tmp_path),
                  "spark.rapids.control.route.minSamples": "2",
                  "spark.rapids.control.intervalSeconds": "999"})
    try:
        df = _df(s)
        fp = s._control._fingerprint(df._plan)
        assert fp
        p = tmp_path / HISTORY_FILE
        with open(p, "w") as f:
            for _ in range(3):
                f.write(_json.dumps({
                    "plan_fingerprint": fp, "state": "FINISHED",
                    "wall_s": 0.01, "mesh_devices": 1}) + "\n")
        idx = s._control._history_index
        idx.min_refresh_s = 0.0
        conf = s._routed_conf(df._plan)
        assert conf is not s.conf
        assert conf.settings["spark.rapids.control.express"] == "true"
    finally:
        s.shutdown()


def test_express_marker_skips_stage_boundaries():
    from spark_rapids_tpu.conf import TpuConf
    from spark_rapids_tpu.plan.overrides import TpuOverrides

    conf = TpuConf({"spark.rapids.control.express": "true",
                    "spark.sql.adaptive.enabled": "true"})
    ov = TpuOverrides(conf)

    # the express marker must win over adaptive=true: the method
    # returns before touching the plan at all (exec_node=None would
    # blow up inside the AQE splitter, so surviving proves the
    # early return)
    class _Root:
        exec_node = None
    root = _Root()
    ov._insert_stage_boundaries(root)
    assert root.exec_node is None


# ---------------------------------------------------------------------------
# /control endpoint + degraded healthz
# ---------------------------------------------------------------------------

def test_control_endpoint_and_degraded_healthz():
    import json as _json
    import urllib.request

    from spark_rapids_tpu.obs.http import ObsHttpServer
    s = _session({"spark.rapids.control.slo.batch.p99Seconds": "0.001",
                  "spark.rapids.control.intervalSeconds": "999"})
    s._http = ObsHttpServer(s, 0)   # conf port 0 = off; bind ephemeral
    try:
        base = s._http.address
        body = _json.loads(urllib.request.urlopen(
            base + "/control", timeout=5).read())
        assert body["enabled"] is True
        assert body["admission"]["max_concurrent"] is not None
        assert "batch" in body["slo"]
        # shed the tenant: healthz flips to degraded WITH the name
        s._control.slo.shed["batch"] = "test"
        health = s._http.health()
        assert health["status"] == "degraded"
        assert health["shed_tenants"] == ["batch"]
        body = _json.loads(urllib.request.urlopen(
            base + "/control", timeout=5).read())
        assert body["shed_tenants"] == {"batch": "test"}
    finally:
        s.shutdown()


def test_control_endpoint_stub_when_disabled():
    from spark_rapids_tpu.obs.http import ObsHttpServer
    s = TpuSession({})
    s._http = ObsHttpServer(s, 0)
    try:
        assert s._http.control() == {"enabled": False}
        assert s._http.health()["status"] == "ok"
    finally:
        s.shutdown()


def test_control_confs_registered_and_slo_parser():
    from spark_rapids_tpu.conf import _REGISTRY
    from spark_rapids_tpu.control import parse_tenant_slos
    for key in ("spark.rapids.control.enabled",
                "spark.rapids.control.intervalSeconds",
                "spark.rapids.control.admission.maxConcurrent",
                "spark.rapids.control.governor.watermarkStep",
                "spark.rapids.control.fleet.cooldownSeconds"):
        assert key in _REGISTRY, key
    slos = parse_tenant_slos({
        "spark.rapids.control.slo.web.p99Seconds": "1.5",
        "spark.rapids.control.slo.batch.p99Seconds": "30",
        "spark.rapids.control.slo.bad.p99Seconds": "nope",
        "spark.rapids.control.slo.violationTicks": "3",
        "unrelated": "x"})
    assert slos == {"web": 1.5, "batch": 30.0}
