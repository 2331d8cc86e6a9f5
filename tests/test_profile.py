"""Cost-attribution plane: operator/device profiler, HBM occupancy
timeline, per-tenant metering, live progress (obs/profile.py +
obs/metering.py) and the tools.history forensics over their output.

Covers the plane's contracts, not just happy paths:

* fused-stage / mesh-region time is attributed to member ops as child
  rows that never double-count in top-level sums;
* the per-query artifact validates against ci/obs_schema.json, a
  synthetic one and the export of a real query over the 8-device mesh;
* the two accounting paths (per-tenant charges vs. instrumentation
  totals) conserve, and the cross-check catches books that DON'T;
* worker drain/merge deltas move tenant charges exactly once;
* the profiler is inert when disabled (ExecCtx.profiler is None) —
  the stronger sys.modules guarantee needs a fresh interpreter and is
  tests/test_telemetry.py::test_disabled_path_never_imports;
* Prometheus label escaping survives hostile tenant names, and
  histogram snapshot merges are exact under scrape-while-observe.
"""
import json
import threading

import pytest

from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.obs.metering import USAGE_METRICS, TenantMeter
from spark_rapids_tpu.obs.profile import (ProfileStore, QueryProfiler,
                                          live_progress)
from spark_rapids_tpu.obs.registry import (Histogram, MetricsRegistry,
                                           get_registry,
                                           merge_histogram_snapshots)

PROF_CONF = {"spark.rapids.obs.profile.enabled": "true"}


def _conf(extra=None):
    return TpuConf(dict(PROF_CONF, **(extra or {})))


class _FusedNode:
    """Stand-in for FusedStageExec: a container exposing fused_ops."""

    def __init__(self, members):
        self.fused_ops = tuple(members)


class _Leaf:
    pass


class _LeafA:
    pass


class _LeafB:
    pass


# ---------------------------------------------------------------------------
# operator profiler: attribution + artifact
# ---------------------------------------------------------------------------

def test_member_attribution_splits_container_time():
    prof = QueryProfiler("q-attr", _conf())
    node = _FusedNode([_LeafA(), _LeafB()])
    prof.record_op(node, "FusedStageExec#1", active_s=0.4, wall_s=0.5,
                   batches=2, rows=100, partition=0)
    ops = prof.operators()
    top = {k: e for k, e in ops.items() if e["parent"] is None}
    kids = {k: e for k, e in ops.items() if e["parent"]}
    assert list(top) == ["FusedStageExec#1"]
    assert len(kids) == 2
    # equal split, and the member sum never exceeds the container
    for e in kids.values():
        assert e["parent"] == "FusedStageExec#1"
        assert e["device_s"] == pytest.approx(0.2)
    assert sum(e["device_s"] for e in kids.values()) <= \
        top["FusedStageExec#1"]["device_s"] + 1e-9
    # top-level device_seconds counts the container once, members never
    assert prof.device_seconds() == pytest.approx(0.4)


def test_flamegraph_members_not_double_counted():
    prof = QueryProfiler("q-flame", _conf())
    prof.record_op(_Leaf(), "ScanExec#0", 0.1, 0.1, 1, 10, 0)
    prof.record_op(_FusedNode([_Leaf()]), "FusedStageExec#1",
                   0.2, 0.2, 1, 10, 0)
    text = prof.flamegraph()
    lines = [ln for ln in text.splitlines() if ln]
    # every line is "frame[;frame] value-in-us"
    total_us = 0
    for ln in lines:
        stack, val = ln.rsplit(" ", 1)
        assert stack.startswith("q-flame;")
        total_us += int(val)
    # container frames with members contribute ONLY via member lines
    assert not any(ln.rsplit(" ", 1)[0].endswith("FusedStageExec#1")
                   for ln in lines)
    assert total_us == pytest.approx((0.1 + 0.2) * 1e6, rel=0.01)


def test_artifact_validates_against_checked_in_schema():
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    from validate_obs import load_schema, validate
    prof = QueryProfiler("q-schema", _conf())
    prof.record_op(_FusedNode([_Leaf(), _Leaf()]), "FusedStageExec#2",
                   0.3, 0.4, 3, 42, 1)
    art = prof.artifact()
    assert validate(art, load_schema("profile")) == []
    assert art["kind"] == "profile" and art["query_id"] == "q-schema"
    blob = prof.history_blob()
    assert validate(blob, load_schema("history")["properties"]
                    ["profile"]) == []


def test_profiler_op_table_is_bounded():
    prof = QueryProfiler("q-bound", _conf(
        {"spark.rapids.obs.profile.maxOps": "8"}))
    for i in range(50):
        prof.record_op(_Leaf(), f"ProjectExec#{i}", 0.001, 0.001, 1, 1, 0)
    ops = prof.operators()
    assert len(ops) <= 9  # 8 + the "(other)" overflow row
    assert "(other)" in ops
    # overflow still conserves: nothing dropped from the total
    assert prof.device_seconds() == pytest.approx(0.05)


def test_profile_store_keeps_per_fingerprint_tables():
    store = ProfileStore(max_fingerprints=2)
    store.note("fp-a", {"X": {"op": "X", "device_s": 1.0}}, wall_s=1.0)
    store.note("fp-b", {"Y": {"op": "Y", "device_s": 2.0}}, wall_s=2.0)
    store.note("fp-c", {"Z": {"op": "Z", "device_s": 3.0}}, wall_s=3.0)
    snap = store.snapshot()
    assert "fp-a" not in snap  # LRU-evicted
    assert set(snap) == {"fp-b", "fp-c"}


# ---------------------------------------------------------------------------
# per-tenant metering + conservation
# ---------------------------------------------------------------------------

def test_conservation_holds_and_detects_broken_books():
    m = TenantMeter()
    # charge path and instrumentation path agree
    m.charge("etl", "fp1", {"device_seconds": 1.0, "queries": 1})
    m.charge("web", "fp2", {"device_seconds": 3.0, "queries": 1})
    m.add_total("device_seconds", 4.0)
    get_registry().inc("queries_executed", 2)
    cons = m.conservation()
    assert cons["ok"], cons
    assert cons["device_seconds"]["tenants_sum"] == pytest.approx(4.0)
    # now break the instrumentation side by >5%: the cross-check trips
    m.add_total("device_seconds", 1.0)
    cons = m.conservation()
    assert not cons["ok"]
    assert not cons["device_seconds"]["ok"]
    # a tighter tolerance flags what a loose one forgives
    m2 = TenantMeter()
    m2.charge("t", None, {"device_seconds": 1.0})
    m2.add_total("device_seconds", 1.04)
    assert m2.conservation(tolerance=0.05)["ok"]
    assert not m2.conservation(tolerance=0.01)["ok"]


def test_meter_snapshot_tracks_tenant_and_fingerprint():
    m = TenantMeter()
    m.charge("etl", "fp1", {"device_seconds": 0.5, "scan_bytes": 100})
    m.charge("etl", "fp1", {"device_seconds": 0.5, "scan_bytes": 100})
    snap = m.snapshot()
    assert snap["tenants"]["etl"]["device_seconds"] == pytest.approx(1.0)
    assert snap["tenants"]["etl"]["scan_bytes"] == pytest.approx(200)
    assert snap["fingerprints"]["fp1"]["device_seconds"] == \
        pytest.approx(1.0)
    assert set(snap) >= {"tenants", "fingerprints", "totals", "workers"}


def test_drain_merge_moves_charges_exactly_once():
    worker, driver = TenantMeter(), TenantMeter()
    worker.charge("etl", "fp1", {"device_seconds": 2.0})
    d1 = worker.drain_delta()
    assert d1 is not None
    assert d1["tenants"]["etl"]["device_seconds"] == pytest.approx(2.0)
    # nothing new moved: the next drain is empty, not a re-ship
    assert worker.drain_delta() is None
    worker.charge("etl", "fp1", {"device_seconds": 0.5})
    d2 = worker.drain_delta()
    assert d2["tenants"]["etl"]["device_seconds"] == pytest.approx(0.5)
    for d in (d1, d2):
        driver.merge_delta({"tenants": d["tenants"]})
    assert driver.snapshot()["tenants"]["etl"]["device_seconds"] == \
        pytest.approx(2.5)
    # worker totals land under the per-worker ledger, NOT the driver's
    # own conservation books
    driver.ingest_worker("w1", {"device_seconds": 2.5})
    snap = driver.snapshot()
    assert snap["workers"]["w1"]["device_seconds"] == pytest.approx(2.5)
    assert driver.conservation()["device_seconds"]["total"] < 2.0


def test_usage_metrics_is_the_closed_vocabulary():
    m = TenantMeter()
    m.charge("t", None, {"device_seconds": 1.0, "bogus_metric": 9.0})
    assert "bogus_metric" not in m.snapshot()["tenants"]["t"]
    assert m.snapshot()["tenants"]["t"]["device_seconds"] == \
        pytest.approx(1.0)
    assert set(USAGE_METRICS) >= {"device_seconds", "hbm_byte_seconds",
                                  "shuffle_bytes", "spill_bytes",
                                  "scan_bytes", "compile_seconds",
                                  "queries"}


# ---------------------------------------------------------------------------
# live progress
# ---------------------------------------------------------------------------

class _FakeMetric:
    def __init__(self, rows):
        self.values = {"numOutputRows": float(rows)}


class _FakeCtx:
    def __init__(self, rows):
        self.metrics = {"ScanExec#0@p0": _FakeMetric(rows)}


class _FakeLc:
    def __init__(self, rows, fp, started):
        self.ctx = _FakeCtx(rows)
        self.plan_fingerprint = fp
        self._started_at = started


def test_live_progress_uses_row_medians_then_wall_fallback():
    import time as _t
    from spark_rapids_tpu.obs.history import HistoryIndex
    idx = HistoryIndex()
    for w in (2.0, 2.0, 2.0):
        idx.note_entry({"plan_fingerprint": "fp-p", "state": "FINISHED",
                        "wall_s": w, "rows_processed": 1000,
                        "metering": {"device_seconds": 0.5}})
    lc = _FakeLc(rows=500, fp="fp-p", started=_t.monotonic() - 1.0)
    out = live_progress(lc, idx)
    assert out["rows_processed"] == 500
    assert out["percent_complete"] == pytest.approx(50.0, abs=0.2)
    assert out["eta_s"] == pytest.approx(1.0, rel=0.2)
    assert out["median_wall_s"] == pytest.approx(2.0)
    # unknown fingerprint: rows still reported, no pct/eta invented
    out = live_progress(_FakeLc(500, "fp-never-seen",
                                _t.monotonic()), idx)
    assert out == {"rows_processed": 500}
    # history without row counts degrades to elapsed/median-wall
    idx2 = HistoryIndex()
    idx2.note_entry({"plan_fingerprint": "fp-w", "state": "FINISHED",
                     "wall_s": 4.0})
    lc = _FakeLc(rows=0, fp="fp-w", started=_t.monotonic() - 1.0)
    out = live_progress(lc, idx2)
    assert out["percent_complete"] == pytest.approx(25.0, abs=1.0)


# ---------------------------------------------------------------------------
# disabled path (in-process half; fresh-interpreter half in
# tests/test_telemetry.py::test_disabled_path_never_imports)
# ---------------------------------------------------------------------------

def test_exec_ctx_profiler_is_none_when_disabled():
    from spark_rapids_tpu.exec.core import ExecCtx
    with ExecCtx(backend="device", conf=TpuConf({})) as ctx:
        assert ctx.profiler is None
        # the negative answer is cached so the hot path never re-reads
        # the conf
        assert ctx.cache.get("profiler") is None
        assert ctx.profiler is None
    with ExecCtx(backend="device", conf=_conf()) as ctx:
        p = ctx.profiler
        assert isinstance(p, QueryProfiler)
        assert ctx.profiler is p  # cached, not rebuilt per access


# ---------------------------------------------------------------------------
# HTTP views
# ---------------------------------------------------------------------------

@pytest.fixture()
def prof_session():
    from spark_rapids_tpu.session import TpuSession
    s = TpuSession(dict(PROF_CONF))
    yield s
    s.shutdown()


def _get_json(url):
    import urllib.request
    with urllib.request.urlopen(url, timeout=5) as r:
        assert r.status == 200
        return json.loads(r.read())


def test_http_profile_and_tenants_views(prof_session):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.obs.http import ObsHttpServer
    schema = T.Schema([T.StructField("v", T.LongType(), True)])
    prof_session.from_pydict({"v": list(range(64))}, schema,
                             partitions=2).collect(tenant="acct")
    srv = ObsHttpServer(prof_session, 0)
    try:
        prof = _get_json(srv.address + "/profile")
        assert prof["enabled"] is True
        assert "hbm" in prof and "fingerprints" in prof
        ten = _get_json(srv.address + "/tenants")
        assert ten["enabled"] is True
        assert ten["tenants"]["acct"]["queries"] >= 1
        assert "conservation" in ten
        q = _get_json(srv.address + "/queries")
        assert q["count"] == 0
    finally:
        srv.close()


def test_http_views_answer_disabled_without_importing():
    from spark_rapids_tpu.obs.http import ObsHttpServer
    from spark_rapids_tpu.session import TpuSession
    s = TpuSession({})
    srv = ObsHttpServer(s, 0)
    try:
        assert _get_json(srv.address + "/profile") == {"enabled": False}
        assert _get_json(srv.address + "/tenants") == {"enabled": False}
    finally:
        srv.close()
        s.shutdown()


# ---------------------------------------------------------------------------
# history entries carry the cost-attribution fields
# ---------------------------------------------------------------------------

def test_history_entry_has_metering_rows_and_profile(tmp_path):
    import os
    import sys
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.obs.history import HISTORY_FILE
    from spark_rapids_tpu.session import TpuSession
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    from validate_obs import load_schema, validate
    s = TpuSession(dict(PROF_CONF, **{
        "spark.rapids.obs.history.dir": str(tmp_path)}))
    try:
        schema = T.Schema([T.StructField("v", T.LongType(), True)])
        s.from_pydict({"v": list(range(100))}, schema,
                      partitions=2).collect(tenant="etl")
    finally:
        s.shutdown()
    lines = [json.loads(ln) for ln in
             (tmp_path / HISTORY_FILE).read_text().splitlines() if ln]
    e = next(x for x in lines if x.get("state") == "FINISHED")
    assert validate(e, load_schema("history")) == []
    assert e["tenant"] == "etl"
    assert e["metering"]["device_seconds"] >= 0.0
    assert e["metering"]["queries"] == 1
    assert e["rows_processed"] >= 0
    assert e["profile"]["operators"]
    assert e["profile"]["device_seconds"] == pytest.approx(
        sum(o["device_s"] for o in e["profile"]["operators"].values()
            if o["parent"] is None), abs=1e-6)


def test_profiled_mesh_query_exports_members_and_counter_tracks(tmp_path):
    """A real profiled query over the 8-device mesh: the exported
    artifact is schema-valid, the region's time is attributed to member
    rows that never exceed their container, the flamegraph is written,
    and the operator counter tracks are merged into the trace."""
    import glob
    import os
    import sys

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.expr.core import col
    from spark_rapids_tpu.session import TpuSession
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    from validate_obs import load_schema, validate
    pdir, tdir = tmp_path / "profiles", tmp_path / "traces"
    s = TpuSession(dict(PROF_CONF, **{
        "spark.rapids.tpu.mesh.deviceCount": 8,
        "spark.rapids.sql.resultCache.enabled": "false",
        "spark.rapids.obs.profile.dir": str(pdir),
        "spark.rapids.obs.trace.enabled": "true",
        "spark.rapids.obs.trace.dir": str(tdir)}))
    try:
        schema = T.Schema([T.StructField("k", T.IntegerType(), True),
                           T.StructField("v", T.LongType(), True)])
        df = s.from_pydict({"k": [i % 7 for i in range(512)],
                            "v": list(range(512))}, schema, partitions=4)
        rows = df.where(col("v") > 3).group_by("k") \
            .agg(Sum(col("v")).alias("sv")).collect()
        assert len(rows) == 7
    finally:
        s.shutdown()
    exported = glob.glob(str(pdir / "profile_*.json"))
    assert len(exported) == 1, exported
    prof = json.load(open(exported[0]))
    assert validate(prof, load_schema("profile")) == []
    ops = prof["operators"]
    shares: dict = {}
    for e in ops.values():
        if e["parent"]:
            shares.setdefault(e["parent"], []).append(e["device_s"])
    assert shares, f"no member-attributed rows: {sorted(ops)}"
    for parent, members in shares.items():
        # every exported number is rounded to a microsecond on its own
        assert sum(members) <= ops[parent]["device_s"] \
            + 1e-6 * (len(members) + 1), (parent, members)
    assert prof["flamegraph"].strip()
    flame = glob.glob(str(pdir / "flamegraph_*.txt"))
    assert flame and open(flame[0]).read().strip()
    traces = glob.glob(str(tdir / "trace_*.json"))
    assert len(traces) == 1, traces
    doc = json.load(open(traces[0]))
    assert validate(doc, load_schema("trace")) == []
    assert any(ev.get("ph") == "C"
               and ev["name"] == "operator.device_seconds"
               for ev in doc["traceEvents"])


# ---------------------------------------------------------------------------
# tools.history: top + show --profile (engine-free CLI)
# ---------------------------------------------------------------------------

def _write_history(tmp_path, entries):
    p = tmp_path / "query_history.jsonl"
    p.write_text("".join(json.dumps(e) + "\n" for e in entries))
    return str(tmp_path)


def _hist_entry(qid, fp, wall, tenant="etl", profile=None,
                metering=None):
    e = {"kind": "history", "version": 1, "query_id": qid,
         "tenant": tenant, "state": "FINISHED",
         "submitted_unix_s": 1_700_000_000.0, "wall_s": wall,
         "registry_delta": {"counters": {}, "histograms": {}},
         "plan_fingerprint": fp}
    if profile is not None:
        e["profile"] = profile
    if metering is not None:
        e["metering"] = metering
    return e


def test_tools_history_top_flags_regressions(tmp_path, capsys):
    from tools.history import main
    entries = (
        [_hist_entry(f"q-s{i}", "fp-steady", 1.0) for i in range(4)] +
        [_hist_entry(f"q-r{i}", "fp-regressed", 0.5) for i in range(2)] +
        [_hist_entry(f"q-r{i+2}", "fp-regressed", 2.0,
                     metering={"device_seconds": 0.25})
         for i in range(2)])
    rc = main(["--dir", _write_history(tmp_path, entries), "top"])
    out = capsys.readouterr().out
    assert rc == 0
    lines = out.splitlines()
    # sorted by median wall desc: the regressed fingerprint leads
    assert lines[1].startswith("fp-regressed"[:16])
    assert "REGRESSED(>2x)" in lines[1]
    assert "fp-steady"[:16] in lines[2] and "REGRESSED" not in lines[2]


def test_tools_history_show_profile_renders_member_rows(tmp_path,
                                                        capsys):
    from tools.history import main
    prof = {"device_seconds": 0.3, "hbm_byte_seconds": 12.5,
            "operators": {
                "FusedStageExec#1": {
                    "op": "FusedStageExec#1", "parent": None,
                    "device_s": 0.3, "wall_s": 0.35, "batches": 4,
                    "rows": 100},
                "FusedStageExec#1/ProjectExec": {
                    "op": "ProjectExec", "parent": "FusedStageExec#1",
                    "device_s": 0.15, "wall_s": 0.175, "batches": 4,
                    "rows": 100}}}
    d = _write_history(tmp_path, [
        _hist_entry("q-prof", "fp-x", 0.4, profile=prof,
                    metering={"device_seconds": 0.3})])
    rc = main(["--dir", d, "show", "q-prof", "--profile"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "FusedStageExec#1" in out
    assert "\n  ProjectExec" in out  # member indented under container
    assert "metered_device_s=0.3" in out
    # an entry without a stored profile explains itself, exit 1
    d = _write_history(tmp_path, [_hist_entry("q-bare", "fp-y", 0.1)])
    rc = main(["--dir", d, "show", "q-bare", "--profile"])
    assert rc == 1
    assert "no stored profile" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# registry: Prometheus label escaping + histogram merge under load
# ---------------------------------------------------------------------------

def test_prometheus_label_escaping_hostile_tenant_names():
    reg = MetricsRegistry()
    labeled = ['web-1', 'a.b.c', 'Ünïcôde™', 'q"uote', 'back\\slash']
    for i, t in enumerate(labeled):
        reg.inc(f"admission.tenant.{t}.admitted", i + 1)
    # a newline never crosses the dotted-name pattern ('.' stops at it)
    # so it degrades to a sanitized plain family, not a torn label
    reg.inc("admission.tenant.new\nline.admitted", 9)
    text = reg.to_prometheus()
    lines = [ln for ln in text.splitlines() if not ln.startswith("#")]
    assert len(lines) == len(labeled) + 1
    for ln in lines:
        # every sample stays one well-formed single-line series
        name, val = ln.rsplit(" ", 1)
        float(val)
        assert "\n" not in name
        if "{" not in name:
            continue
        assert name.startswith('srt_admission_tenant_admitted{tenant="')
        inner = name[name.index('{tenant="') + 9:-2]
        # quotes inside the label value are escaped
        assert not any(c == '"' and (i == 0 or inner[i - 1] != "\\")
                       for i, c in enumerate(inner))
    assert 'srt_admission_tenant_admitted{tenant="a.b.c"}' in text
    assert 'tenant="web-1"' in text
    assert 'tenant="Ünïcôde™"' in text
    assert 'tenant="q\\"uote"' in text
    assert 'tenant="back\\\\slash"' in text
    assert "srt_admission_tenant_new_line_admitted 9" in text


def test_prometheus_empty_label_value_falls_back_to_plain_family():
    reg = MetricsRegistry()
    # "admission.tenant..admitted" has an empty tenant: the labeled
    # pattern requires >=1 char, so it renders as a sanitized plain
    # family instead of an invalid empty-label series
    reg.inc("admission.tenant..admitted", 3)
    text = reg.to_prometheus()
    assert 'tenant=""' not in text
    assert "srt_admission_tenant__admitted 3" in text


def test_histogram_merge_exact_under_concurrent_observe():
    src = Histogram()
    acc = {"snap": None}
    stop = threading.Event()
    N_THREADS, N_OBS = 4, 2000

    def observe(seed):
        for i in range(N_OBS):
            src.observe(0.001 * ((seed * 31 + i) % 500 + 1))

    def scrape():
        while not stop.is_set():
            acc["snap"] = merge_histogram_snapshots(
                acc["snap"], None) if acc["snap"] else None
            snap = src.snapshot()
            # a torn snapshot would break the cumulative invariant
            assert sum(snap["counts"]) == snap["count"]

    workers = [threading.Thread(target=observe, args=(s,))
               for s in range(N_THREADS)]
    scraper = threading.Thread(target=scrape, daemon=True)
    scraper.start()
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    stop.set()
    scraper.join(timeout=5)
    final = src.snapshot()
    assert final["count"] == N_THREADS * N_OBS
    assert sum(final["counts"]) == final["count"]
    # merging two disjoint halves reproduces the whole exactly
    a, b = Histogram(), Histogram()
    for i in range(500):
        (a if i % 2 else b).observe(0.001 * (i % 100 + 1))
    merged = merge_histogram_snapshots(a.snapshot(), b.snapshot())
    whole = Histogram()
    for i in range(500):
        whole.observe(0.001 * (i % 100 + 1))
    assert merged["counts"] == whole.snapshot()["counts"]
    assert merged["count"] == 500
    assert merged["sum"] == pytest.approx(whole.snapshot()["sum"])


def test_histogram_merge_rebuckets_mismatched_bounds():
    a = Histogram(bounds=(0.001, 0.01, 0.1))
    b = Histogram(bounds=(0.005, 0.05))
    for v in (0.0005, 0.02, 5.0):
        a.observe(v)
        b.observe(v)
    m = merge_histogram_snapshots(a.snapshot(), b.snapshot())
    assert m["le"] == [0.001, 0.01, 0.1]  # a's bounds win
    assert m["count"] == 6
    assert sum(m["counts"]) == 6
    assert m["sum"] == pytest.approx(2 * (0.0005 + 0.02 + 5.0))
