#!/bin/bash
# Premerge tier: every change runs this before merging.
#
# Reference model: jenkins/Jenkinsfile-blossom.premerge runs the unit
# suite + a smoke slice of the integration tests per PR, with the full
# sweeps deferred to nightly (jenkins/spark-tests.sh).  Here:
#   * full unit/differential suite on the virtual 8-device CPU mesh
#     (tests/conftest.py forces JAX_PLATFORMS=cpu) — TPC-DS/TPC-H run
#     their smoke query subsets,
#   * API-surface drift gate (tests/test_api_validation.py is part of
#     the suite),
#   * multichip dryrun: the full mesh pipeline compiles + executes on
#     8 virtual devices.
#
# Usage: ci/premerge.sh  (writes artifacts/ci_premerge_<utc-date>.txt)
set -euo pipefail
cd "$(dirname "$0")/.."

STAMP=$(date -u +%Y%m%dT%H%M%SZ)
OUT="artifacts/ci_premerge_${STAMP}.txt"
mkdir -p artifacts

{
  echo "== premerge @ ${STAMP} (commit $(git rev-parse --short HEAD)) =="
  echo "-- static analysis: enginelint --strict --"
  # source-convention gate (docs/developer-guide.md): zero unsuppressed
  # findings, and every suppression carries a written reason
  python -m tools.enginelint spark_rapids_tpu/ --strict
  echo "-- plan verifier smoke: TPC-H ladder, mesh-8, fusion+AQE --"
  # every ladder plan must verify clean through EVERY rewrite pass
  # (everyPass mode), and the default-mode walk (one pass after the
  # final rewrite) must add <2% to the bench's planning step
  # (build_query + prepare) aggregated across the ladder
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python - <<'PY'
import os, tempfile, time

from spark_rapids_tpu.bench.tpch_gen import generate_tpch
from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
from spark_rapids_tpu.plan.verify import verify_plan
from spark_rapids_tpu.session import TpuSession

d = os.path.join(tempfile.mkdtemp(), "tpch")
generate_tpch(d, sf=0.01)
LADDER = ["q1", "q3", "q6", "q12", "q13", "q18"]
BASE = {"spark.rapids.tpu.mesh.deviceCount": 8,
        "spark.sql.adaptive.shuffledHashJoin.enabled": True}

# 1) zero violations with per-pass verification armed on every query
every = TpuSession({**BASE, "spark.rapids.sql.verify.plan.everyPass": True})
for q in LADDER:
    build_tpch_query(q, every, d)._overridden(quiet=True)
print(f"verifier smoke: {len(LADDER)} ladder plans clean through every pass")

# 2) overhead probe: default-mode verify (one final-pass walk) must add
# <2% to plan-time, aggregated across the ladder (median-of-samples)
s = TpuSession({**BASE, "spark.rapids.sql.verify.plan": False})
tot_plan = tot_verify = 0.0
for q in LADDER:
    df = build_tpch_query(q, s, d)
    for _ in range(30):  # warm tag/expr caches before timing
        df._overridden(quiet=True)
    plans, ts_plan = [], []
    for _ in range(60):
        t0 = time.perf_counter()
        df2 = build_tpch_query(q, s, d)
        ov, meta = df2._overridden(quiet=True)
        ts_plan.append(time.perf_counter() - t0)
        plans.append(meta.exec_node)
    ts_verify = []
    for p in plans:
        t0 = time.perf_counter()
        verify_plan(p, s.conf)  # first verify of a fresh plan
        ts_verify.append(time.perf_counter() - t0)
    ts_plan.sort(); ts_verify.sort()
    med_p, med_v = ts_plan[len(ts_plan)//2], ts_verify[len(ts_verify)//2]
    tot_plan += med_p; tot_verify += med_v
    print(f"  {q}: plan={med_p*1e6:.0f}us verify={med_v*1e6:.1f}us "
          f"({med_v/med_p*100:.2f}%)")
frac = tot_verify / tot_plan
print(f"verifier overhead across ladder: {frac*100:.2f}% of plan-time")
assert frac < 0.02, \
    f"plan verifier adds {frac*100:.2f}% to plan-time (budget: 2%)"
PY
  echo "-- unit + differential suite (CPU mesh) --"
  python -m pytest tests/ -q --durations=10
  echo "-- shuffle fault-tolerance chaos suite (seeded, CPU-only) --"
  JAX_PLATFORMS=cpu python -m pytest tests/test_shuffle_fault_tolerance.py -q
  echo "-- OOM chaos suite: TPC-H under memory.oom.until_rows storm --"
  # split-and-retry must return exact-oracle results with nonzero
  # oom_splits, and retry_sync must recover flush-point OOMs with
  # async dispatch (SRT_SYNC_DISPATCH=0 behavior)
  JAX_PLATFORMS=cpu python -m pytest tests/test_oom_chaos.py \
    tests/test_oom_retry.py -q
  echo "-- stage-recovery chaos suite: peer death + spill corruption --"
  # lineage recomputation must return exact-oracle results with nonzero
  # stage_recomputes, and the spill-file leak check must find the spill
  # dir empty after ExecCtx close
  JAX_PLATFORMS=cpu python -m pytest tests/test_recovery_chaos.py \
    tests/test_stage_recovery.py -q
  # the fault registry must be INERT when spark.rapids.test.faults is
  # unset: no registry object, so every injection site is one None check
  JAX_PLATFORMS=cpu python - <<'PY'
from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.faults import FaultRegistry
assert FaultRegistry.from_conf(TpuConf({})) is None, \
    "fault registry must be inert when spark.rapids.test.faults is unset"
assert FaultRegistry.from_conf(None) is None
print("fault registry inert without spark.rapids.test.faults: ok")
PY
  echo "-- observability gate: traced TPC-H run + schema validation --"
  # a TPC-H query with tracing + metrics on must export a trace and a
  # metrics snapshot that validate against the checked-in schema
  # (ci/obs_schema.json), with every event under ONE query/trace id
  JAX_PLATFORMS=cpu python - <<'PY'
import json, os, sys, tempfile
sys.path.insert(0, "scripts")
from validate_obs import validate, load_schema
d = tempfile.mkdtemp()
trace_dir = os.path.join(d, "traces")
from spark_rapids_tpu.bench.runner import run_benchmark
from spark_rapids_tpu.bench.tpch_gen import generate_tpch
data = os.path.join(d, "tpch")
generate_tpch(data, sf=0.01)
r = run_benchmark(data, 0.01, ["q6"], generate=False, suite="tpch",
                  session_conf={
                      "spark.rapids.obs.trace.enabled": "true",
                      "spark.rapids.obs.trace.dir": trace_dir})[0]
assert r.get("ok") and "error" not in r, r
traces = sorted(os.listdir(trace_dir))
assert traces, "no trace exported"
for t in traces:
    doc = json.load(open(os.path.join(trace_dir, t)))
    errs = validate(doc, load_schema("trace"))
    assert not errs, errs[:5]
    ids = {e["args"]["query_id"] for e in doc["traceEvents"]}
    assert len(ids) == 1, ids
obs = r["observability"]
assert obs["query_id"] and obs["trace_id"] and obs["plan_analyzed"]
# the unified metrics snapshot validates too
from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.exec.core import ExecCtx
from spark_rapids_tpu.obs.registry import query_metrics_snapshot
with ExecCtx(backend="host", conf=TpuConf({})) as ctx:
    errs = validate(query_metrics_snapshot(ctx), load_schema("metrics"))
assert not errs, errs[:5]
print(f"observability gate: {len(traces)} trace(s) schema-valid")
PY
  # disabled-path import discipline: with tracing off, the per-batch hot
  # path must never import the tracer or diagnostics modules (their cost
  # is provably zero, not just "small"); obs.registry is stdlib-only and
  # allowed
  JAX_PLATFORMS=cpu python - <<'PY'
import sys
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.aggregates import Sum
from spark_rapids_tpu.expr.core import col
s = TpuSession({})
schema = T.Schema([T.StructField("k", T.IntegerType(), True),
                   T.StructField("v", T.LongType(), True)])
df = s.from_pydict({"k": [i % 5 for i in range(200)],
                    "v": list(range(200))}, schema, partitions=2)
assert len(df.group_by("k").agg(Sum(col("v"))).collect()) == 5
for mod in ("spark_rapids_tpu.obs.trace", "spark_rapids_tpu.obs.diag"):
    assert mod not in sys.modules, \
        f"{mod} imported on the tracing-disabled path"
print("disabled path imports no tracer/diagnostics: ok")
PY
  echo "-- query lifecycle gate: admission + cancel + deadline + shutdown --"
  # four concurrent queries through one session bounded to 2 admitted:
  # one is cancelled mid-flight (QueryCancelled), one carries a tiny
  # deadline (QueryDeadlineExceeded), the other two must return EXACT
  # results; after shutdown the session rejects new work and no
  # tpu-task / tpu-shuffle-srv threads are left alive
  JAX_PLATFORMS=cpu python - <<'PY'
import threading
import time

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exec.lifecycle import (QueryCancelled,
                                             QueryDeadlineExceeded,
                                             QueryRejected)
from spark_rapids_tpu.expr.aggregates import Sum
from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.session import TpuSession

s = TpuSession({"spark.rapids.sql.admission.maxConcurrentQueries": 2,
                "spark.rapids.sql.admission.maxQueuedQueries": 8})
schema = T.Schema([T.StructField("k", T.IntegerType(), True),
                   T.StructField("v", T.LongType(), True)])
small = s.from_pydict({"k": [i % 7 for i in range(4000)],
                       "v": list(range(4000))}, schema, partitions=4) \
    .group_by("k").agg(Sum(col("v")))
big = s.from_pydict({"k": [i % 97 for i in range(400000)],
                     "v": list(range(400000))}, schema, partitions=8) \
    .group_by("k").agg(Sum(col("v")))
expected = sorted(small.collect())

results = {}
def run(name, df, timeout=None):
    try:
        results[name] = ("ok", df.collect(timeout=timeout))
    except BaseException as e:
        results[name] = ("err", e)

before = get_registry().snapshot()
threads = [threading.Thread(target=run, args=("victim", big))]
threads[0].start()
deadline = time.monotonic() + 30.0
while not s.active_queries() and time.monotonic() < deadline:
    time.sleep(0.002)
victim_qid, = s.active_queries()
for name, df, tmo in (("deadline", small, 0.0005),
                      ("exact1", small, None), ("exact2", small, None)):
    t = threading.Thread(target=run, args=(name, df, tmo))
    t.start()
    threads.append(t)
assert s.cancel(victim_qid), "victim finished before the cancel landed"
for t in threads:
    t.join(timeout=120.0)
    assert not t.is_alive(), "query did not unwind in time"

kind, val = results["victim"]
assert kind == "err" and isinstance(val, QueryCancelled), results["victim"]
kind, val = results["deadline"]
assert kind == "err" and isinstance(val, QueryDeadlineExceeded), \
    results["deadline"]
for name in ("exact1", "exact2"):
    kind, val = results[name]
    assert kind == "ok" and sorted(val) == expected, (name, kind)
moved = get_registry().delta(before)["counters"]
assert moved.get("queries_cancelled") == 1, moved
assert moved.get("queries_deadline_exceeded") == 1, moved

s.shutdown(drain=True, timeout=60.0)
try:
    small.collect()
    raise SystemExit("collect after shutdown must raise QueryRejected")
except QueryRejected:
    pass
leaked = [t.name for t in threading.enumerate()
          if t.name.startswith(("tpu-task", "tpu-shuffle-srv"))]
assert not leaked, f"leaked engine threads after shutdown: {leaked}"
print("lifecycle gate: cancel/deadline/exact x2 + clean shutdown: ok")
PY
  echo "-- memory governor gate: pressure shed + exact + zero leaked reservations --"
  # four concurrent queries on one session under a small device budget
  # with the shed watermark forced low: at least one NEW admission must
  # be load-shed with QueryRejected while the four run, the four must
  # return EXACT results, the governor_* counters/gauges must be
  # present, and after shutdown(drain=True) the governor holds zero
  # ledgers, zero reservations, and its daemon thread is gone
  JAX_PLATFORMS=cpu python - <<'PY'
import threading
import time

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exec.lifecycle import QueryRejected
from spark_rapids_tpu.expr.aggregates import Sum
from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.memory.governor import get_governor
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.plan.verify import verify_governor_ledger
from spark_rapids_tpu.session import TpuSession

s = TpuSession({
    "spark.rapids.sql.admission.maxConcurrentQueries": 4,
    "spark.rapids.sql.admission.maxQueuedQueries": 0,
    "spark.rapids.memory.tpu.spillStoreSize": 8 << 20,
    "spark.rapids.memory.governor.shedWatermark": 0.01,
    "spark.rapids.memory.governor.shedHoldSeconds": 0.05,
})
schema = T.Schema([T.StructField("k", T.IntegerType(), True),
                   T.StructField("v", T.LongType(), True)])

def big():
    return s.from_pydict({"k": [i % 97 for i in range(400000)],
                          "v": list(range(400000))}, schema, partitions=8) \
        .group_by("k").agg(Sum(col("v")))

expected = sorted(big().collect())
gov = get_governor()
before = get_registry().snapshot()

results = {}
def run(name, df):
    try:
        results[name] = ("ok", df.collect())
    except BaseException as e:
        results[name] = ("err", e)

threads = [threading.Thread(target=run, args=(f"q{i}", big()))
           for i in range(4)]
for t in threads:
    t.start()

# wait for sustained pressure, then the fifth admission must shed
shed = None
probe = big()
deadline = time.monotonic() + 60.0
while time.monotonic() < deadline and shed is None:
    if gov.admission_pressure() is None:
        time.sleep(0.01)
        continue
    try:
        probe.collect()
    except QueryRejected as e:
        shed = e
assert shed is not None, "no admission was pressure-shed within 60s"
assert "shedWatermark" in str(shed), shed

for t in threads:
    t.join(timeout=180.0)
    assert not t.is_alive(), "query did not finish in time"
for name, (kind, val) in results.items():
    assert kind == "ok" and sorted(val) == expected, (name, kind)

moved = get_registry().delta(before)["counters"]
assert moved.get("governor_pressure_sheds", 0) >= 1, moved
gauges = get_registry().snapshot()["gauges"]
for g in ("governor.device_bytes_total", "governor.reserved_bytes",
          "governor.queries_registered", "governor.budget_bytes"):
    assert g in gauges, (g, sorted(gauges))

s.shutdown(drain=True, timeout=60.0)
assert gov.query_stats() == {}, gov.query_stats()
assert gov.reserved_bytes() == 0, "leaked grant reservation"
verify_governor_ledger(gov)
deadline = time.monotonic() + 5.0
while time.monotonic() < deadline and any(
        t.name == "tpu-mem-governor" for t in threading.enumerate()):
    time.sleep(0.05)
leaked = [t.name for t in threading.enumerate()
          if t.name.startswith(("tpu-task", "tpu-shuffle-srv",
                                "tpu-mem-governor"))]
assert not leaked, f"leaked engine threads after shutdown: {leaked}"
print("governor gate: pressure shed, 4x exact, zero leaked reservations: ok")
PY
  echo "-- fusion + compile-cache gate: warm reruns compile NOTHING --"
  # the same query run twice in one process must be pure cache reuse
  # (compile_count delta 0 on the second run — the whole point of the
  # process-wide compile cache), and fusion.enabled=false must restore
  # the exact unfused plan shape
  JAX_PLATFORMS=cpu python - <<'PY'
import os, tempfile

from spark_rapids_tpu.bench.tpch_gen import generate_tpch
from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.session import TpuSession

d = os.path.join(tempfile.mkdtemp(), "tpch")
generate_tpch(d, sf=0.01)

def classes(query, conf):
    s = TpuSession(dict(conf))
    df = build_tpch_query(query, s, d)
    ov, meta = df._overridden(quiet=True)
    acc = []
    def walk(n):
        acc.append(type(n).__name__)
        for c in n.children:
            walk(c)
    walk(meta.exec_node)
    return acc, sorted(df.collect(), key=str)

# 1) warm rerun: a FRESH session over the same q6 must record ZERO new
# compiles and zero program-cache misses — only hits
classes("q6", {})
before = get_registry().snapshot()
_, rows = classes("q6", {})
moved = get_registry().delta(before)["counters"]
assert rows, "q6 returned no rows"
assert moved.get("compile_count", 0) == 0, f"second run compiled: {moved}"
assert moved.get("fusion_cache_misses", 0) == 0, moved
assert moved.get("fusion_cache_hits", 0) >= 1, moved

# 2) shape reversibility: q3 fuses its filter/project chain; disabling
# fusion restores the per-operator plan with identical results
fused, frows = classes("q3", {})
plain, prows = classes("q3", {"spark.rapids.sql.fusion.enabled": "false"})
assert "FusedStageExec" in fused, fused
assert "FusedStageExec" not in plain, plain
assert all(c in plain for c in fused if c != "FusedStageExec"), (fused, plain)
assert frows == prows, "fused vs unfused rows diverge on q3"
print("fusion gate: warm rerun compiles 0, shape reversible: ok")
PY
  echo "-- adaptive execution gate: broadcast switch, skew split, reversible --"
  # three contracts on the runtime re-optimizer: a forced-small build
  # side is rewritten to broadcast strategy EXACTLY once with rows
  # identical to the static plan; a skewed AQE shuffle records skew
  # splits with rows identical; and adaptive.enabled=false restores the
  # byte-identical static plan shape
  JAX_PLATFORMS=cpu python - <<'PY'
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.session import TpuSession

AQE = {"spark.sql.adaptive.shuffledHashJoin.enabled": True}
SB = T.Schema([T.StructField("k", T.LongType()),
               T.StructField("v", T.DoubleType())])
SS = T.Schema([T.StructField("k", T.LongType()),
               T.StructField("w", T.DoubleType())])

def q(s, n=600, nkeys=10, skew=0.0):
    rng = np.random.default_rng(7)
    keys = rng.integers(0, nkeys, n)
    if skew:
        keys = np.where(rng.random(n) < skew, 7, keys)
    big = s.from_pydict({"k": [int(x) for x in keys],
                         "v": [float(i) for i in range(n)]},
                        SB, partitions=4, rows_per_batch=128)
    small = s.from_pydict({"k": list(range(nkeys)),
                           "w": [float(k) * 10 for k in range(nkeys)]}, SS)
    return big.join(small, on="k", how="inner")

# 1) forced-small build: exactly ONE broadcast switch, rows exact
want = sorted(q(TpuSession({})).collect(), key=str)
before = get_registry().snapshot()
got = sorted(q(TpuSession(AQE)).collect(), key=str)
moved = get_registry().delta(before)["counters"]
assert got == want and got, "broadcast-switch rows diverge from static plan"
assert moved.get("aqe_broadcast_switches", 0) == 1, moved

# 2) skewed shuffle: >=1 skew split, rows exact
skew_conf = dict(AQE)
skew_conf.update({"spark.sql.adaptive.autoBroadcastJoinThreshold": 0,
                  "spark.sql.adaptive.advisoryPartitionSizeInBytes": 4096,
                  "spark.sql.adaptive.skewedPartitionThresholdInBytes": 16384})
kw = dict(n=4000, nkeys=64, skew=0.9)
want = sorted(q(TpuSession({}), **kw).collect(), key=str)
before = get_registry().snapshot()
got = sorted(q(TpuSession(skew_conf), **kw).collect(), key=str)
moved = get_registry().delta(before)["counters"]
assert got == want and got, "skew-split rows diverge from static plan"
assert moved.get("aqe_skew_splits", 0) >= 1, moved

# 3) adaptive.enabled=false restores the byte-identical static shape
off = dict(AQE)
off["spark.sql.adaptive.enabled"] = False
_, m_off = q(TpuSession(off))._overridden(quiet=True)
_, m_static = q(TpuSession({"spark.sql.adaptive.enabled": False})) \
    ._overridden(quiet=True)
assert m_off.exec_node.tree_string() == m_static.exec_node.tree_string()
assert "StageBoundaryExec" not in m_off.exec_node.tree_string()
print("adaptive gate: 1 broadcast switch, skew splits, off-switch reversible: ok")
PY
  echo "-- pod-scale mesh gate: regions exact, warm, and reversible --"
  # q6 + q3 over an 8-device mesh must return EXACTLY the single-chip
  # rows; a warm rerun at the SAME mesh shape must compile nothing (the
  # region/mesh programs are keyed by mesh shape in the process-wide
  # compile cache); and mesh.deviceCount=0 must restore the exact
  # single-chip plan shape
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python - <<'PY'
import os, tempfile

from spark_rapids_tpu.bench.tpch_gen import generate_tpch
from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.session import TpuSession

d = os.path.join(tempfile.mkdtemp(), "tpch")
generate_tpch(d, sf=0.01)
MESH = {"spark.rapids.tpu.mesh.deviceCount": 8}

def classes(query, conf):
    s = TpuSession(dict(conf))
    df = build_tpch_query(query, s, d)
    ov, meta = df._overridden(quiet=True)
    acc = []
    def walk(n):
        acc.append(type(n).__name__)
        for c in n.children:
            walk(c)
    walk(meta.exec_node)
    return acc, sorted(df.collect(), key=str)

# 1) mesh-vs-single exact equality on q6 and q3
for q in ("q6", "q3"):
    mnames, mrows = classes(q, MESH)
    _, prows = classes(q, {})
    assert mrows == prows, f"{q}: mesh-8 rows != single-chip rows"
    assert any(n.startswith("Mesh") for n in mnames), (q, mnames)

# 2) warm rerun at the FIXED mesh shape compiles nothing
before = get_registry().snapshot()
_, rows = classes("q3", MESH)
moved = get_registry().delta(before)["counters"]
assert rows, "q3 returned no rows"
assert moved.get("compile_count", 0) == 0, f"warm mesh rerun compiled: {moved}"

# 3) deviceCount=0 restores the exact single-chip plan shape
zero, zrows = classes("q3", {"spark.rapids.tpu.mesh.deviceCount": 0})
plain, prows = classes("q3", {})
assert zero == plain, (zero, plain)
assert zrows == prows
assert not any(n.startswith("Mesh") for n in zero), zero
print("mesh gate: q6/q3 exact, warm rerun compiles 0, deviceCount=0 reversible: ok")
PY
  echo "-- mesh-join gate: joins absorbed into regions, no gather, exact --"
  # q3's joins must run INSIDE a mesh region (one per-device program,
  # build broadcast / key exchanges as in-program collectives), with
  # zero mesh_gather_fallbacks end to end, rows exactly equal to the
  # single-chip run, and deviceCount=0 must restore the exact
  # single-chip plan shape untouched by region formation
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python - <<'PY'
import os, tempfile

from spark_rapids_tpu.bench.tpch_gen import generate_tpch
from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.session import TpuSession

d = os.path.join(tempfile.mkdtemp(), "tpch")
generate_tpch(d, sf=0.01)
MESH = {"spark.rapids.tpu.mesh.deviceCount": 8}

def plan_and_rows(query, conf):
    s = TpuSession(dict(conf))
    df = build_tpch_query(query, s, d)
    ov, meta = df._overridden(quiet=True)
    nodes = []
    def walk(n):
        nodes.append(n)
        for c in n.children:
            walk(c)
    walk(meta.exec_node)
    return nodes, sorted(df.collect(), key=str)

# 1) q3 at mesh-8: a region whose program contains a join, zero gather
#    fallbacks, rows exactly the single-chip rows
before = get_registry().snapshot()
mnodes, mrows = plan_and_rows("q3", MESH)
moved = get_registry().delta(before)["counters"]
regions = [n for n in mnodes if type(n).__name__ == "MeshRegionExec"]
assert regions, [type(n).__name__ for n in mnodes]
assert any("MeshJoinExec" in r.node_desc() for r in regions), \
    [r.node_desc() for r in regions]
assert moved.get("mesh_gather_fallbacks", 0) == 0, moved
assert moved.get("mesh_regions", 0) >= 1, moved
_, prows = plan_and_rows("q3", {})
assert mrows == prows, "q3: mesh-8 rows != single-chip rows"

# 2) deviceCount=0 restores the exact single-chip plan shape
znodes, zrows = plan_and_rows("q3", {"spark.rapids.tpu.mesh.deviceCount": 0})
pnodes, prows2 = plan_and_rows("q3", {})
assert [type(n).__name__ for n in znodes] == \
    [type(n).__name__ for n in pnodes]
assert zrows == prows2
print("mesh-join gate: q3 join-in-region, 0 gather fallbacks, exact, "
      "deviceCount=0 reversible: ok")
PY
  echo "-- serving tier gate: warm cache hit, weighted order, tenant shed, reversible --"
  # the multi-tenant serving tier's four contracts: (1) 8 queries from
  # 2 tenants at 3:1 weights, then the identical warm set again — the
  # warm round must be pure result-cache hits with compile_count delta
  # 0 AND queries_executed delta 0 (the executor is never dispatched);
  # (2) the observed admission order under a 6:2 backlog respects the
  # 3:1 weights; (3) a pressure event sheds the over-quota tenant and
  # spares the quiet one; (4) resultCache.enabled=false is
  # byte-identical to today — same rows, every query re-executed, and
  # not one result_cache counter moves
  JAX_PLATFORMS=cpu python - <<'PY'
import os, tempfile, threading, time

from spark_rapids_tpu.bench.tpch_gen import generate_tpch
from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
from spark_rapids_tpu.exec.lifecycle import AdmissionController, QueryRejected
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.session import TpuSession

d = os.path.join(tempfile.mkdtemp(), "tpch")
generate_tpch(d, sf=0.01)
WEIGHTS = {"spark.rapids.sql.admission.tenantWeights": "etl:3,bi:1"}
PLAN = [("etl", "q3"), ("etl", "q13"), ("etl", "q18"), ("bi", "q3"),
        ("etl", "q3"), ("bi", "q13"), ("etl", "q13"), ("etl", "q18")]

def run_plan(s):
    out = {}
    for tenant, q in PLAN:
        rows = build_tpch_query(q, s, d).collect(tenant=tenant)
        out[q] = sorted(rows, key=str)
    return out

# 1) 8 queries from 2 tenants cold, then the identical set warm: the
# warm round is served entirely from the result cache — zero compiles,
# zero executor dispatches
s = TpuSession(dict(WEIGHTS))
cold = run_plan(s)
before = get_registry().snapshot()
warm = run_plan(s)
moved = get_registry().delta(before)["counters"]
assert warm == cold, "warm cache-served rows != cold rows"
assert moved.get("compile_count", 0) == 0, f"warm round compiled: {moved}"
assert moved.get("queries_executed", 0) == 0, \
    f"warm round dispatched the executor: {moved}"
assert moved.get("result_cache_hits", 0) >= len(PLAN), moved

# 2) admission order respects the 3:1 weights: saturate the one slot,
# backlog 6 etl + 2 bi with pinned arrival order, drain, and check the
# admission log — 6:2 overall, >=2x share while bi is queued, and bi
# is not starved out of the first 4 slots
ac = AdmissionController(max_concurrent=1, max_queued=16,
                         queue_timeout=30.0,
                         tenant_weights={"etl": 3.0, "bi": 1.0})
ac.admit("holder")
specs = [("etl", f"e{i}") for i in range(6)] + \
        [("bi", f"b{i}") for i in range(2)]
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
ac.release()
for t in threads:
    t.join(timeout=10.0)
    assert not t.is_alive(), "queued admission never drained"
log = [tenant for tenant, _q in ac.admission_log if tenant != "default"]
assert log.count("etl") == 6 and log.count("bi") == 2, log
last_bi = max(i for i, t in enumerate(log) if t == "bi")
window = log[:last_bi + 1]
assert window.count("etl") >= 2 * window.count("bi"), log
assert "bi" in log[:4], log

# 3) pressure sheds the over-quota tenant first: hog holds 3 of 4
# occupied slots at equal weight, so the pressure event rejects hog's
# next admission while the quiet tenant is spared and admitted
before = get_registry().snapshot()
ac2 = AdmissionController(max_concurrent=0)
for i in range(3):
    ac2.admit(f"h{i}", tenant="hog")
ac2.admit("q0", tenant="quiet")
ac2.pressure_hook = lambda tenant: "memory pressure: premerge"
try:
    ac2.admit("h3", tenant="hog")
    raise SystemExit("over-quota tenant was not pressure-shed")
except QueryRejected:
    pass
ac2.admit("q1", tenant="quiet")
dm = get_registry().delta(before)["counters"]
assert dm.get("admission.tenant.hog.rejected") == 1, dm
assert dm.get("admission.tenant.quiet.rejected", 0) == 0, dm
assert dm.get("admission_pressure_spared") == 1, dm

# 4) reversibility: resultCache.enabled=false is byte-identical —
# same rows, both runs dispatch the executor, no cache counter moves
off = TpuSession(dict(WEIGHTS,
                      **{"spark.rapids.sql.resultCache.enabled": "false"}))
before = get_registry().snapshot()
off1 = run_plan(off)
off2 = run_plan(off)
moved = get_registry().delta(before)["counters"]
assert off1 == cold and off2 == cold, "cache-off rows diverge"
assert moved.get("queries_executed", 0) == 2 * len(PLAN), moved
assert not any(k.startswith("result_cache") for k in moved), moved
print("serving gate: warm hit 0-dispatch, 3:1 order, tenant shed, "
      "cache-off identical: ok")
PY
  echo "-- cluster runtime gate: local[2] exact, worker-death recovery, clean drain --"
  # driver/worker pools over the DCN shuffle plane (cluster/): q6+q3 on
  # local[2] must equal the host-oracle rows exactly; SIGKILLing a
  # worker mid-q18 must recompute only the lost map outputs on the
  # survivor (exact rows, nonzero recovery counters); and
  # shutdown(drain=True) must leave zero orphan worker processes and
  # no cluster threads
  JAX_PLATFORMS=cpu python - <<'PY'
import os, tempfile, threading, time

import pyarrow.parquet as pq

from spark_rapids_tpu.bench.runner import run_benchmark
from spark_rapids_tpu.bench.tpch_gen import generate_tpch
from spark_rapids_tpu.session import TpuSession

d = os.path.join(tempfile.mkdtemp(), "tpch")
generate_tpch(d, sf=0.01)
# split tables so scans are multi-partition and the planner inserts
# real shuffle exchanges for the cluster to shard
for table in ("lineitem", "orders", "customer"):
    t = pq.read_table(os.path.join(d, table, "part-0.parquet"))
    step = -(-t.num_rows // 4)
    for i in range(4):
        pq.write_table(t.slice(i * step, step),
                       os.path.join(d, table, f"part-{i}.parquet"))

FAST = {"spark.rapids.cluster.mode": "local[2]",
        "spark.rapids.shuffle.tcp.maxRetries": 1,
        "spark.rapids.shuffle.tcp.retryWaitSeconds": 0.1}

# 1) local[2] q6+q3 exact vs the host oracle, q3's shuffles clustered
reports = run_benchmark(d, 0.01, ["q6", "q3"], verify=True, generate=False,
                        suite="tpch", session_conf=dict(FAST))
for r in reports:
    assert r.get("ok") and "error" not in r, r
reg = (reports[1]["observability"].get("registry") or {}) \
    .get("counters") or {}
assert reg.get("cluster.shuffles_clustered", 0) >= 1, reg

# 2) worker SIGKILLed mid-q18: lineage recovery on the survivor, exact
chaos = dict(FAST)
chaos["spark.rapids.test.faults"] = "cluster.worker.dead:dead,times=1"
r = run_benchmark(d, 0.01, ["q18"], verify=True, generate=False,
                  suite="tpch", session_conf=chaos)[0]
assert r.get("ok") and "error" not in r, r
reg = (r["observability"].get("registry") or {}).get("counters") or {}
assert reg.get("cluster_workers_lost", 0) >= 1, reg
assert reg.get("stage_recomputes", 0) > 0, reg
assert reg.get("map_outputs_recomputed", 0) > 0, reg

# 3) shutdown(drain=True) reaps every worker and every cluster thread
s = TpuSession({"spark.rapids.cluster.mode": "local[2]"})
handles = s._cluster().workers()
assert len(handles) == 2 and all(h.alive for h in handles)
s.shutdown(drain=True)
for h in handles:
    assert h.proc.poll() is not None, \
        f"orphan worker {h.worker_id} after shutdown"
deadline = time.monotonic() + 5.0
while time.monotonic() < deadline and any(
        t.name in ("tpu-cluster-rpc", "tpu-cluster-monitor")
        for t in threading.enumerate()):
    time.sleep(0.05)
leaked = [t.name for t in threading.enumerate()
          if t.name in ("tpu-cluster-rpc", "tpu-cluster-monitor")]
assert not leaked, f"leaked cluster threads after shutdown: {leaked}"
print("cluster gate: local[2] q6/q3 exact, worker-death recovery, "
      "clean drain: ok")
PY
  echo "-- elasticity gate: mid-query drain, straggler speculation, quarantine --"
  # ISSUE 16 elastic membership: retiring a worker mid-q18 must migrate
  # its map outputs to the survivor (exact rows, ZERO recomputes — a
  # planned scale-down costs a copy, not a recompute); a fragment held
  # by the slow fault must be speculatively duplicated and the
  # duplicate's rows committed exactly once; and a flaky worker must be
  # quarantined after maxFailures, re-admitted after probation, with
  # zero orphan processes at the end
  JAX_PLATFORMS=cpu python - <<'PY'
import os, tempfile, time

import numpy as np
import pyarrow.parquet as pq

import spark_rapids_tpu.cluster.exec as cexec
from spark_rapids_tpu import types as T
from spark_rapids_tpu.bench.tpch_gen import generate_tpch
from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
from spark_rapids_tpu.expr.aggregates import Sum
from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.session import TpuSession

d = os.path.join(tempfile.mkdtemp(), "tpch")
generate_tpch(d, sf=0.01)
for table in ("lineitem", "orders", "customer"):
    t = pq.read_table(os.path.join(d, table, "part-0.parquet"))
    step = -(-t.num_rows // 4)
    for i in range(4):
        pq.write_table(t.slice(i * step, step),
                       os.path.join(d, table, f"part-{i}.parquet"))

# 1) graceful drain mid-q18: retire w1 synchronously at the reduce's
# first map-output fetch (all maps registered, nothing consumed yet)
s0 = TpuSession()
want = sorted(build_tpch_query("q18", s0, d).collect())
s0.shutdown()
s = TpuSession({"spark.rapids.cluster.mode": "local[2]"})
drv = s._cluster()
fired = {}
orig = cexec.ClusterMapOutputTracker.fetch_partition
def hooked(self, shuffle_id, pid, lo=0, hi=None):
    if not fired:
        fired["ok"] = True
        fired.update(drv.remove_worker("w1", drain=True))
    return orig(self, shuffle_id, pid, lo, hi)
cexec.ClusterMapOutputTracker.fetch_partition = hooked
before = get_registry().snapshot()
got = sorted(build_tpch_query("q18", s, d).collect())
cexec.ClusterMapOutputTracker.fetch_partition = orig
assert fired.get("ok"), "drain never triggered mid-q18"
assert got == want, "drained q18 rows diverge from the oracle"
reg = get_registry().delta(before)["counters"]
assert reg.get("map_outputs_migrated", 0) > 0, reg
assert reg.get("stage_recomputes", 0) == 0, reg
h = drv.worker_by_id("w1")
assert h.retired and h.proc.poll() is not None
s.shutdown(drain=True)

SCHEMA = T.Schema([T.StructField("k", T.IntegerType(), True),
                   T.StructField("v", T.LongType(), True)])
rng = np.random.default_rng(16)
data = {"k": [int(x) for x in rng.integers(0, 997, 20000)],
        "v": [int(x) for x in rng.integers(-1000, 1000, 20000)]}
s0 = TpuSession()
want = sorted(s0.from_pydict(data, SCHEMA, partitions=6,
                             rows_per_batch=512)
              .group_by("k").agg(Sum(col("v")).alias("sv")).collect())
s0.shutdown()

# 2) straggler storm: a 2s hold on one worker's fragment must be beaten
# by a speculative duplicate, rows committed exactly once
s = TpuSession({
    "spark.rapids.cluster.mode": "local[2]",
    "spark.rapids.cluster.speculation.enabled": "true",
    "spark.rapids.cluster.speculation.multiplier": "2.0",
    "spark.rapids.cluster.speculation.minRuntimeSeconds": "0.2",
    "spark.rapids.test.faults":
        "cluster.worker.slow:slow,seconds=2.0,worker=w1,times=1"})
df = s.from_pydict(data, SCHEMA, partitions=6, rows_per_batch=512)
q = df.group_by("k").agg(Sum(col("v")).alias("sv"))
assert sorted(q.collect()) == want  # warm-up seeds the wall median
before = get_registry().snapshot()
assert sorted(q.collect()) == want, "speculated rows diverge"
reg = get_registry().delta(before)["counters"]
assert reg.get("speculative_launched", 0) >= 1, reg
assert reg.get("speculative_wasted", 0) >= 1, reg
assert reg.get("stage_recomputes", 0) == 0, reg
s.shutdown(drain=True)

# 3) flaky worker: quarantined after 2 consecutive failures, old map
# outputs stay fetchable, probation re-admits, zero orphans
s = TpuSession({
    "spark.rapids.cluster.mode": "local[2]",
    "spark.rapids.cluster.quarantine.maxFailures": "2",
    "spark.rapids.cluster.quarantine.probationSeconds": "4.0",
    "spark.rapids.cluster.heartbeat.intervalSeconds": "0.2",
    "spark.rapids.test.faults":
        "cluster.worker.flaky:flaky,worker=w1,times=2"})
df = s.from_pydict(data, SCHEMA, partitions=6, rows_per_batch=512)
q = df.group_by("k").agg(Sum(col("v")).alias("sv"))
before = get_registry().snapshot()
assert sorted(q.collect()) == want, "flaky-worker rows diverge"
reg = get_registry().delta(before)["counters"]
assert reg.get("cluster_workers_quarantined", 0) == 1, reg
drv = s._cluster()
h = drv.worker_by_id("w1")
assert h.alive and h.state == "quarantined"
deadline = time.monotonic() + 10.0
while time.monotonic() < deadline and \
        drv.worker_by_id("w1").quarantined_until is not None:
    time.sleep(0.1)
assert drv.worker_by_id("w1").quarantined_until is None, \
    "probation never re-admitted the quarantined worker"
reg = get_registry().delta(before)["counters"]
assert reg.get("cluster_workers_readmitted", 0) == 1, reg
handles = drv.workers()
s.shutdown(drain=True)
for h in handles:
    assert h.proc.poll() is not None, \
        f"orphan worker {h.worker_id} after elasticity gate"
print("elasticity gate: mid-q18 drain 0-recompute, speculation "
      "exactly-once, quarantine+re-admission: ok")
PY
  echo "-- telemetry gate: live /metrics mid-query, cluster trace, disabled-path imports --"
  # ISSUE 15 observability plane: the HTTP endpoint must serve
  # well-formed Prometheus (with at least one latency histogram) WHILE
  # queries run; a local[2] q3 must yield ONE Perfetto trace carrying
  # spans from BOTH worker pids on named lanes; and with the confs at
  # their defaults neither obs/http.py nor obs/history.py may be
  # imported and no telemetry socket may exist — the disabled path is
  # zero-overhead by construction
  JAX_PLATFORMS=cpu python - <<'PY'
import glob, json, os, re, socket, sys, tempfile, threading, urllib.request

from spark_rapids_tpu.bench.tpch_gen import generate_tpch
from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
from spark_rapids_tpu.session import TpuSession

d = os.path.join(tempfile.mkdtemp(), "tpch")
generate_tpch(d, sf=0.01)

# 1) live endpoint mid-query: q6 looping in a worker thread, scraped
# concurrently — every sample line must parse, the query-latency
# histogram must be present with cumulative buckets
with socket.socket() as s:
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
sess = TpuSession({"spark.rapids.obs.http.port": str(port)})
assert sess._http is not None and sess._http.port == port
stop = threading.Event()
errs = []

def loop_q6():
    try:
        while not stop.is_set():
            build_tpch_query("q6", sess, d).collect()
    except Exception as e:  # surfaced below; thread must not die silent
        errs.append(repr(e))

t = threading.Thread(target=loop_q6, daemon=True)
t.start()
try:
    build_tpch_query("q6", sess, d).collect()   # ensure >= 1 completion
    scraped = None
    for _ in range(5):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            assert r.status == 200, r.status
            assert r.headers["Content-Type"].startswith("text/plain")
            scraped = r.read().decode()
    sample = re.compile(r'^[a-zA-Z_][a-zA-Z0-9_]*(\{[^}]*\})? '
                        r'[-+0-9.einfa]+$')
    for ln in scraped.splitlines():
        if ln and not ln.startswith("#"):
            assert sample.match(ln), f"malformed sample line: {ln!r}"
    assert "# TYPE srt_query_wall_seconds histogram" in scraped, scraped
    buckets = [float(ln.rsplit(" ", 1)[1]) for ln in scraped.splitlines()
               if ln.startswith("srt_query_wall_seconds_bucket{")]
    assert buckets and buckets == sorted(buckets) and buckets[-1] >= 1
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
        assert json.loads(r.read())["status"] == "ok"
finally:
    stop.set()
    t.join(timeout=60)
    sess.shutdown()
assert not errs, errs
assert sess._http is None, "endpoint must be torn down by shutdown()"
print("telemetry gate 1: mid-query /metrics scrape well-formed, "
      f"{len(buckets)} histogram buckets: ok")

# 2) local[2] q3: ONE merged trace with driver + both worker pids.
# Multi-part tables so the planner inserts real exchanges for the
# cluster to shard — single-part scans would keep q3 driver-local.
import pyarrow.parquet as pq
for table in ("lineitem", "orders", "customer"):
    t = pq.read_table(os.path.join(d, table, "part-0.parquet"))
    step = -(-t.num_rows // 4)
    for i in range(4):
        pq.write_table(t.slice(i * step, step),
                       os.path.join(d, table, f"part-{i}.parquet"))
tdir = tempfile.mkdtemp()
sess = TpuSession({"spark.rapids.cluster.mode": "local[2]",
                   "spark.rapids.obs.trace.enabled": "true",
                   "spark.rapids.obs.trace.dir": tdir})
try:
    worker_pids = {h.pid for h in sess._cluster().workers()}
    build_tpch_query("q3", sess, d).collect()
finally:
    sess.shutdown()
traces = glob.glob(os.path.join(tdir, "trace_*.json"))
assert len(traces) == 1, f"want ONE merged trace, got {traces}"
doc = json.load(open(traces[0]))
lanes = {ev["pid"]: ev["args"]["name"] for ev in doc["traceEvents"]
         if ev.get("ph") == "M" and ev["name"] == "process_name"}
span_pids = {ev["pid"] for ev in doc["traceEvents"]
             if ev.get("ph") == "X"}
assert worker_pids <= span_pids, (worker_pids, span_pids)
assert worker_pids <= set(lanes), (worker_pids, lanes)
assert os.getpid() in span_pids and lanes.get(os.getpid()) == "driver"
print(f"telemetry gate 2: one trace, lanes {sorted(lanes.values())}, "
      f"spans from {len(span_pids)} pids: ok")

# 3) disabled path: defaults leave the telemetry modules unimported
# (checked in a pristine interpreter — this one imported them above)
import subprocess
code = """
import sys
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
sess = TpuSession({})
build_tpch_query("q6", sess, %r).collect()
sess.shutdown()
assert sess._http is None
bad = [m for m in sys.modules
       if m in ("spark_rapids_tpu.obs.http", "spark_rapids_tpu.obs.history")]
assert not bad, f"telemetry modules imported on disabled path: {bad}"
print("disabled path clean")
""" % d
r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                   text=True, timeout=600,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))
assert r.returncode == 0, r.stdout + r.stderr
print("telemetry gate 3: port-off default imports nothing, no socket: ok")
PY
  echo "-- cost-attribution gate: profiled q3@mesh-8, conservation, <3% overhead, disabled-path inert --"
  # ISSUE 19 cost-attribution plane, four contracts: (1) a profiled
  # q3@mesh-8 exports a schema-valid profile artifact whose mesh-region
  # time is attributed to member ops, with flamegraph text and ph="C"
  # counter tracks merged into the Perfetto trace; (2) on a serial
  # profiled session the per-tenant charges conserve against the
  # independently-accumulated process totals (within 5%); (3) warm q6
  # with profiling on stays within 3% of unprofiled wall; (4) with the
  # conf at its default neither obs.profile nor obs.metering is ever
  # imported
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python - <<'PY'
import glob, json, os, sys, tempfile
sys.path.insert(0, "scripts")
from validate_obs import validate, load_schema
from spark_rapids_tpu.bench.runner import run_benchmark
from spark_rapids_tpu.bench.tpch_gen import generate_tpch

d = tempfile.mkdtemp()
data = os.path.join(d, "tpch")
generate_tpch(data, sf=0.01)
pdir, tdir = os.path.join(d, "profiles"), os.path.join(d, "traces")
r = run_benchmark(data, 0.01, ["q3"], generate=False, suite="tpch",
                  session_conf={
                      "spark.rapids.tpu.mesh.deviceCount": "8",
                      "spark.rapids.obs.profile.enabled": "true",
                      "spark.rapids.obs.profile.dir": pdir,
                      "spark.rapids.obs.trace.enabled": "true",
                      "spark.rapids.obs.trace.dir": tdir})[0]
assert r.get("ok") and "error" not in r, r
prof = r["observability"]["profile"]
errs = validate(prof, load_schema("profile"))
assert not errs, errs[:5]
exported = glob.glob(os.path.join(pdir, "profile_*.json"))
assert exported, "no profile artifact exported"
for p in exported:
    errs = validate(json.load(open(p)), load_schema("profile"))
    assert not errs, (p, errs[:5])
ops = prof["operators"]
members = {k: e for k, e in ops.items() if e["parent"]}
assert members, f"no member-attributed rows on mesh-8 q3: {sorted(ops)}"
shares: dict = {}
for e in members.values():
    shares[e["parent"]] = shares.get(e["parent"], 0.0) + e["device_s"]
for par, s in shares.items():
    assert s <= ops[par]["device_s"] + 1e-6, \
        f"members of {par} exceed their container: {s} > {ops[par]}"
assert prof["flamegraph"].strip(), "empty flamegraph"
flame = glob.glob(os.path.join(pdir, "flamegraph_*.txt"))
assert flame and open(flame[0]).read().strip()
traces = glob.glob(os.path.join(tdir, "trace_*.json"))
assert traces, "no trace exported alongside the profile"
doc = json.load(open(traces[0]))
errs = validate(doc, load_schema("trace"))
assert not errs, errs[:5]
counters = [ev for ev in doc["traceEvents"] if ev.get("ph") == "C"]
assert any(ev["name"] == "operator.device_seconds" for ev in counters), \
    f"no operator counter track among {len(counters)} C events"
print(f"cost gate 1: q3@mesh-8 profile schema-valid, "
      f"{len(members)} member rows, {len(counters)} counter samples: ok")
PY
  JAX_PLATFORMS=cpu python - <<'PY'
import os, tempfile, time

from spark_rapids_tpu.bench.tpch_gen import generate_tpch
from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
from spark_rapids_tpu.session import TpuSession

d = os.path.join(tempfile.mkdtemp(), "tpch")
generate_tpch(d, sf=0.01)
NOCACHE = {"spark.rapids.sql.resultCache.enabled": "false"}

# 2) conservation: EVERY profiled query in this process goes through
# the session charge path, so tenant sums must meet the independent
# instrumentation totals within 5%
s_on = TpuSession(dict(NOCACHE,
                       **{"spark.rapids.obs.profile.enabled": "true"}))
for tenant, q in (("etl", "q3"), ("web", "q6"), ("etl", "q6"),
                  ("web", "q3")):
    build_tpch_query(q, s_on, d).collect(tenant=tenant)
from spark_rapids_tpu.obs.metering import get_meter
cons = get_meter().conservation()
assert cons["ok"], f"conservation failed: {cons}"
snap = get_meter().snapshot()
assert set(snap["tenants"]) == {"etl", "web"}, snap["tenants"]
assert snap["tenants"]["etl"]["queries"] == 2, snap["tenants"]["etl"]
print(f"cost gate 2: conservation within 5% "
      f"(device_s tenants={cons['device_seconds']['tenants_sum']:.4f} "
      f"total={cons['device_seconds']['total']:.4f}): ok")

# 3) warm q6 overhead < 3%: medians over interleaved samples so host
# drift cancels; a noisy CI host gets bounded retries — a real hot-path
# regression fails every attempt
s_off = TpuSession(dict(NOCACHE))
df_on = build_tpch_query("q6", s_on, d)
df_off = build_tpch_query("q6", s_off, d)
for _ in range(5):  # warm compile/fusion caches on both paths
    df_on.collect(tenant="warm")
    df_off.collect()
ratio = None
for attempt in (1, 2, 3):
    ts_on, ts_off = [], []
    for _ in range(40):
        t0 = time.perf_counter()
        df_off.collect()
        ts_off.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        df_on.collect(tenant="warm")
        ts_on.append(time.perf_counter() - t0)
    ts_on.sort(); ts_off.sort()
    med_on, med_off = ts_on[len(ts_on) // 2], ts_off[len(ts_off) // 2]
    ratio = med_on / med_off
    print(f"  attempt {attempt}: profiled={med_on * 1e3:.2f}ms "
          f"unprofiled={med_off * 1e3:.2f}ms ({(ratio - 1) * 100:+.2f}%)")
    if ratio < 1.03:
        break
assert ratio < 1.03, \
    f"profiling adds {(ratio - 1) * 100:.2f}% to warm q6 (budget: 3%)"
s_on.shutdown(); s_off.shutdown()
print(f"cost gate 3: warm q6 overhead {(ratio - 1) * 100:+.2f}% (< 3%): ok")
PY
  # 4) disabled path: the default leaves the profiler modules unimported
  # (pristine interpreter — this shell already imported them above)
  JAX_PLATFORMS=cpu python - <<'PY'
import os, subprocess, sys, tempfile
from spark_rapids_tpu.bench.tpch_gen import generate_tpch
d = os.path.join(tempfile.mkdtemp(), "tpch")
generate_tpch(d, sf=0.01)
code = """
import sys
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
sess = TpuSession({})
build_tpch_query("q6", sess, %r).collect()
sess.shutdown()
bad = [m for m in sys.modules
       if m in ("spark_rapids_tpu.obs.profile",
                "spark_rapids_tpu.obs.metering")]
assert bad == [], f"profiler modules imported on disabled path: {bad}"
import threading
assert not [t.name for t in threading.enumerate()
            if t.name == "obs-hbm-sampler"], "sampler thread while disabled"
print("disabled path clean")
"""
r = subprocess.run([sys.executable, "-c", code % d], capture_output=True,
                   text=True, timeout=600,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))
assert r.returncode == 0, r.stdout + r.stderr
print("cost gate 4: profile-off default imports nothing: ok")
PY
  echo "-- transactional write gate: CTAS exact under fault storm, no stray staging --"
  # q6-shaped CTAS (lineitem under q6's filter, hive-partitioned) must
  # produce the SAME read-back row hash across a clean run, an
  # io.write.* fault storm, a cluster worker-death run, and a
  # speculation-duplicate run — with every visible file listed in
  # _MANIFEST.json and zero staging leftovers.  (The mid-write drain
  # variant needs a monkeypatch hook and rides the unit suite:
  # tests/test_write_chaos.py::test_drain_during_write_fences_and_completes.)
  JAX_PLATFORMS=cpu python - <<'PY'
import datetime, glob, hashlib, json, os, tempfile

from spark_rapids_tpu.bench.tpch_gen import generate_tpch
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.session import TpuSession

d = os.path.join(tempfile.mkdtemp(), "tpch")
generate_tpch(d, sf=0.01)

# split lineitem into 4 part files so the write job has multiple tasks
# and the cluster runs actually spread fragments over both workers
import pyarrow.parquet as pq
_t = pq.read_table(os.path.join(d, "lineitem", "part-0.parquet"))
_step = -(-_t.num_rows // 4)
for _i in range(4):
    pq.write_table(_t.slice(_i * _step, _step),
                   os.path.join(d, "lineitem", f"part-{_i}.parquet"))


def ctas(conf, out):
    sess = TpuSession(conf)
    try:
        li = sess.read_parquet(
            os.path.join(d, "lineitem"),
            columns=["l_returnflag", "l_extendedprice", "l_discount",
                     "l_shipdate", "l_quantity"])
        q6ish = li.where(
            (col("l_shipdate") >= lit(datetime.date(1994, 1, 1)))
            & (col("l_shipdate") < lit(datetime.date(1995, 1, 1)))
            & (col("l_discount") >= lit(0.05))
            & (col("l_discount") <= lit(0.07))
            & (col("l_quantity") < lit(24.0)))
        stats = q6ish.write_parquet(out, partition_by=["l_returnflag"])
        return stats
    finally:
        if hasattr(sess, "shutdown"):
            sess.shutdown()


def row_hash(out):
    import pyarrow.dataset as ds
    t = ds.dataset(out, format="parquet", partitioning="hive").to_table()
    t = t.select(sorted(t.column_names))
    rows = sorted(zip(*(t.column(n).to_pylist()
                        for n in t.column_names)), key=str)
    h = hashlib.sha256()
    for r in rows:
        h.update(repr(r).encode())
    return h.hexdigest()


def check_committed(out):
    man = json.load(open(os.path.join(out, "_MANIFEST.json")))
    committed = {os.path.normpath(e["rel"]) for e in man["files"]}
    visible = set()
    for root, dirs, files in os.walk(out):
        dirs[:] = [x for x in dirs if not x.startswith(("_", "."))]
        for fn in files:
            if not fn.startswith(("_", ".")):
                visible.add(os.path.normpath(os.path.relpath(
                    os.path.join(root, fn), out)))
    assert visible == committed, (visible ^ committed)
    assert not os.path.exists(os.path.join(out, "_staging"))


base = tempfile.mkdtemp()
clean = os.path.join(base, "clean")
ctas({}, clean)
want = row_hash(clean)
check_committed(clean)

STORMS = {
    "faultstorm": {"spark.rapids.test.faults":
                   "io.write.partial:crash,times=2;"
                   "io.write.commit.drop:drop,times=1;"
                   "io.write.rename.fail:fail,times=1"},
    "workerdeath": {"spark.rapids.cluster.mode": "local[2]",
                    "spark.rapids.test.faults":
                    "cluster.worker.dead:dead,worker=w1,"
                    "seconds=0.02,times=1"},
    "speculation": {"spark.rapids.cluster.mode": "local[2]",
                    "spark.rapids.cluster.speculation.enabled": "true",
                    "spark.rapids.cluster.speculation.multiplier": "2.0",
                    "spark.rapids.cluster.speculation."
                    "minRuntimeSeconds": "0.2",
                    "spark.rapids.test.faults":
                    "cluster.worker.slow:slow,seconds=2.0,"
                    "worker=w1,times=1"},
}
for name, conf in STORMS.items():
    out = os.path.join(base, name)
    before = get_registry().snapshot()
    ctas(conf, out)
    delta = get_registry().delta(before)["counters"]
    injected = sum(v for k, v in delta.items()
                   if k.startswith("faults.injected."))
    assert injected > 0, f"{name}: storm never fired: {delta}"
    assert row_hash(out) == want, f"{name}: read-back hash diverged"
    check_committed(out)
    print(f"write gate [{name}]: exact hash, {injected} faults injected, "
          f"no orphans: ok")
print("transactional write gate: ok")
PY
  echo "-- self-driving control gate: off-path inert, storm shed targeted --"
  # two halves.  OFF: spark.rapids.control.enabled=false must be
  # byte-identical to the static engine — same plans, same confs after
  # a run, and the control package never even imports.  ON: a reduced
  # mixed-tenant storm (single-worker grid) where every fixed config
  # misses a served tenant's SLO that the closed loop meets, shedding
  # ONLY the storm tenant.
  JAX_PLATFORMS=cpu python - <<'PY'
import sys

from spark_rapids_tpu.bench.tpch_gen import generate_tpch
from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
from spark_rapids_tpu.session import TpuSession

import os, tempfile, threading
d = os.path.join(tempfile.mkdtemp(), "tpch")
generate_tpch(d, sf=0.01)

# -- OFF: the disabled path is the static engine, byte for byte ------
assert "spark_rapids_tpu.control" not in sys.modules, \
    "control package imported before any session asked for it"
def run_off(conf):
    s = TpuSession(conf)
    try:
        df = build_tpch_query("q3", s, d)
        plan = df.explain()
        rows = df.collect(tenant="gate")
        return plan, rows, dict(s.conf.settings)
    finally:
        s.shutdown()
static = run_off({})
disabled = run_off({"spark.rapids.control.enabled": "false"})
assert static[0] == disabled[0], "explain drifted with control disabled"
assert static[1] == disabled[1], "rows drifted with control disabled"
assert disabled[2] == {"spark.rapids.control.enabled": "false"}, \
    f"disabled control mutated session confs: {disabled[2]}"
assert "spark_rapids_tpu.control" not in sys.modules, \
    "control package imported on the DISABLED path"
assert not [t.name for t in threading.enumerate()
            if t.name == "control-loop"], "control thread on disabled path"
print("control gate [off]: plans, rows, imports identical: ok")

# -- ON: reduced storm; the loop must beat every fixed rung ----------
# one retry: the storm scores wall-clock p99s, and a noisy CI host
# can push a served tenant a few percent over its margin — a real
# control-plane regression fails BOTH attempts
from spark_rapids_tpu.bench.storm import run_storm
for attempt in (1, 2):
    rep = run_storm(d, 0.01, grid=((2, 1), (8, 1)), duration_s=4.0,
                    generate=False)
    if rep["ok"]:
        break
    print(f"control gate [storm]: attempt {attempt} failed: "
          f"{rep.get('error')}")
assert rep["ok"], f"storm gate failed: {rep.get('error')}"
assert rep["all_fixed_missed"] and rep["storm_tenant_shed"] \
    and rep["served_tenants_clean"]
cl = rep["closed"]
assert not cl["missed"], f"closed loop missed {cl['missed']}"
shed = [t for t, i in cl["tenants"].items() if i["shed"]]
assert shed == ["batch"], f"shed set {shed} != ['batch']"
# the controller's thread dies with its session
assert not [t.name for t in threading.enumerate()
            if t.name == "control-loop" and t.is_alive()], \
    "control-loop thread leaked past shutdown"
print(f"control gate [storm]: fixed grid missed everywhere, closed "
      f"loop margin {rep['closed_slo_margin']}x, only batch shed: ok")
PY
  echo "-- driver failover gate: mid-q18 SIGKILL -> journal recovery, write roll-forward, off-path inert --"
  # three halves.  CRASH: a real driver process is SIGKILLed on its
  # first reduce-side fetch of q18; recovery from the write-ahead
  # journal must re-attach BOTH lingering workers and re-serve the
  # exact rows with zero recompute of journaled map outputs.  WRITE:
  # a SIGKILL mid-commit rolls FORWARD to exactly one _SUCCESS and no
  # _staging residue.  OFF: journal disabled is byte-identical plans,
  # zero journal I/O, and cluster/journal.py never imports.
  JAX_PLATFORMS=cpu python - <<'PY'
import json, os, signal, subprocess, sys, tempfile

import pyarrow.parquet as pq

from spark_rapids_tpu.bench.tpch_gen import generate_tpch
from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
from spark_rapids_tpu.session import TpuSession

base = tempfile.mkdtemp(prefix="tpu-failover-gate-")
d = os.path.join(base, "tpch")
generate_tpch(d, sf=0.01)
# multi-partition scans so the planner inserts REAL shuffle exchanges
# (single-partition q18 never touches the cluster shuffle plane)
for table in ("lineitem", "orders", "customer"):
    t = pq.read_table(os.path.join(d, table, "part-0.parquet"))
    step = -(-t.num_rows // 4)
    for i in range(4):
        pq.write_table(t.slice(i * step, step),
                       os.path.join(d, table, f"part-{i}.parquet"))

s = TpuSession()
want = sorted(map(tuple, build_tpch_query("q18", s, d).collect()))
s.shutdown()
assert "spark_rapids_tpu.cluster.journal" not in sys.modules, \
    "cluster/journal.py imported in single-process mode"

DRIVER = r'''
import json, sys
from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
from spark_rapids_tpu.session import TpuSession
conf = json.loads(sys.argv[1]); d = sys.argv[2]; mode = sys.argv[3]
s = TpuSession(conf)
df = build_tpch_query("q18", s, d)
if mode == "write":
    df.write_parquet(sys.argv[4])
else:
    df.collect()
s.shutdown()
print("CLEAN_EXIT", flush=True)
'''

def run_driver(conf, *extra):
    # stderr to a FILE: the workers inherit the driver's stderr, and a
    # captured pipe would block this gate for the whole linger window
    with tempfile.TemporaryFile(mode="w+") as ef:
        p = subprocess.run([sys.executable, "-c", DRIVER,
                            json.dumps(conf), d, *extra],
                           stdout=subprocess.PIPE, stderr=ef,
                           text=True, timeout=240)
        ef.seek(0)
        p.stderr = ef.read()
    return p

def worker_pids(jdir):
    from spark_rapids_tpu.cluster.journal import ClusterJournal
    st = ClusterJournal.replay(jdir)
    return [w["pid"] for w in st.workers.values() if w.get("pid")]

def kill_stragglers(pids):
    for pid in pids:
        try:
            os.kill(pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass

jdir = os.path.join(base, "journal")
conf = {"spark.rapids.cluster.mode": "local[2]",
        "spark.rapids.cluster.journal.dir": jdir,
        "spark.rapids.cluster.driver.reattachGraceSeconds": "90"}

# -- 1) SIGKILL mid-q18, recover, exact rows, zero recompute ---------
crashed = run_driver({**conf, "spark.rapids.test.faults":
                      "cluster.driver.crash:kill,point=shuffle_read"},
                     "collect")
assert crashed.returncode == -signal.SIGKILL, \
    f"driver survived: rc={crashed.returncode} {crashed.stderr[-2000:]}"
assert "CLEAN_EXIT" not in crashed.stdout
from spark_rapids_tpu.cluster.driver import ClusterDriver
from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.obs.registry import get_registry
pids = worker_pids(jdir)
try:
    driver = ClusterDriver.recover(TpuConf(conf), jdir)
    info = dict(driver.recovery_info)
    assert info["workers_reattached"] == 2, info
    s = TpuSession(conf).attach_cluster(driver)
    before = get_registry().snapshot()
    got = sorted(map(tuple, build_tpch_query("q18", s, d).collect()))
    delta = get_registry().delta(before)["counters"]
    s.shutdown()
    assert got == want, "recovered q18 rows diverged from oracle"
    assert delta.get("map_outputs_recomputed", 0) == 0, delta
finally:
    kill_stragglers(pids)
print("failover gate 1: mid-q18 SIGKILL -> 2 reattached, exact rows, "
      "0 journaled outputs recomputed: ok")

# -- 2) SIGKILL mid-write-commit rolls FORWARD -----------------------
jdir2 = os.path.join(base, "journal2")
out = os.path.join(base, "out")
conf2 = {**conf, "spark.rapids.cluster.journal.dir": jdir2}
crashed = run_driver({**conf2, "spark.rapids.test.faults":
                      "cluster.driver.crash:kill,point=write.commit"},
                     "write", out)
assert crashed.returncode == -signal.SIGKILL, crashed.stderr[-2000:]
assert not os.path.exists(os.path.join(out, "_SUCCESS"))
pids = worker_pids(jdir2)
try:
    drv = ClusterDriver.recover(TpuConf(conf2), jdir2)
    info2 = dict(drv.recovery_info)
    drv.shutdown()
    assert info2["write_rollforward"] == 1, info2
    assert info2["write_rollback"] == 0, info2
    names = os.listdir(out)
    assert names.count("_SUCCESS") == 1, names
    assert "_staging" not in names, names
finally:
    kill_stragglers(pids)
print("failover gate 2: mid-commit SIGKILL -> rolled forward, one "
      "_SUCCESS, no _staging residue: ok")
PY
  # -- 3) journal disabled: identical plans, zero journal I/O --------
  # fresh interpreter so sys.modules proves the DISABLED path never
  # imports cluster/journal.py even in cluster mode
  JAX_PLATFORMS=cpu python - <<'PY'
import os, sys, tempfile

import pyarrow.parquet as pq

from spark_rapids_tpu.bench.tpch_gen import generate_tpch
from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
from spark_rapids_tpu.session import TpuSession

base = tempfile.mkdtemp(prefix="tpu-failover-off-")
d = os.path.join(base, "tpch")
generate_tpch(d, sf=0.01)
for table in ("lineitem", "orders", "customer"):
    t = pq.read_table(os.path.join(d, table, "part-0.parquet"))
    step = -(-t.num_rows // 4)
    for i in range(4):
        pq.write_table(t.slice(i * step, step),
                       os.path.join(d, table, f"part-{i}.parquet"))
jdir = os.path.join(base, "never-touched")

off = {"spark.rapids.cluster.mode": "local[2]",
       "spark.rapids.cluster.journal.enabled": "false",
       "spark.rapids.cluster.journal.dir": jdir}
s = TpuSession(off)
plan_off = build_tpch_query("q18", s, d).explain()
s.shutdown()
assert "spark_rapids_tpu.cluster.journal" not in sys.modules, \
    "journal module imported with journaling DISABLED"
assert not os.path.exists(jdir), "disabled journal still did I/O"

on = {"spark.rapids.cluster.mode": "local[2]",
      "spark.rapids.cluster.journal.dir": os.path.join(base, "j")}
s = TpuSession(on)
plan_on = build_tpch_query("q18", s, d).explain()
s.shutdown()
assert plan_off == plan_on, "journal changed the plan"
print("failover gate 3: journal-off plans byte-identical, zero "
      "journal I/O, module never imported: ok")
PY
  echo "-- multichip dryrun (8 virtual devices) --"
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun ok')"
  echo "== premerge PASS =="
} 2>&1 | tee "$OUT"

# Machine-feature-mismatch gate: a cpu_aot_loader complaint means a
# stale/foreign AOT executable was loaded — a SIGILL from one would be
# indistinguishable from any other crash in CI.
if grep -q "cpu_aot_loader" "$OUT"; then
  echo "== premerge FAIL: cpu_aot_loader machine-feature warnings in log =="
  exit 1
fi
