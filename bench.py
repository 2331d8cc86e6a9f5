"""Benchmark: TPC-DS q6 (BASELINE configs[0]) device vs CPU oracle.

Prints one JSON line per metric:
  {"metric": "tpcds_q6_sf..._speedup_vs_cpu_oracle", "value": N, ...}
  {"metric": "tpch_multichip_scaling_sf...", "value": N, "ladder": [...]}
  {"metric": "tpch_cluster_scaling_sf...", "value": N, "ladder": [...]}
  {"metric": "tpch_multistream_qph_sf...", "value": N, "ladder": [...]}
  {"metric": "tpch_storm_p99_slo_sf...", "value": N, "report": {...}}

The cluster line is the driver/worker runtime ladder
(spark_rapids_tpu/cluster): q6 + q3 at 1/2/4 local worker processes
(spark.rapids.cluster.mode=local[N]) with map-side shuffle work
sharded over the pool and per-worker registry deltas in each rung's
observability block.

The third line is the serving-tier THROUGHPUT ladder
(spark_rapids_tpu/bench/throughput.py): N ∈ {1,2,4,8} concurrent
tenant streams through ONE session, distinct query permutations per
stream, warm queries-per-hour per rung with cache-hit and fairness
counters, every stream's rows verified against the host oracle.

The storm line is the CONTROL-PLANE rung
(spark_rapids_tpu/bench/storm.py): web/etl/batch tenants share one
bottlenecked session; every fixed admission configuration in a
maxConcurrent x workers grid misses at least one self-calibrated p99
SLO, while the closed loop (spark.rapids.control.enabled=true) meets
the served tenants' SLOs by shedding exactly the storm tenant.  value
= min(slo/p99) over served tenants in the closed-loop run.

The second line is the pod-scale device-count ladder: TPC-H q6, q3,
q13 and q18 at 1/2/4/8 mesh devices
(spark.rapids.tpu.mesh.deviceCount), wall time and scaling efficiency
t1/(n*tn) per rung — q13/q18 exercise shard-resident multi-join
regions, not just scan->filter->agg.  Setting
SPARK_RAPIDS_BENCH_MESH_DEVICES=N additionally runs the PRIMARY q6
ladder itself over an N-device mesh, so a multichip harness run stops
reporting healthy-but-idle devices.

Runs a scale-factor ladder (SF0.1 smoke -> SF1 -> SF10) of TPC-DS q6
through the real engine (parquet scan -> joins -> filter -> group-by ->
having -> sort -> limit, spark_rapids_tpu.bench.runner), verifying each
rung against the host oracle.  The emitted line is the LARGEST rung that
completed, labeled with its scale factor — a smoke number is never
reported under a bigger-SF metric name.

Robustness: a hung thread cannot be killed, so every rung runs in its
OWN subprocess under a deadline — a backend that hangs is killed, not
waited on.  The parent process never imports jax: a chip belongs to one
process at a time, and the rungs run one after another.  There is no
CPU fallback: if no rung completes on the TPU, no metric is emitted and
the exit code is non-zero (a number from a CPU run is not a device
number under any label).

vs_baseline = speedup / 4.0 against BASELINE.json's >=4x-vs-CPU-Spark
target.  The oracle is this repo's single-threaded numpy engine, NOT
CPU Spark — an interim proxy, stated in the metric name.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

TOTAL_TIMEOUT_S = float(os.environ.get("BENCH_TOTAL_TIMEOUT_S", "540"))
# quick backend-liveness probe budget: device acquisition that hangs
# would otherwise burn a whole rung timeout before anyone learns of it
PROBE_TIMEOUT_S = float(os.environ.get("BENCH_PROBE_TIMEOUT_S", "75"))
MAX_SF = float(os.environ.get("BENCH_SF", "10"))
DATA_DIR = os.environ.get("BENCH_DATA_DIR",
                          os.path.join(os.path.dirname(
                              os.path.abspath(__file__)), ".bench_data"))
# smoke rung is SF0.1, the smallest scale where q6 produces result rows —
# a 0-row "device == oracle" comparison verifies nothing (round-2 verdict)
LADDER = [sf for sf in (0.1, 1.0, 10.0) if sf <= MAX_SF] or [0.1]

# pod-scale knob: when set (>1) every bench rung runs the engine over an
# n-device mesh (spark.rapids.tpu.mesh.deviceCount=n), so a multichip
# harness run stops reporting healthy-but-IDLE devices — the devices it
# probes are the devices the measured plan executes on
MESH_DEVICES = int(os.environ.get("SPARK_RAPIDS_BENCH_MESH_DEVICES", "0")
                   or "0")
# device-count scaling ladder (MULTICHIP metric): q6 + q3 + q13 + q18 at
# 1/2/4/8 devices, wall time and scaling efficiency per rung — q13/q18
# keep multi-join pipelines (joins absorbed into mesh regions) honest
MULTICHIP_QUERIES = ("q6", "q3", "q13", "q18")
MULTICHIP_LADDER = tuple(
    int(x) for x in os.environ.get("BENCH_MULTICHIP_LADDER",
                                   "1,2,4,8").split(",") if x.strip())
MULTICHIP_SF = float(os.environ.get("BENCH_MULTICHIP_SF", "0.1"))
MULTICHIP_TIMEOUT_S = float(os.environ.get("BENCH_MULTICHIP_TIMEOUT_S",
                                           "420"))
# multi-stream THROUGHPUT ladder (serving-tier metric): N concurrent
# tenant streams through one session, queries-per-hour per rung, warm
# (result cache + compile cache primed), per-stream oracle-verified
THROUGHPUT_SF = float(os.environ.get("BENCH_THROUGHPUT_SF", "0.1"))
THROUGHPUT_STREAMS = tuple(
    int(x) for x in os.environ.get("BENCH_THROUGHPUT_STREAMS",
                                   "1,2,4,8").split(",") if x.strip())
THROUGHPUT_QUERIES = ("q3", "q13", "q18")
THROUGHPUT_TIMEOUT_S = float(os.environ.get("BENCH_THROUGHPUT_TIMEOUT_S",
                                            "420"))
# cluster-runtime worker ladder (CLUSTER metric): q6 + q3 at 1/2/4
# local worker subprocesses over the DCN shuffle plane
# (spark.rapids.cluster.mode=local[N]).  Always measured on the CPU
# backend: co-tenant worker processes cannot share one exclusively-held
# TPU, so a CPU ladder is the honest shape measurement.
CLUSTER_QUERIES = ("q6", "q3")
CLUSTER_LADDER = tuple(
    int(x) for x in os.environ.get("BENCH_CLUSTER_LADDER",
                                   "1,2,4").split(",") if x.strip())
CLUSTER_SF = float(os.environ.get("BENCH_CLUSTER_SF", "0.05"))
CLUSTER_TIMEOUT_S = float(os.environ.get("BENCH_CLUSTER_TIMEOUT_S", "420"))
# transactional CTAS write rung (WRITE metric): a q6-shaped CTAS
# (lineitem under q6's filter, hive-partitioned by l_returnflag)
# through the two-phase commit protocol (io/writer.py) — clean run for
# the throughput number, then an io.write.* fault storm and a cluster
# worker-death run, each of which must reproduce the clean run's
# read-back row hash exactly.  CPU backend, like the cluster ladder.
WRITE_SF = float(os.environ.get("BENCH_WRITE_SF", "0.1"))
WRITE_TIMEOUT_S = float(os.environ.get("BENCH_WRITE_TIMEOUT_S", "300"))
# mixed-tenant STORM rung (control-plane metric): web/etl/batch tenants
# share one bottlenecked session; a fixed admission grid is swept with
# the control plane OFF, then the closed loop runs with it ON.  value =
# min(slo/p99) over the served tenants in the closed-loop run (>1 means
# every served SLO met, with margin) — and the report carries the whole
# grid, so the claim "no fixed config serves what the closed loop
# serves" is inspectable.  CPU backend: admission/SLO dynamics are
# host-side, like the cluster ladder.
STORM_SF = float(os.environ.get("BENCH_STORM_SF", "0.01"))
STORM_DURATION_S = float(os.environ.get("BENCH_STORM_DURATION_S", "5"))
STORM_TIMEOUT_S = float(os.environ.get("BENCH_STORM_TIMEOUT_S", "420"))


def _mesh_env(n_devices: int) -> dict:
    """Child env forcing n virtual host devices (idempotent append)."""
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count=" not in flags:
        env["XLA_FLAGS"] = (
            flags +
            f" --xla_force_host_platform_device_count={n_devices}").strip()
    return env


def _emit(value: float, sf: float, error: str | None = None,
          extra: dict | None = None):
    """The q6 ladder metric — only ever emitted for a TPU rung."""
    name = f"tpcds_q6_sf{sf:g}_speedup_vs_cpu_oracle"
    rec = {
        "metric": name,
        "value": round(float(value), 3),
        "unit": "x",
        "vs_baseline": round(float(value) / 4.0, 3),
    }
    if extra:
        rec.update(extra)
    if error:
        rec["error"] = str(error)[:500]
    print(json.dumps(rec))
    sys.stdout.flush()


_REPORT_PREFIX = "BENCH_REPORT:"


def _probe_tpu(timeout_s: float) -> tuple[bool, str]:
    """Cheaply check the TPU backend can initialize at all.

    Runs ``jax.devices()`` plus one tiny device computation in a killable
    subprocess with a faulthandler watchdog, so a hang in device
    acquisition is killed and reported instead of waited on.
    """
    watchdog = max(5.0, timeout_s - 10.0)
    code = (
        "import faulthandler, os, sys\n"
        f"faulthandler.dump_traceback_later({watchdog:.0f}, exit=True)\n"
        "import jax\n"
        "ds = jax.devices()\n"
        "import jax.numpy as jnp\n"
        "x = jnp.arange(8); x.block_until_ready()\n"
        "print('PROBE_OK', ds[0].platform, len(ds), flush=True)\n"
        "os._exit(0)\n"
    )
    rc, out, errout = _run_killable([sys.executable, "-c", code], timeout_s)
    out = (out or "") + (errout or "")
    if rc is None:
        return False, f"probe killed after {timeout_s:.0f}s (hung)"
    for line in out.splitlines():
        if line.startswith("PROBE_OK"):
            parts = line.split()
            got = parts[1] if len(parts) > 1 else "?"
            if got != "tpu":
                return False, f"probe initialized '{got}' not 'tpu'"
            return True, f"backend '{got}' x{parts[2] if len(parts) > 2 else '?'}"
    tail = out.strip().splitlines()[-1][:200] if out.strip() else "no output"
    return False, f"probe rc={rc}: {tail}"


def _run_killable(cmd: list[str], timeout_s: float,
                  **popen_kw) -> tuple[int | None, str, str]:
    """Spawn ``cmd`` in its own session and wait up to ``timeout_s``.

    On timeout the whole process GROUP is killed (helper children die
    with it instead of holding the chip and the stdout pipe forever)
    and whatever output was produced is
    still drained and returned.  Returns (returncode|None-if-killed,
    stdout, stderr)."""
    p = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True,
                         start_new_session=True, **popen_kw)
    try:
        out, errout = p.communicate(timeout=timeout_s)
        return p.returncode, out or "", errout or ""
    except subprocess.TimeoutExpired:
        try:
            os.killpg(os.getpgid(p.pid), 9)
        except (ProcessLookupError, PermissionError):
            p.kill()
        try:
            out, errout = p.communicate(timeout=10)
        except subprocess.TimeoutExpired:
            out, errout = "", ""
        return None, out or "", errout or ""


def _run_rung(sf: float, platform: str, timeout_s: float) -> dict:
    """One ladder rung in a killable subprocess; returns its JSON report
    or {"error": ...}."""
    cmd = [sys.executable, os.path.abspath(__file__),
           "--child", str(sf), platform]
    kw = {}
    if MESH_DEVICES > 1 and platform == "cpu":
        # the mesh needs the virtual devices to exist before jax inits
        kw["env"] = _mesh_env(MESH_DEVICES)
    rc, out, errout = _run_killable(
        cmd, timeout_s,
        cwd=os.path.dirname(os.path.abspath(__file__)) or None, **kw)
    if rc is None:
        return {"error": f"rung sf{sf:g}/{platform} killed after "
                         f"{timeout_s:.0f}s (backend hang)"}
    for line in reversed(out.splitlines()):
        line = line.strip()
        if line.startswith(_REPORT_PREFIX):
            try:
                return json.loads(line[len(_REPORT_PREFIX):])
            except json.JSONDecodeError:
                break
    tail = (errout or "")[-300:].replace("\n", " | ")
    return {"error": f"rung sf{sf:g}/{platform} exited rc={rc} "
                     f"with no report; stderr tail: {tail}"}


def _scenario_pass(sf: float, session_conf, aqe: bool) -> list:
    """One q13+q18 scenario sweep, static or adaptive.  The adaptive
    pass records the per-query aqe_* counter movement from the runner's
    observability block so the artifact shows what the re-optimizer
    actually did (broadcast switches, coalesced/split partitions,
    dynamic filters), not just the wall time."""
    from spark_rapids_tpu.bench.runner import run_benchmark
    conf = dict(session_conf or {})
    if aqe:
        conf["spark.sql.adaptive.shuffledHashJoin.enabled"] = True
    out = []
    srs = run_benchmark(
        os.path.join(DATA_DIR, f"tpch_sf{sf:g}"), sf,
        ["q13", "q18"], iterations=1, verify=True, suite="tpch",
        session_conf=conf or None)
    for sr in srs:
        row = {
            "suite": "tpch", "query": sr.get("query"),
            "kind": ("string_heavy" if sr.get("query") == "q13"
                     else "high_skew"),
            "adaptive": aqe,
            "ok": bool(sr.get("ok")) and not sr.get("error"),
            "speedup": sr.get("speedup"),
            "device_s": sr.get("device_s"),
            "oracle_s": sr.get("oracle_s"),
            "rows": sr.get("rows"),
        }
        if aqe:
            counters = (sr.get("observability", {})
                        .get("registry", {}).get("counters", {}))
            row["aqe"] = {k: v for k, v in counters.items()
                          if k.startswith("aqe_")}
        # memory-governor movement for this query: reclaim/grant/shed
        # counters plus the per-query peak-bytes gauges (the registry
        # delta is captured while the query's ExecCtx is still open, so
        # its governor.q.<qid>.* gauges are present)
        reg = sr.get("observability", {}).get("registry", {})
        gov = {k: v for k, v in reg.get("counters", {}).items()
               if k.startswith("governor_")}
        gov.update({k: v for k, v in reg.get("gauges", {}).items()
                    if k.startswith("governor.q.")
                    and k.endswith("peak_bytes")})
        if gov:
            row["governor"] = gov
        out.append(row)
    return out


def _child(sf: float, platform: str) -> None:
    """Run one rung in-process and print its report as the last line."""
    import jax
    if platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")
    from spark_rapids_tpu.runtime import enable_compilation_cache
    enable_compilation_cache()
    backend = jax.default_backend()
    # jax silently falls back to CPU when accelerator init FAILS fast
    # (vs hanging) — that mislabeling is not acceptable in the metric
    if (platform == "tpu") != (backend != "cpu"):
        print(_REPORT_PREFIX + json.dumps(
            {"ok": False,
             "error": f"requested {platform} but jax initialized "
                      f"'{backend}'"}), flush=True)
        os._exit(1)
    from spark_rapids_tpu.bench.runner import run_benchmark
    # pod-scale: when SPARK_RAPIDS_BENCH_MESH_DEVICES is set the rung's
    # plan runs sharded over the mesh; with fewer devices than that the
    # engine raises (a 1-device "mesh" run would mislabel the metric)
    session_conf = None
    if MESH_DEVICES > 1:
        session_conf = {"spark.rapids.tpu.mesh.deviceCount": MESH_DEVICES}
    # 3 iterations at every SF: the median discards the one-time
    # executable-cache load that dominates iteration 0, at the cost of
    # ~2 extra warm runs — the per-rung subprocess budget (not an
    # iteration count) is what bounds a slow backend here
    reports = run_benchmark(os.path.join(DATA_DIR, f"sf{sf:g}"), sf, ["q6"],
                            iterations=3, verify=True,
                            session_conf=session_conf)
    r = reports[0]
    if session_conf is not None:
        r["mesh_devices"] = MESH_DEVICES
    if r.get("ok") and r.get("rows", 0) <= 0:
        r["ok"] = False
        r["error"] = "query produced 0 rows"
    # scenario-diversity rider (ROADMAP): one string-heavy and one
    # high-skew query alongside q6, so fusion/compile wins aren't
    # measured on arithmetic-only plans.  TPC-H q13 is LIKE-dominated
    # (o_comment scan) and q18 concentrates on heavy-order keys.
    # Small SFs only, and never fatal to the rung: the q6 ladder metric
    # stays the gate, the scenarios ride along in the artifact.
    if r.get("ok") and sf <= 1:
        scenarios = []
        try:
            scenarios += _scenario_pass(sf, session_conf, aqe=False)
            # AQE on/off A-B on the same rungs: q13's string-heavy plan
            # and q18's skewed orderkeys are exactly where the
            # re-optimizer should move the aqe_* counters, and rows must
            # stay identical to the static pass either way
            scenarios += _scenario_pass(sf, session_conf, aqe=True)
        except Exception as e:  # pragma: no cover - rider must not gate
            scenarios.append({"error": str(e)[:300]})
        r["scenarios"] = scenarios
    print(_REPORT_PREFIX + json.dumps(r))
    sys.stdout.flush()
    # a wedged PJRT teardown must not eat the already-printed report
    os._exit(0)


def _mchild(n_devices: int, platform: str) -> None:
    """One MULTICHIP rung: q6 + q3 + q13 + q18 (TPC-H) on an n-device
    mesh.

    Prints a BENCH_REPORT line with per-query wall times.  The parent
    forces ``--xla_force_host_platform_device_count`` in this child's
    env for the virtual-CPU ladder, so jax must not initialize before
    that takes effect (it already has: env is set pre-spawn)."""
    import jax
    if platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")
    from spark_rapids_tpu.runtime import enable_compilation_cache
    enable_compilation_cache()
    have = len(jax.devices())
    if have < n_devices:
        print(_REPORT_PREFIX + json.dumps(
            {"ok": False, "error": f"need {n_devices} devices, have {have}"}),
            flush=True)
        os._exit(1)
    from spark_rapids_tpu.bench.runner import run_benchmark
    conf = ({"spark.rapids.tpu.mesh.deviceCount": n_devices}
            if n_devices > 1 else None)
    sf = MULTICHIP_SF
    reports = run_benchmark(
        os.path.join(DATA_DIR, f"tpch_sf{sf:g}"), sf,
        list(MULTICHIP_QUERIES), iterations=3, verify=True, suite="tpch",
        session_conf=conf)
    out = {"ok": True, "devices": n_devices, "queries": {}}
    for r in reports:
        q = r.get("query")
        qr = {"ok": bool(r.get("ok")) and not r.get("error"),
              "wall_s": r.get("device_s"), "rows": r.get("rows")}
        if r.get("error"):
            qr["error"] = str(r["error"])[:300]
        out["queries"][q] = qr
        out["ok"] = out["ok"] and qr["ok"]
    print(_REPORT_PREFIX + json.dumps(out))
    sys.stdout.flush()
    os._exit(0)


def _split_tpch_tables(data_dir: str, tables, parts: int) -> None:
    """Re-write each table as ``parts`` parquet files so its scan is
    multi-partition and the plans above it contain real shuffle
    exchanges for the cluster runtime to shard (a 1-file sf0.1 scan
    plans as a single complete aggregation with nothing to
    distribute)."""
    import pyarrow.parquet as pq
    for table in tables:
        d = os.path.join(data_dir, table)
        have = [f for f in os.listdir(d) if f.endswith(".parquet")]
        if len(have) >= parts:
            continue
        t = pq.read_table(os.path.join(d, "part-0.parquet"))
        step = -(-t.num_rows // parts)
        for i in range(parts):
            pq.write_table(t.slice(i * step, step),
                           os.path.join(d, f"part-{i}.parquet"))


def _cchild(n_workers: int, platform: str) -> None:
    """One CLUSTER rung: q6 + q3 (TPC-H) over a local[N] worker pool.

    Prints a BENCH_REPORT line with per-query wall times plus the
    cluster's registry movement and per-worker heartbeat deltas."""
    import jax
    if platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")
    from spark_rapids_tpu.runtime import enable_compilation_cache
    enable_compilation_cache()
    from spark_rapids_tpu.bench.runner import run_benchmark
    from spark_rapids_tpu.bench.tpch_gen import generate_tpch
    sf = CLUSTER_SF
    data = os.path.join(DATA_DIR, f"tpch_cluster_sf{sf:g}")
    generate_tpch(data, sf=sf)
    _split_tpch_tables(data, ("lineitem", "orders", "customer"), 4)
    conf = {"spark.rapids.cluster.mode": f"local[{n_workers}]"}
    reports = run_benchmark(data, sf, list(CLUSTER_QUERIES), iterations=2,
                            verify=True, suite="tpch", generate=False,
                            session_conf=conf)
    out = {"ok": True, "workers": n_workers, "queries": {}}
    for r in reports:
        q = r.get("query")
        obs = r.get("observability") or {}
        reg = (obs.get("registry") or {}).get("counters") or {}
        qr = {"ok": bool(r.get("ok")) and not r.get("error"),
              "wall_s": r.get("device_s"), "rows": r.get("rows"),
              "cluster": {k: v for k, v in reg.items()
                          if k.startswith("cluster")},
              "worker_deltas": obs.get("cluster_workers")}
        if r.get("error"):
            qr["error"] = str(r["error"])[:300]
        out["queries"][q] = qr
        out["ok"] = out["ok"] and qr["ok"]
    print(_REPORT_PREFIX + json.dumps(out))
    sys.stdout.flush()
    os._exit(0)


def _wchild(platform: str) -> None:
    """One CTAS write rung: q6-shaped CTAS, clean + chaos, in one
    killable child.  Prints a BENCH_REPORT line with the clean write's
    wall/rows/bytes plus each chaos variant's hash verdict."""
    import datetime
    import hashlib
    import shutil
    import tempfile

    import jax
    if platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")
    from spark_rapids_tpu.runtime import enable_compilation_cache
    enable_compilation_cache()
    from spark_rapids_tpu.bench.tpch_gen import generate_tpch
    from spark_rapids_tpu.expr.core import col, lit
    from spark_rapids_tpu.obs.registry import get_registry
    from spark_rapids_tpu.session import TpuSession
    sf = WRITE_SF
    data = os.path.join(DATA_DIR, f"tpch_write_sf{sf:g}")
    generate_tpch(data, sf=sf)
    _split_tpch_tables(data, ("lineitem",), 4)

    def ctas(conf, out):
        sess = TpuSession(conf)
        try:
            li = sess.read_parquet(
                os.path.join(data, "lineitem"),
                columns=["l_returnflag", "l_extendedprice", "l_discount",
                         "l_shipdate", "l_quantity"])
            q6ish = li.where(
                (col("l_shipdate") >= lit(datetime.date(1994, 1, 1)))
                & (col("l_shipdate") < lit(datetime.date(1995, 1, 1)))
                & (col("l_discount") >= lit(0.05))
                & (col("l_discount") <= lit(0.07))
                & (col("l_quantity") < lit(24.0)))
            t0 = time.perf_counter()
            stats = q6ish.write_parquet(out,
                                        partition_by=["l_returnflag"])
            return stats, time.perf_counter() - t0
        finally:
            sess.shutdown()

    def row_hash(out):
        import pyarrow.dataset as ds
        t = ds.dataset(out, format="parquet",
                       partitioning="hive").to_table()
        t = t.select(sorted(t.column_names))
        rows = sorted(zip(*(t.column(n).to_pylist()
                            for n in t.column_names)), key=str)
        h = hashlib.sha256()
        for r in rows:
            h.update(repr(r).encode())
        return h.hexdigest()

    base = tempfile.mkdtemp()
    clean_out = os.path.join(base, "clean")
    stats, wall = ctas({}, clean_out)
    want = row_hash(clean_out)
    out = {"ok": True, "sf": sf, "rows": stats.num_rows,
           "files": stats.num_files, "bytes": stats.num_bytes,
           "clean_wall_s": round(wall, 4),
           "rows_per_s": round(stats.num_rows / max(wall, 1e-9), 1),
           "read_back_hash": want[:16], "chaos": {}}
    storms = {
        "fault_storm": {"spark.rapids.test.faults":
                        "io.write.partial:crash,times=2;"
                        "io.write.commit.drop:drop,times=1;"
                        "io.write.rename.fail:fail,times=1"},
        "worker_death": {"spark.rapids.cluster.mode": "local[2]",
                         "spark.rapids.test.faults":
                         "cluster.worker.dead:dead,worker=w1,"
                         "seconds=0.02,times=1"},
    }
    for name, conf in storms.items():
        cdir = os.path.join(base, name)
        before = get_registry().snapshot()
        try:
            _, cwall = ctas(conf, cdir)
            delta = get_registry().delta(before)["counters"]
            injected = sum(v for k, v in delta.items()
                           if k.startswith("faults.injected."))
            exact = row_hash(cdir) == want
            out["chaos"][name] = {
                "ok": exact and injected > 0, "exact": exact,
                "faults_injected": injected, "wall_s": round(cwall, 4)}
        except Exception as e:  # pragma: no cover - reported, not raised
            out["chaos"][name] = {"ok": False, "error": str(e)[:300]}
        out["ok"] = out["ok"] and out["chaos"][name]["ok"]
    shutil.rmtree(base, ignore_errors=True)
    print(_REPORT_PREFIX + json.dumps(out))
    sys.stdout.flush()
    os._exit(0)


def _emit_write(rep: dict | None, error) -> None:
    rec = {
        "metric": f"tpch_ctas_write_sf{WRITE_SF:g}_cpu",
        "value": float((rep or {}).get("rows_per_s") or 0.0),
        "unit": "rows/s",
        "report": rep or {},
    }
    if error:
        rec["error"] = str(error)[:500]
    print(json.dumps(rec))
    sys.stdout.flush()


def _write_rung(deadline: float) -> None:
    """Fourth metric line: the transactional CTAS write rung, its own
    killable subprocess like every other ladder."""
    budget = min(WRITE_TIMEOUT_S, deadline - time.monotonic())
    if budget < 30:
        _emit_write(None, "no budget for write rung")
        return
    cmd = [sys.executable, os.path.abspath(__file__), "--wchild", "cpu"]
    rc, out, errout = _run_killable(
        cmd, budget,
        cwd=os.path.dirname(os.path.abspath(__file__)) or None)
    if rc is None:
        _emit_write(None, f"write rung killed after {budget:.0f}s")
        return
    rep = None
    for line in reversed(out.splitlines()):
        line = line.strip()
        if line.startswith(_REPORT_PREFIX):
            try:
                rep = json.loads(line[len(_REPORT_PREFIX):])
            except json.JSONDecodeError:
                pass
            break
    if rep is None:
        tail = (errout or "")[-300:].replace("\n", " | ")
        _emit_write(None, f"write rung rc={rc} no report; {tail}")
        return
    _emit_write(rep, None if rep.get("ok") else "write rung not exact")


def _schild(platform: str) -> None:
    """One killable mixed-tenant storm run: the whole grid plus the
    closed loop live in one child so every rung shares one warm
    compile cache and the comparison is apples-to-apples."""
    import jax
    if platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")
    from spark_rapids_tpu.runtime import enable_compilation_cache
    enable_compilation_cache()
    from spark_rapids_tpu.bench.storm import run_storm
    sf = STORM_SF
    rep = run_storm(os.path.join(DATA_DIR, f"tpch_sf{sf:g}"), sf,
                    duration_s=STORM_DURATION_S)
    print(_REPORT_PREFIX + json.dumps(rep))
    sys.stdout.flush()
    os._exit(0)


def _storm_rung(deadline: float) -> None:
    """Fifth metric line: the mixed-tenant storm — does the closed
    control loop serve SLOs that no fixed configuration can?"""
    rec = {
        "metric": f"tpch_storm_p99_slo_sf{STORM_SF:g}_cpu",
        "value": 0.0,
        "unit": "x",
    }
    budget = min(STORM_TIMEOUT_S, deadline - time.monotonic())
    if budget < 60:
        rec["error"] = "no budget for storm rung"
        print(json.dumps(rec))
        sys.stdout.flush()
        return
    cmd = [sys.executable, os.path.abspath(__file__), "--schild", "cpu"]
    rc, out, errout = _run_killable(
        cmd, budget,
        cwd=os.path.dirname(os.path.abspath(__file__)) or None)
    rep = None
    if rc is not None:
        for line in reversed(out.splitlines()):
            line = line.strip()
            if line.startswith(_REPORT_PREFIX):
                try:
                    rep = json.loads(line[len(_REPORT_PREFIX):])
                except json.JSONDecodeError:
                    pass
                break
    if rep is None:
        tail = (errout or "")[-300:].replace("\n", " | ")
        rec["error"] = (f"storm rung killed after {budget:.0f}s"
                        if rc is None else
                        f"storm rung rc={rc} no report; {tail}")
        print(json.dumps(rec))
        sys.stdout.flush()
        return
    rec["value"] = float(rep.get("closed_slo_margin") or 0.0)
    rec["ok"] = bool(rep.get("ok"))
    rec["report"] = rep
    if rep.get("error"):
        rec["error"] = str(rep["error"])[:500]
    print(json.dumps(rec))
    sys.stdout.flush()


def _tchild(platform: str) -> None:
    """One killable multi-stream throughput run (the whole ladder lives
    in one child: rungs share the warm session-level caches, which is
    the point of the measurement)."""
    import jax
    if platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")
    from spark_rapids_tpu.runtime import enable_compilation_cache
    enable_compilation_cache()
    from spark_rapids_tpu.bench.throughput import run_throughput
    sf = THROUGHPUT_SF
    rep = run_throughput(os.path.join(DATA_DIR, f"tpch_sf{sf:g}"), sf,
                         streams=THROUGHPUT_STREAMS,
                         queries=THROUGHPUT_QUERIES, suite="tpch")
    print(_REPORT_PREFIX + json.dumps(rep))
    sys.stdout.flush()
    os._exit(0)


def _throughput(deadline: float, tpu_probe_ok: bool) -> None:
    """Third metric line: the multi-stream throughput ladder.

    value = warm queries-per-hour at the LARGEST verified stream count;
    the rung list carries the whole curve plus cache-hit and fairness
    counter movement, and ``scaling_4v1`` pins the acceptance shape
    (4-stream warm throughput vs 1-stream)."""
    platform = "tpu" if tpu_probe_ok else "cpu"
    budget = min(THROUGHPUT_TIMEOUT_S, deadline - time.monotonic())
    rec = {
        "metric": f"tpch_multistream_qph_sf{THROUGHPUT_SF:g}_{platform}",
        "value": 0.0,
        "unit": "queries/hour",
        "streams": list(THROUGHPUT_STREAMS),
        "queries": list(THROUGHPUT_QUERIES),
    }
    if budget < 45:
        rec["error"] = "no budget for throughput ladder"
        print(json.dumps(rec))
        sys.stdout.flush()
        return
    cmd = [sys.executable, os.path.abspath(__file__), "--tchild", platform]
    rc, out, errout = _run_killable(
        cmd, budget,
        cwd=os.path.dirname(os.path.abspath(__file__)) or None)
    rep = None
    if rc is not None:
        for line in reversed(out.splitlines()):
            line = line.strip()
            if line.startswith(_REPORT_PREFIX):
                try:
                    rep = json.loads(line[len(_REPORT_PREFIX):])
                except json.JSONDecodeError:
                    pass
                break
    if rep is None:
        tail = (errout or "")[-300:].replace("\n", " | ")
        rec["error"] = (f"throughput run killed after {budget:.0f}s"
                        if rc is None else
                        f"throughput run rc={rc} no report; {tail}")
        print(json.dumps(rec))
        sys.stdout.flush()
        return
    rungs = rep.get("streams", [])
    qph = {r["streams"]: r for r in rungs
           if r.get("qph") and not r.get("errors")
           and not r.get("mismatches")}
    if qph:
        top = max(qph)
        rec["value"] = qph[top]["qph"]
        rec["streams_at_value"] = top
        if 1 in qph and 4 in qph and qph[1]["qph"] > 0:
            rec["scaling_4v1"] = round(qph[4]["qph"] / qph[1]["qph"], 3)
    rec["ok"] = bool(rep.get("ok"))
    rec["qph_cold_1stream"] = rep.get("qph_cold_1stream")
    rec["ladder"] = rungs
    if rep.get("error"):
        rec["error"] = str(rep["error"])[:500]
    print(json.dumps(rec))
    sys.stdout.flush()


def _emit_multichip(rungs: list, backend: str, error: str | None) -> None:
    """Second metric line: the MULTICHIP device-count scaling ladder.

    value = q3 scaling speedup t(1)/t(n) at the largest completed rung;
    every rung carries its wall times and efficiency t1/(n*tn) so the
    artifact shows the whole curve, not one point."""
    base = {}     # query -> t(1)
    for r in rungs:
        if r.get("devices") == 1 and r.get("ok"):
            for q, qr in r.get("queries", {}).items():
                if qr.get("ok") and qr.get("wall_s"):
                    base[q] = qr["wall_s"]
    value = 0.0
    top = 0
    for r in rungs:
        n = r.get("devices", 0)
        for q, qr in r.get("queries", {}).items():
            t = qr.get("wall_s")
            if qr.get("ok") and t and q in base:
                qr["speedup_vs_1dev"] = round(base[q] / t, 3)
                qr["efficiency"] = round(base[q] / (n * t), 3)
        q3 = r.get("queries", {}).get("q3", {})
        if r.get("ok") and n > top and "speedup_vs_1dev" in q3:
            top, value = n, q3["speedup_vs_1dev"]
    rec = {
        "metric": f"tpch_multichip_scaling_sf{MULTICHIP_SF:g}_{backend}",
        "value": round(float(value), 3),
        "unit": "x",
        "devices": top,
        "queries": list(MULTICHIP_QUERIES),
        "ladder": rungs,
    }
    if error:
        rec["error"] = str(error)[:500]
    print(json.dumps(rec))
    sys.stdout.flush()


def _multichip(deadline: float, tpu_probe_detail: str) -> None:
    """Climb the device-count ladder and emit the MULTICHIP metric line.

    Real multi-device TPU hardware is used when the probe saw >=2
    devices; otherwise the ladder runs on virtual CPU devices (honestly
    labeled ``cpu_virtual``) — scaling SHAPE is still meaningful there
    because the per-device programs and collectives are identical."""
    m = None
    for tok in tpu_probe_detail.split():
        if tok.startswith("x") and tok[1:].isdigit():
            m = int(tok[1:])
    max_n = max(MULTICHIP_LADDER)
    if m is not None and m >= 2:
        platform, backend = "tpu", "tpu"
        env = None
    else:
        platform, backend = "cpu", "cpu_virtual"
        env = _mesh_env(max_n)
    rungs: list[dict] = []
    err = None
    for n in MULTICHIP_LADDER:
        budget = min(MULTICHIP_TIMEOUT_S, deadline - time.monotonic())
        if budget < 45:
            err = (err or "") + f" (no budget for x{n})"
            break
        if platform == "tpu" and n > (m or 1):
            rungs.append({"devices": n, "ok": False,
                          "error": f"only {m} tpu devices"})
            continue
        cmd = [sys.executable, os.path.abspath(__file__),
               "--mchild", str(n), platform]
        kw = {"env": env} if env else {}
        rc, out, errout = _run_killable(
            cmd, budget,
            cwd=os.path.dirname(os.path.abspath(__file__)) or None, **kw)
        r = {"error": f"rung x{n} killed after {budget:.0f}s"} \
            if rc is None else None
        if r is None:
            for line in reversed(out.splitlines()):
                line = line.strip()
                if line.startswith(_REPORT_PREFIX):
                    try:
                        r = json.loads(line[len(_REPORT_PREFIX):])
                    except json.JSONDecodeError:
                        pass
                    break
            if r is None:
                tail = (errout or "")[-300:].replace("\n", " | ")
                r = {"error": f"rung x{n} rc={rc} no report; {tail}"}
        r.setdefault("devices", n)
        r.setdefault("ok", False)
        rungs.append(r)
        if not r["ok"]:
            err = r.get("error") or f"x{n} failed"
    _emit_multichip(rungs, backend, err)


def _emit_cluster(rungs: list, backend: str, error) -> None:
    base: dict = {}
    for r in rungs:
        if r.get("workers") == 1 and r.get("ok"):
            for q, qr in r.get("queries", {}).items():
                if qr.get("ok") and qr.get("wall_s"):
                    base[q] = qr["wall_s"]
    value = 0.0
    top = 0
    for r in rungs:
        n = r.get("workers", 0)
        for q, qr in r.get("queries", {}).items():
            t = qr.get("wall_s")
            if qr.get("ok") and t and q in base:
                qr["speedup_vs_1worker"] = round(base[q] / t, 3)
                qr["efficiency"] = round(base[q] / (n * t), 3)
        q3 = r.get("queries", {}).get("q3", {})
        if r.get("ok") and n > top and "speedup_vs_1worker" in q3:
            top, value = n, q3["speedup_vs_1worker"]
    rec = {
        "metric": f"tpch_cluster_scaling_sf{CLUSTER_SF:g}_{backend}",
        "value": round(float(value), 3),
        "unit": "x",
        "workers": top,
        "queries": list(CLUSTER_QUERIES),
        "ladder": rungs,
    }
    if error:
        rec["error"] = str(error)[:500]
    print(json.dumps(rec))
    sys.stdout.flush()


def _cluster_scaling(deadline: float) -> None:
    """Climb the worker-count ladder (local[1] -> local[2] -> local[4])
    and emit the CLUSTER metric line.  Each rung is its own killable
    subprocess — a wedged worker pool is killed, not waited on — and
    every query is oracle-verified, so a scaling number can never come
    from wrong rows."""
    rungs: list[dict] = []
    err = None
    for n in CLUSTER_LADDER:
        budget = min(CLUSTER_TIMEOUT_S, deadline - time.monotonic())
        if budget < 45:
            err = (err or "") + f" (no budget for {n} workers)"
            break
        cmd = [sys.executable, os.path.abspath(__file__),
               "--cchild", str(n), "cpu"]
        rc, out, errout = _run_killable(
            cmd, budget,
            cwd=os.path.dirname(os.path.abspath(__file__)) or None)
        r = {"error": f"rung {n}w killed after {budget:.0f}s"} \
            if rc is None else None
        if r is None:
            for line in reversed(out.splitlines()):
                line = line.strip()
                if line.startswith(_REPORT_PREFIX):
                    try:
                        r = json.loads(line[len(_REPORT_PREFIX):])
                    except json.JSONDecodeError:
                        pass
                    break
            if r is None:
                tail = (errout or "")[-300:].replace("\n", " | ")
                r = {"error": f"rung {n}w rc={rc} no report; {tail}"}
        r.setdefault("workers", n)
        r.setdefault("ok", False)
        rungs.append(r)
        if not r["ok"]:
            err = r.get("error") or f"{n} workers failed"
    _emit_cluster(rungs, "cpu", err)


def _ladder(deadline: float, rungs: list):
    """Climb the ladder on the TPU; returns ((sf, report) | None,
    err).  Every rung attempt (pass or fail) is appended to ``rungs`` so
    the emitted artifact shows the partial ladder, not just the summit."""
    best = None
    err = None
    for sf in LADDER:
        budget = deadline - time.monotonic()
        if budget < 45:
            err = (err or "") + f" (no budget for sf{sf:g})"
            break
        r = _run_rung(sf, "tpu", budget)
        rung = {"sf": sf, "backend": "tpu",
                "ok": bool(r.get("ok")) and not r.get("error")}
        for k in ("speedup", "device_s", "oracle_s", "rows", "scenarios"):
            if k in r:
                rung[k] = r[k]
        if r.get("error"):
            rung["error"] = str(r["error"])[:300]
        rungs.append(rung)
        if rung["ok"]:
            best = (sf, r)
        else:
            err = r.get("error") or f"sf{sf:g}: device != oracle"
            break
    return best, err


def _prewarm(sf: float) -> None:
    """Resumable compile-cache warmer: run the engine once on the TPU at
    a small SF purely to populate the persistent XLA executable cache
    (runtime.enable_compilation_cache), so a later bench run measures
    execution instead of compilation.  Safe to re-run; each invocation
    adds whatever entries the previous one didn't reach before being
    killed.  Exits 0 if the rung completed, 1 otherwise."""
    ok, detail = _probe_tpu(PROBE_TIMEOUT_S)
    print(f"prewarm: tpu probe: {detail}", file=sys.stderr)
    if not ok:
        sys.exit(1)
    budget = TOTAL_TIMEOUT_S
    r = _run_rung(sf, "tpu", budget)
    print(f"prewarm: rung sf{sf:g} -> "
          f"{'ok' if r.get('ok') else r.get('error')}", file=sys.stderr)
    sys.exit(0 if r.get("ok") else 1)


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        _child(float(sys.argv[2]), sys.argv[3])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--mchild":
        _mchild(int(sys.argv[2]), sys.argv[3])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--cchild":
        _cchild(int(sys.argv[2]), sys.argv[3])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--tchild":
        _tchild(sys.argv[2])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--wchild":
        _wchild(sys.argv[2])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--schild":
        _schild(sys.argv[2])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--prewarm":
        _prewarm(float(sys.argv[2]) if len(sys.argv) > 2 else 0.1)
        return
    deadline = time.monotonic() + TOTAL_TIMEOUT_S
    rungs: list[dict] = []
    probe_ok, probe_detail = _probe_tpu(PROBE_TIMEOUT_S)
    if probe_ok:
        best, err = _ladder(deadline, rungs)
    else:
        # don't burn a full rung timeout on a backend that can't even
        # enumerate devices
        best, err = None, f"tpu probe failed: {probe_detail}"
    if best is None:
        # no chip, no number: nothing measured on a CPU is emitted under
        # this benchmark's names
        print(json.dumps({"error": err or "no rung completed",
                          "ladder": rungs, "tpu_probe": probe_detail}))
        sys.exit(1)
    extra = {"ladder": rungs, "tpu_probe": probe_detail}
    if MESH_DEVICES > 1:
        extra["mesh_devices"] = MESH_DEVICES
    sf, r = best
    extra.update({"device_s": r.get("device_s"),
                  "oracle_s": r.get("oracle_s"),
                  "rows": r.get("rows")})
    if r.get("scenarios"):
        extra["scenarios"] = r["scenarios"]
    _emit(r.get("speedup", 0.0), sf, error=err, extra=extra)
    # second metric line: the pod-scale device-count ladder (q6 + q3 +
    # q13 + q18 at 1/2/4/8 devices).  Runs after the primary metric so a
    # wedged mesh rung can never eat the gate number.
    mc_deadline = time.monotonic() + MULTICHIP_TIMEOUT_S
    try:
        _multichip(mc_deadline, probe_detail)
    except Exception as e:  # pragma: no cover - rider must not gate
        _emit_multichip([], "none", f"multichip ladder crashed: {e}")
    # cluster-runtime worker ladder (q6 + q3 at local[1]/[2]/[4]):
    # runs after the primary metric so a wedged worker pool can never
    # eat the gate number
    c_deadline = time.monotonic() + CLUSTER_TIMEOUT_S
    try:
        _cluster_scaling(c_deadline)
    except Exception as e:  # pragma: no cover - rider must not gate
        _emit_cluster([], "none", f"cluster ladder crashed: {e}")
    # third metric line: the multi-stream serving-tier throughput ladder
    # (queries-per-hour at 1/2/4/8 concurrent tenant streams, warm)
    t_deadline = time.monotonic() + THROUGHPUT_TIMEOUT_S
    try:
        _throughput(t_deadline, probe_ok)
    except Exception as e:  # pragma: no cover - rider must not gate
        print(json.dumps({
            "metric": f"tpch_multistream_qph_sf{THROUGHPUT_SF:g}_none",
            "value": 0.0, "unit": "queries/hour",
            "error": f"throughput ladder crashed: {e}"}))
        sys.stdout.flush()
    # fourth metric line: the transactional CTAS write rung (clean
    # throughput + fault-storm/worker-death exactness)
    w_deadline = time.monotonic() + WRITE_TIMEOUT_S
    try:
        _write_rung(w_deadline)
    except Exception as e:  # pragma: no cover - rider must not gate
        _emit_write(None, f"write rung crashed: {e}")
    # fifth metric line: the mixed-tenant storm — the closed control
    # loop vs a fixed admission grid under the same self-calibrated SLOs
    s_deadline = time.monotonic() + STORM_TIMEOUT_S
    try:
        _storm_rung(s_deadline)
    except Exception as e:  # pragma: no cover - rider must not gate
        print(json.dumps({
            "metric": f"tpch_storm_p99_slo_sf{STORM_SF:g}_cpu",
            "value": 0.0, "unit": "x",
            "error": f"storm rung crashed: {e}"}))
        sys.stdout.flush()


if __name__ == "__main__":
    main()
