"""TPC-DS benchmark runner: per-query timing + JSON reports.

Reference: BenchmarkRunner.scala (collect/writeParquet modes, iteration
timing) + BenchUtils.scala (JSON report per run) + CompareResults.scala
(CPU-vs-accelerator output verification).  Here verification is the
host-oracle backend of the same plan (the round-trip the test suite
uses), selected with ``--verify``.

CLI:
    python -m spark_rapids_tpu.bench.runner --sf 0.1 --queries q3,q6 \
        --data-dir /tmp/tpcds --iterations 2 --verify
"""
from __future__ import annotations

import argparse
import json
import os
import time

__all__ = ["run_benchmark"]


def _collect_rows(df, backend: str, plan=None, metrics_out: dict | None = None,
                  obs_out: dict | None = None):
    from spark_rapids_tpu.exec.core import (ExecCtx, collect_device,
                                            collect_host, device_to_host,
                                            _rows_from_host)
    if plan is None:
        ov, meta = df._overridden(quiet=True)
        plan = meta.exec_node

    def make_ctx() -> ExecCtx:
        ctx = ExecCtx(backend=backend, conf=df._s.conf)
        if backend == "device":
            # session-owned cluster pool (cluster/driver.py); the host
            # oracle stays single-process on purpose
            cluster = df._s._cluster()
            if cluster is not None:
                ctx.cache["cluster"] = cluster
        return ctx

    if metrics_out is None:
        if backend == "host":
            return collect_host(plan, df._s.conf)
        return collect_device(plan, df._s.conf, ctx=make_ctx())
    # metrics-capturing run (reference BenchUtils JSON reports include
    # per-exec SQL metrics, docs/benchmarks.md:149-163)
    with make_ctx() as ctx:
        from spark_rapids_tpu.obs.registry import get_registry
        before = get_registry().snapshot() if obs_out is not None else None
        out = []
        for b in plan.execute(ctx):
            hb = device_to_host(b) if backend == "device" else b
            out.extend(_rows_from_host(hb))
        for key, m in ctx.metrics.items():
            name = key.split("@")[0]
            agg = metrics_out.setdefault(name, {})
            for k, v in m.values.items():
                agg[k] = round(agg.get(k, 0.0) + v, 4)
        cat = ctx.cache.get("catalog")
        if cat is not None:
            # memory-plane counters (spills, oom_retries/oom_splits,
            # device_bytes_peak) live on the BufferCatalog, not on any
            # one exec — report them alongside the per-exec metrics
            metrics_out["BufferCatalog"] = dict(cat.metrics)
        if obs_out is not None:
            # full observability record: registry counter MOVEMENT over
            # this run (the process registry is cumulative), ids tying
            # the report to any exported trace, and the analyzed plan
            from spark_rapids_tpu.plan.overrides import explain_analyze
            obs_out["query_id"] = ctx.query_id
            obs_out["trace_id"] = ctx.trace_id
            obs_out["registry"] = get_registry().delta(before)
            cluster = ctx.cache.get("cluster")
            if cluster is not None:
                # per-worker registry movement (heartbeat snapshots
                # diffed against each worker's first) — the cluster
                # bench rungs report these alongside the driver's delta
                obs_out["cluster_workers"] = \
                    cluster.worker_registry_deltas()
            obs_out["plan_analyzed"] = explain_analyze(
                plan, ctx).splitlines()
            prof = ctx.cache.get("profiler")
            if prof is not None:
                # cost-attribution artifact (obs/profile.py): the same
                # schema-checked document the profile dir export writes
                obs_out["profile"] = prof.artifact()
        return out


def _plan_of(df):
    ov, meta = df._overridden(quiet=True)
    return meta.exec_node


def _norm(rows, digits=6):
    """Order-insensitive row normalization with float tolerance: device
    and oracle may sum doubles in different orders (streaming joins /
    concurrent partials), and on-chip f64 is a float32 pair (~48-bit
    mantissa, docs/compatibility.md), so floats compare at ``digits``
    significant digits (reference asserts.py approximate_float)."""
    def cell(x):
        if isinstance(x, float):
            return (x is None, f"{x:.{digits}g}")
        return (x is None, str(x))
    return sorted(tuple(cell(x) for x in r) for r in rows)


def _rows_match(got, want, strict: bool | None = None) -> bool:
    """Exact significant-digit match, falling back to a PAIRED
    relative comparison: fixed-digit formatting is boundary-brittle —
    1-ulp summation-order noise on a value sitting exactly at a digit
    boundary (q47's 103.1275, q20's HALF_UP money ratios) flips the
    formatted string while the values agree to 1e-10.  The fallback
    buckets rows by their NON-float cells and greedily pairs each got
    row with an unused want row whose floats all agree within a
    relative tolerance (reference approximate_float semantics,
    asserts.py) — no float takes part in any ordering, so
    boundary/NaN/mixed-type sort brittleness cannot mispair rows.

    The tolerance is keyed on the device backend: on true-f64 platforms
    (XLA:CPU) the only legitimate noise is summation order, so floats
    compare at 12 digits / rel 1e-9; the loose 6-digit / rel 1e-5
    tier applies only when the f32-pair f64 emulation is in play (TPU
    backend, ~48-bit mantissa)."""
    import math
    from collections import defaultdict
    if strict is None:
        import jax
        strict = jax.default_backend() != "tpu"
    digits, rel, abst = (12, 1e-9, 1e-11) if strict else (6, 1e-5, 1e-7)
    if _norm(got, digits) == _norm(want, digits):
        return True
    if len(got) != len(want):
        return False

    def fixed(r):
        return tuple((i, x is None, str(x)) for i, x in enumerate(r)
                     if not isinstance(x, float))

    def floats(r):
        return [(i, x) for i, x in enumerate(r) if isinstance(x, float)]

    def close(a, b):
        fa, fb = floats(a), floats(b)
        if [i for i, _ in fa] != [i for i, _ in fb]:
            return False
        for (_, x), (_, y) in zip(fa, fb):
            if math.isnan(x) and math.isnan(y):
                continue
            if math.isnan(x) or math.isnan(y):
                return False
            if not math.isclose(x, y, rel_tol=rel, abs_tol=abst):
                return False
        return True

    buckets = defaultdict(list)
    for r in want:
        buckets[fixed(r)].append(r)
    for r in got:
        cands = buckets.get(fixed(r))
        if not cands:
            return False
        for i, w in enumerate(cands):
            if close(r, w):
                cands.pop(i)
                break
        else:
            return False
    return True


def run_benchmark(data_dir: str, sf: float, queries, iterations: int = 1,
                  verify: bool = False, session_conf: dict | None = None,
                  generate: bool = True, suite: str = "tpcds") -> list[dict]:
    """Run each query ``iterations`` times on the device engine; report
    per-query wall times (median), row counts, and optional host-oracle
    verification. Returns a list of per-query report dicts.
    ``suite`` selects the workload: "tpcds" (default), "tpch",
    "tpcxbb", or "mortgage" (reference BenchmarkRunner supports the
    same suites, BenchmarkRunner.scala)."""
    from spark_rapids_tpu.session import TpuSession
    if suite == "tpch":
        from spark_rapids_tpu.bench.tpch_gen import generate_tpch as gen
        from spark_rapids_tpu.bench.tpch_queries import (
            build_tpch_query as build_query)
    elif suite == "mortgage":
        from spark_rapids_tpu.bench.mortgage import (
            build_mortgage_query as build_query, generate_mortgage as gen)
    elif suite == "tpcxbb":
        from spark_rapids_tpu.bench.tpcxbb_gen import (
            generate_tpcxbb as gen)
        from spark_rapids_tpu.bench.tpcxbb_queries import (
            build_tpcxbb_query as build_query)
    else:
        from spark_rapids_tpu.bench.tpcds_gen import generate_tpcds as gen
        from spark_rapids_tpu.bench.tpcds_queries import build_query

    if generate:
        t0 = time.perf_counter()
        gen(data_dir, sf=sf)
        gen_s = time.perf_counter() - t0
    else:
        gen_s = 0.0

    reports = []
    for name in queries:
        session = TpuSession(dict(session_conf or {}))
        rec = {"query": name, "sf": sf, "gen_s": round(gen_s, 3)}
        try:
            times = []
            rows = None
            # ONE plan reused across iterations: the reference's kernels
            # are precompiled library entry points, so the steady-state
            # analog here is traced-and-compiled programs, not re-tracing
            # a fresh expression tree per run
            df = build_query(name, session, data_dir)
            plan = _plan_of(df)
            metrics: dict = {}
            obs: dict = {}
            for it in range(max(1, iterations)):
                t0 = time.perf_counter()
                # last iteration captures per-operator metrics + plan
                # (reference BenchmarkRunner JSON reports)
                last = it == iterations - 1
                rows = _collect_rows(
                    df, "device", plan,
                    metrics_out=metrics if last else None,
                    obs_out=obs if last else None)
                times.append(time.perf_counter() - t0)
            times.sort()
            rec["device_s"] = round(times[len(times) // 2], 4)
            rec["device_s_all"] = [round(t, 4) for t in times]
            rec["rows"] = len(rows)
            rec["plan"] = plan.tree_string().strip().splitlines()
            rec["metrics"] = metrics
            rec["observability"] = obs
            if verify:
                t0 = time.perf_counter()
                oracle = _collect_rows(df, "host", plan)
                rec["oracle_s"] = round(time.perf_counter() - t0, 4)
                rec["ok"] = _rows_match(rows, oracle)
            else:
                rec["ok"] = True
        except Exception as e:  # noqa: BLE001 - per-query isolation
            from spark_rapids_tpu.exec.lifecycle import QueryLifecycleError
            if isinstance(e, QueryLifecycleError):
                # cancellation / deadline / shutdown apply to the whole
                # run — recording them as a per-query failure and moving
                # on would keep benchmarking a killed session.  Other
                # terminal errors (e.g. unrecoverable map-output loss)
                # kill only THIS query and are part of the report.
                raise
            rec["error"] = f"{type(e).__name__}: {e}"
            rec["ok"] = False
        finally:
            # release per-query session resources NOW, not at interpreter
            # exit — in cluster mode each session owns a pool of worker
            # subprocesses that would otherwise pile up across queries
            session.shutdown(drain=False)
        reports.append(rec)
    return reports


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--data-dir", default=os.environ.get(
        "TPCDS_DATA_DIR", "/tmp/tpcds_data"))
    ap.add_argument("--sf", type=float, default=0.01)
    ap.add_argument("--queries", default="q3,q6,q42,q52,q55")
    ap.add_argument("--iterations", type=int, default=1)
    ap.add_argument("--verify", action="store_true")
    ap.add_argument("--suite", default="tpcds", choices=("tpcds", "tpch", "mortgage", "tpcxbb"))
    ap.add_argument("--train", action="store_true",
                    help="mortgage suite: run the ETL -> to_jax -> "
                         "jitted training pipeline (BASELINE config 5)")
    ap.add_argument("--report", default=None,
                    help="write the JSON report to this path")
    args = ap.parse_args()

    data_dir = os.path.join(args.data_dir, f"sf{args.sf:g}")
    if args.train:
        assert args.suite == "mortgage", "--train is a mortgage mode"
        from spark_rapids_tpu.bench.mortgage import (generate_mortgage,
                                                     train_pipeline)
        from spark_rapids_tpu.session import TpuSession
        generate_mortgage(data_dir, sf=args.sf)
        rec = train_pipeline(TpuSession({}), data_dir)
        out = json.dumps(rec, indent=2)
        print(out)
        if args.report:
            with open(args.report, "w") as f:
                f.write(out + "\n")
        return
    reports = run_benchmark(data_dir, args.sf,
                            [q.strip() for q in args.queries.split(",")],
                            iterations=args.iterations, verify=args.verify,
                            suite=args.suite)
    out = json.dumps(reports, indent=2)
    print(out)
    if args.report:
        with open(args.report, "w") as f:
            f.write(out + "\n")


if __name__ == "__main__":
    main()
