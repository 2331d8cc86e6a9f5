#!/usr/bin/env python3
"""Chip smoke: TPC-DS q6 through session -> planner -> exec on the TPU.

The quickest proof that the engine still starts on the chip.  One
process, normal entry points only (``TpuSession`` ->
``bench.tpcds_queries.build_query("q6")`` -> ``.collect()``), at the
scale of BASELINE.json configs[0] (SF10, single local executor):

    python chip_smoke.py                  # one chip, SF10
    python chip_smoke.py --chips 4        # the mesh path only

On one chip TPC-H Q6 follows at a hundredth of the scale (SF0.1), with
the rows it keeps counted by discount: its predicates compare float64
columns with the fractional literals 0.05 and 0.07, which the chip's
f32-pair arithmetic answered wrongly until PR 46 (every row at exactly
0.05 dropped); the counts are integers, held to the host oracle's.

The platform is checked FIRST, before any data is generated, so a
machine whose chip did not come up fails in seconds.  Every earlier
output line is one JSON fact; the last line is the result
``{"ok": true, "device": {...}}``.  Any failed check or exception in any
phase exits non-zero and prints no result line.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import time

_HERE = os.path.dirname(os.path.abspath(__file__))

#: the five tables q6 reads (every generated column kept)
Q6_TABLES = ["date_dim", "item", "customer", "customer_address",
             "store_sales"]


def _say(**fact) -> None:
    print(json.dumps(fact), flush=True)


def _require(cond, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke: {msg}")


def _peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


@contextlib.contextmanager
def _watch_compiles():
    """Yield ``(cache, compiles)``: persistent-cache hit/miss counts and
    ``(seconds, name)`` of every XLA backend compile of the process while
    the block runs — eager ops included, which SharedJit's
    ``compile_count`` cannot see."""
    import jax
    cache = {"hits": 0, "misses": 0}
    compiles: list[tuple[float, str]] = []

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    def on_duration(event, secs, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append((secs, fun_name))

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield cache, compiles
    finally:
        jax.monitoring.unregister_event_listener(on_event)
        jax.monitoring.unregister_event_duration_listener(on_duration)


def run(sf: float, seed: int, chips: int, expect_platform: str = "tpu",
        data_dir: str = os.path.join(_HERE, ".bench_data")) -> dict:
    """Everything the smoke does; returns the result object of the last
    line.  ``expect_platform="cpu"`` is the rehearsal the tests call —
    no command-line or environment switch reaches it."""
    t_start = time.perf_counter()
    from spark_rapids_tpu import TpuSession

    conf = {
        # an operator that would run on the host raises instead of
        # quietly taking the work off the chip
        "spark.rapids.sql.test.enabled": "true",
        # warm collects must reach the chip, not the result cache
        "spark.rapids.sql.resultCache.enabled": "false",
    }
    if chips > 1:
        conf["spark.rapids.tpu.mesh.deviceCount"] = str(chips)
    session = TpuSession(conf)          # ensure_runtime: device init
    try:
        with _watch_compiles() as watch:
            return _smoke(session, sf, seed, chips, expect_platform,
                          data_dir, t_start, watch)
    finally:
        session.shutdown(drain=False)


def _smoke(session, sf, seed, chips, expect_platform, data_dir, t_start,
           watch) -> dict:
    from spark_rapids_tpu.device import device_info
    info = device_info()
    _require(info["platform"] == expect_platform,
             f"platform is {info['platform']!r}, expected "
             f"{expect_platform!r}")
    # on the chip the count is exact; virtual CPU devices of a rehearsal
    # only have to be enough for the mesh
    _require(info["device_count"] == chips
             or (expect_platform != "tpu" and info["device_count"] >= chips),
             f"{info['device_count']} device(s) present, "
             f"--chips {chips} asked")
    _say(phase="device", **info)

    import jax

    from spark_rapids_tpu import native, runtime
    from spark_rapids_tpu.bench.runner import (_collect_rows, _plan_of,
                                               _rows_match)
    from spark_rapids_tpu.bench.tpcds_gen import generate_tpcds
    from spark_rapids_tpu.bench.tpcds_queries import build_query
    from spark_rapids_tpu.obs.registry import get_registry

    cache_hits, xla_compiles = watch

    t0 = time.perf_counter()
    rows_per_table = generate_tpcds(data_dir, sf=sf, seed=seed,
                                    tables=Q6_TABLES)
    _say(phase="data", sf=sf, seed=seed, dir=data_dir,
         rows=rows_per_table, gen_s=time.perf_counter() - t0)

    # the host arena / spill codec library: q6 in HBM never needs it, so
    # load it here — built with g++ from the committed sources when the
    # git-ignored .so is not on disk
    so_found = os.path.exists(native._so_path())
    native.load()
    _say(phase="native", so=os.path.basename(native._so_path()),
         found_on_disk=so_found, built=not so_found)

    df = build_query("q6", session, data_dir)
    plan_lines = df.explain().splitlines()
    exec_plan = _plan_of(df).tree_string().strip().splitlines()
    _say(phase="plan", explain=plan_lines, exec=exec_plan)
    _require(all(ln.lstrip().startswith("*") for ln in plan_lines),
             "plan holds a host-fallback node")
    if chips > 1:
        _require(any("Mesh" in ln for ln in exec_plan),
                 "no Mesh* node in the executed plan")

    devices = jax.devices()[:chips]
    peak_before = _peak_bytes(devices)
    reg = get_registry()
    rows = None
    for label in ("cold", "warm1", "warm2"):
        before = reg.snapshot()
        t0 = time.perf_counter()
        rows = df.collect()
        wall = time.perf_counter() - t0
        moved = reg.delta(before)["counters"]
        _say(phase="collect", run=label, seconds=wall, rows=len(rows),
             compile_count=moved.get("compile_count", 0),
             compile_wall_s=moved.get("compile_wall_s", 0.0),
             queries_executed=moved.get("queries_executed", 0))
        _require(moved.get("queries_executed", 0) == 1,
                 f"{label} collect did not reach the executor")
        _require(label == "cold" or not moved.get("compile_count"),
                 f"{label} collect compiled "
                 f"{moved.get('compile_count')} program(s)")
    _require(len(rows) > 0, "q6 returned no rows")

    if chips > 1:
        peak_after = _peak_bytes(devices)
        _say(phase="mesh", devices=[str(d) for d in devices],
             peak_bytes_before=peak_before, peak_bytes_after=peak_after)
        if expect_platform == "tpu":    # XLA:CPU reports no memory stats
            _require(all(a > (b or 0)
                         for a, b in zip(peak_after, peak_before)),
                     "not every mesh device held shards")

    # the host oracle runs the same plan on the host backend, outside
    # the timed collects
    t0 = time.perf_counter()
    want = _collect_rows(df, "host")
    _say(phase="oracle", oracle_s=time.perf_counter() - t0,
         rows=len(want))
    # the TPU tolerance whatever the platform: one comparison everywhere
    _require(_rows_match(rows, want, strict=False),
             "device rows differ from the host oracle")

    if chips == 1:
        _tpch_q6(session, sf / 100, seed, os.path.join(data_dir, "tpch"))

    cache_dir = runtime._enabled_dir
    entries = 0
    if cache_dir and os.path.isdir(cache_dir):
        entries = sum(1 for f in os.listdir(cache_dir)
                      if not f.endswith("-atime"))
    _say(phase="cache", dir=cache_dir, entries=entries,
         persistent_hits=cache_hits["hits"],
         persistent_misses=cache_hits["misses"])
    xla_compiles.sort(reverse=True)
    _say(phase="xla_compiles", count=len(xla_compiles),
         total_s=sum(s for s, _ in xla_compiles),
         slowest=[[round(s, 3), name] for s, name in xla_compiles[:12]])
    _say(phase="done", total_s=time.perf_counter() - t_start)
    # device_info holds what jax.devices() reported at init
    return {"ok": True,
            "device": {"platform": info["platform"],
                       "kind": info["device_kind"],
                       "count": info["device_count"]}}


def _tpch_q6(session, sf: float, seed: int, data_dir: str) -> None:
    """TPC-H Q6, and the rows its ``where`` clause keeps counted by
    discount, against the host oracle: the comparisons of a float64
    column with 0.05 and 0.07 are made on the device."""
    import datetime

    from spark_rapids_tpu.bench.runner import _collect_rows, _rows_match
    from spark_rapids_tpu.bench.tpch_gen import generate_tpch
    from spark_rapids_tpu.bench.tpch_queries import q6
    from spark_rapids_tpu.expr.aggregates import CountStar
    from spark_rapids_tpu.expr.core import col, lit

    t0 = time.perf_counter()
    generate_tpch(data_dir, sf=sf, seed=seed)
    gen_s = time.perf_counter() - t0
    revenue = q6(session, data_dir)
    by_discount = session.read_parquet(
        os.path.join(data_dir, "lineitem"),
        columns=["l_discount", "l_shipdate", "l_quantity"]) \
        .where((col("l_shipdate") >= lit(datetime.date(1994, 1, 1)))
               & (col("l_shipdate") < lit(datetime.date(1995, 1, 1)))
               & (col("l_discount") >= lit(0.05))
               & (col("l_discount") <= lit(0.07))
               & (col("l_quantity") < lit(24.0))) \
        .group_by("l_discount").agg(CountStar().alias("lines")) \
        .order_by(("l_discount", True))
    for name, df in (("q6", revenue), ("q6 by discount", by_discount)):
        _require(all(ln.lstrip().startswith("*")
                     for ln in df.explain().splitlines()),
                 f"TPC-H {name}: plan holds a host-fallback node")
        t0 = time.perf_counter()
        rows = df.collect()
        wall = time.perf_counter() - t0
        want = _collect_rows(df, "host")
        _say(phase="tpch_q6", query=name, sf=sf, gen_s=gen_s, seconds=wall,
             rows=[list(r) for r in rows], oracle=[list(r) for r in want])
        _require(_rows_match(rows, want, strict=False),
                 f"TPC-H {name}: device rows differ from the host oracle")
    _require([r[0] for r in rows] == [0.05, 0.06, 0.07]
             and [r[1] for r in rows] == [w[1] for w in want],
             "TPC-H q6: a discount bound lost or split its rows")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=10.0,
                    help="TPC-DS scale factor (BASELINE configs[0]: 10)")
    ap.add_argument("--seed", type=int, default=42,
                    help="data generator seed")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs the mesh phase and nothing else")
    args = ap.parse_args()
    result = run(args.sf, args.seed, args.chips, "tpu")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
