"""One run of one cell: device check, data, reference, session and
warm-up (all set-up), a closed-loop window of collects, the check.

Order of phases and the facts printed follow chip_smoke.py (PR 22).
Every line printed before the result is one free-form JSON fact.
"""
from __future__ import annotations

import json
import os
import shutil
import statistics
import time
from dataclasses import dataclass

from . import bytes as scanned_bytes
from . import host, reduce_trace
from .cell import ROOT, Cell, load_cell, load_module
from .compare import rows_match
from .compiles import watch_compiles

#: a cell whose collect takes longer traces one collect, else up to three
LONG_COLLECT_S = 5.0


class BenchError(RuntimeError):
    """A check of the harness failed: no result line is printed."""


def say(**fact) -> None:
    print(json.dumps(fact), flush=True)


def _require(cond, msg: str) -> None:
    if not cond:
        raise BenchError(f"benchmark: {msg}")


def check_devices(cell: Cell, expect_platform: str) -> list:
    """The cell's devices, or BenchError: first thing in a run, so a
    machine without its chips fails in seconds, before data is made."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    _require(platform == expect_platform,
             f"platform is {platform!r}, expected {expect_platform!r}")
    # on the chip the count is exact; the virtual CPU devices of a
    # rehearsal only have to be enough for the mesh
    _require(len(devices) == cell.chips
             or (expect_platform != "tpu" and len(devices) >= cell.chips),
             f"{len(devices)} device(s) present, the cell asks {cell.chips}")
    return devices[:cell.chips]


def _peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices]


def _make_data(cell: Cell, seed: int, root: str, queries) -> str:
    """Generate this seed's tables in place of whatever an earlier run
    left of the cell's dataset.  Every run makes its data, as every run
    of a check does: a run that found its tables on disk collected
    slower and less steadily than one that had just made them (PERF.md,
    Findings PR 41), and two runs are compared like with like."""
    base = os.path.join(root, ".bench_data", cell.dataset)
    shutil.rmtree(base, ignore_errors=True)
    data_dir = os.path.join(base, f"seed{seed}")
    os.makedirs(data_dir)
    tables = sorted({t for q in queries for t in q.mod.TABLES})
    gen = load_module(root, "datagen", cell.config["datagen"])
    gen.generate(data_dir, cell.config["scale_factor"], seed, tables)
    return data_dir


def _reference_rows(root: str, data_dir: str, qfile: str) -> list:
    """The plain reference's rows, left beside the data they were
    computed from for a look by hand."""
    rows = load_module(root, "reference", qfile).rows(data_dir)
    with open(os.path.join(data_dir, f"reference_{qfile}.json"), "w") as f:
        json.dump(rows, f)
    return [tuple(r) for r in rows]


def _plan_nodes(df) -> list:
    """Lines of the executed plan's tree (the program's own rendering)."""
    _, meta = df._overridden(quiet=True)
    return meta.exec_node.tree_string().strip().splitlines()


def run(workload: str, seed: int, seconds: float, trace: bool,
        root: str = ROOT, expect_platform: str = "tpu",
        t_start: float | None = None,
        keep_trace_dir: str | None = None) -> dict:
    """Everything one run does; returns the object of the last line.
    ``expect_platform="cpu"`` is the rehearsal benchmark/tests calls, and
    ``keep_trace_dir`` keeps the loaded trace for a look by hand
    (benchmark/tests/record_trace.py) — no command-line or environment
    switch reaches either."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(workload, root)
    devices = check_devices(cell, expect_platform)
    say(phase="device", platform=devices[0].platform,
        kind=devices[0].device_kind, count=len(devices), cell=cell.name)

    # the system under test, before any data is made: a directory that
    # holds only the benchmark fails here
    from spark_rapids_tpu import TpuSession

    queries = [_Query(q, load_module(root, "queries", f"{cell.suite}_{q}"))
               for q in cell.traffic["queries"]]

    t0 = time.perf_counter()
    data_dir = _make_data(cell, seed, root, queries)
    gen_s = time.perf_counter() - t0
    for query in queries:
        query.scan = scanned_bytes.scanned(data_dir, query.mod.TABLES)
    say(phase="data", dataset=cell.dataset, seed=seed, gen_s=gen_s,
        scanned={query.name: query.scan for query in queries})

    t0 = time.perf_counter()
    for query in queries:
        query.want = _reference_rows(root, data_dir,
                                     f"{cell.suite}_{query.name}")
    reference_s = time.perf_counter() - t0
    say(phase="reference", reference_s=reference_s,
        rows={query.name: len(query.want) for query in queries})

    counters = {"gen_s": gen_s, "reference_s": reference_s}
    with watch_compiles() as watch:
        session = TpuSession(dict(cell.config["conf"]))
        try:
            for query in queries:
                query.df = query.mod.build(session, data_dir)
            return _measure(cell, devices, queries, seconds, trace, root,
                            t_start, watch, keep_trace_dir, counters)
        finally:
            session.shutdown(drain=False)


@dataclass
class _Query:
    name: str
    mod: object                 # benchmark/queries/<suite>_<name>.py
    scan: dict | None = None    # rows, bytes, files it scans
    want: list | None = None    # the plain reference's rows
    df: object = None           # the DataFrame, built in the session


def _measure(cell, devices, queries, seconds, trace, root, t_start, watch,
             keep_trace_dir, counters) -> dict:
    cache, compiles = watch
    # ---- set-up: check the plan, one warm-up collect a query
    for query in queries:
        explain = query.df.explain().splitlines()
        nodes = _plan_nodes(query.df)
        say(phase="plan", query=query.name, exec=nodes)
        _require(all(ln.lstrip().startswith("*") for ln in explain),
                 f"{query.name}: the plan holds a host-fallback node")
        _require(cell.chips == 1 or any("Mesh" in n for n in nodes),
                 f"{query.name}: no Mesh* node in the executed plan")
    peak_before = _peak_bytes(devices)
    warm_s = {}
    for query in queries:
        _, warm_s[query.name], rows = _timed_collect(query, fatal=True)
        _require(rows_match(rows, query.want),
                 f"{query.name}: warm-up rows differ from the reference: "
                 f"{len(rows)} rows {rows[:3]}, expected "
                 f"{len(query.want)} rows {query.want[:3]}")
    setup_compiles = list(compiles)
    counters.update(
        compile_s=sum(s for s, _ in setup_compiles),
        xla_compiles=len(setup_compiles),
        cache_hits=cache["hits"], cache_misses=cache["misses"])
    say(phase="warmup", seconds=warm_s, xla_compiles=len(setup_compiles),
        compile_s=counters["compile_s"], persistent_hits=cache["hits"],
        persistent_misses=cache["misses"],
        slowest=[[round(s, 3), n]
                 for s, n in sorted(setup_compiles, reverse=True)[:8]])

    # ---- the window: closed loop, one client, queries in turn
    results, trace_dir = [], None
    load = host.load_per_core()
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    at_start = host.snapshot()
    if trace:
        n = 1 if max(warm_s.values()) > LONG_COLLECT_S else 3
        trace_dir = os.path.join(root, ".bench_data", "traces", cell.name)
        results.extend(_traced_collects(
            trace_dir, [queries[i % len(queries)] for i in range(n)]))
    n_traced = len(results)
    while time.perf_counter() - t_window < seconds:
        results.append(_timed_collect(queries[len(results) % len(queries)]))
    t_last_end = time.perf_counter()
    window = host.moved(at_start, host.snapshot())
    window_compiles = len(compiles) - len(setup_compiles)

    # ---- the check, outside the window
    failed = sum(1 for query, _, rows in results
                 if rows is None or not rows_match(rows, query.want))
    peak_after = _peak_bytes(devices)
    correct = failed == 0
    if cell.chips > 1 and any(peak_after):  # XLA:CPU reports no memory
        held = all(a > b for a, b in zip(peak_after, peak_before))
        say(phase="mesh", peak_bytes_before=peak_before,
            peak_bytes_after=peak_after, every_device_held_shards=held)
        correct = correct and held
    plain = [secs for _, secs, rows in results[n_traced:] if rows is not None]
    done = [query for query, _, rows in results if rows is not None]
    counters.update(
        host_load=load,
        host_cpu_s=window["cpu_s"] / len(results),
        host_disk_read_bytes=None if window["disk_read_bytes"] is None
        else window["disk_read_bytes"] / len(results),
        window_compiles=window_compiles,
        collect_seconds=plain,
        traced_collect_seconds=[s for _, s, _ in results[:n_traced]],
        peak_hbm_bytes=max(peak_after),
        scanned_bytes=statistics.mean(q.scan["bytes"] for q in queries),
        chips=len(devices))
    e2e = {
        "query_s": statistics.median(plain) if plain else None,
        "rows_per_s": (sum(q.scan["rows"] for q in done)
                       / (t_last_end - t_window)) if done else None,
        "setup_s": setup_s,
    }
    say(phase="window", collects=len(results), traced=n_traced,
        failed=failed, window_compiles=window_compiles,
        seconds=[round(s, 4) for _, s, _ in results], **e2e,
        # what the host was doing (layer_metrics/host_*.py read the
        # same counters on a traced run), so that a window that reads
        # apart from its neighbours says why
        collect_iqr_s=load_module(
            root, "layer_metrics", "collect_iqr_s").quartile_distance(plain),
        host_cpu_s=counters["host_cpu_s"],
        host_disk_read_bytes=counters["host_disk_read_bytes"],
        host_load=load, cores=host.cores(),
        memory_pool=host.memory_pool())

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(peak_after)}
    out = {"correct": correct, "attempted": len(results), "failed": failed}
    if trace:
        reduced = _reduce(trace_dir, cell, len(devices), keep_trace_dir)
        facts = {"trace": reduced, "counters": counters,
                 "peaks": _peaks(root, devices[0])}
        values = {}
        for m in cell.per_layer:
            value = load_module(root, "layer_metrics", m["name"]).read(facts)
            if value is not None:
                values[m["name"]] = {"value": value, "unit": m["unit"]}
        out["metrics"] = values
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["device"] = device
        out["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                            "idle_gaps": reduced["idle_gaps"][:10]}
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        _require(all(e2e.get(n) is not None for n in units),
                 f"a metric of {sorted(units)} has no value: {e2e}")
        out["metrics"] = {n: {"value": e2e[n], "unit": u}
                          for n, u in units.items()}
        out["device"] = device
    return out


def _timed_collect(query: _Query, fatal: bool = False) -> tuple:
    """(query, seconds on the host clock, rows or None if it raised).
    The rows are on the host when collect returns, so the device has
    finished."""
    t0 = time.perf_counter()
    try:
        rows = query.df.collect()
    except Exception as e:  # in the window a failure is counted, not fatal
        if fatal:
            raise
        say(phase="collect_failed", query=query.name, error=repr(e)[:500])
        rows = None
    return query, time.perf_counter() - t0, rows


def _traced_collects(trace_dir: str, todo) -> list:
    """Collects inside one ``jax.profiler`` trace, each under a
    ``bench.collect`` annotation."""
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # annotations, not every frame
    options.host_tracer_level = 2
    results = []
    t0 = time.perf_counter()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        for query in todo:
            with jax.profiler.TraceAnnotation(reduce_trace.COLLECT):
                results.append(_timed_collect(query))
    finally:
        t1 = time.perf_counter()
        jax.profiler.stop_trace()
    say(phase="traced", collects=len(todo), traced_s=t1 - t0,
        stop_trace_s=time.perf_counter() - t1)
    return results


def _reduce(trace_dir: str, cell: Cell, n_devices: int,
            keep_trace_dir: str | None) -> dict:
    """Reduce the raw trace in this process and delete it: only numbers
    leave the run."""
    t0 = time.perf_counter()
    path = reduce_trace.find_xplane(trace_dir)
    size = os.path.getsize(path)
    planes = reduce_trace.load(path)
    reduced = reduce_trace.reduce(planes, n_devices)
    say(phase="trace", xplane_bytes=size,
        reduce_s=time.perf_counter() - t0, planes=reduced["plane_names"],
        per_device=reduced["devices"], per_collect=reduced["collects"])
    if keep_trace_dir:
        os.makedirs(keep_trace_dir, exist_ok=True)
        with open(os.path.join(keep_trace_dir, cell.name + ".json"),
                  "w") as f:
            json.dump(planes, f)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return reduced


def _peaks(root: str, device) -> dict | None:
    """The published peaks of the device's kind; a chip that is not in
    the table is an error, not a default.  A rehearsal's XLA:CPU has
    none, and its run reports no roofline share."""
    if device.platform != "tpu":
        return None
    with open(os.path.join(root, "benchmark", "peaks.json")) as f:
        table = json.load(f)
    _require(device.device_kind in table,
             f"no peaks for device kind {device.device_kind!r} in peaks.json")
    return table[device.device_kind]
