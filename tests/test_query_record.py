"""The engine's own spans and counters, and the per-query record made of
them (obs/registry.py ``span`` / ``recent_queries``, exec/lifecycle.py
``open_record`` / ``seal_record``): deterministic on XLA:CPU — counts
and byte sums are asserted exactly, seconds only for order and sign.
"""
import ast
import math
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import TpuSession
from spark_rapids_tpu import types as T
from spark_rapids_tpu.exec import compile_cache as cc
from spark_rapids_tpu.exec.aggregate import HashAggregateExec
from spark_rapids_tpu.expr.aggregates import CountStar, Sum
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.obs.registry import (RECENT_QUERIES, MetricsRegistry,
                                           get_registry)

PKG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "spark_rapids_tpu")
CONF = {"spark.rapids.sql.resultCache.enabled": "false",
        "spark.rapids.sql.test.enabled": "true"}
FACT_FILES = 3
DAY0, DAYS = 2450000, 2000


@pytest.fixture(scope="module")
def tables(tmp_path_factory):
    root = tmp_path_factory.mktemp("query_record")
    rng = np.random.default_rng(7)
    os.makedirs(root / "fact")
    os.makedirs(root / "dim")
    for i in range(FACT_FILES):
        n = 4000
        pq.write_table(pa.table({
            "k": rng.integers(0, 50, n), "g": rng.integers(0, 5, n),
            "v": rng.random(n),
            "d": rng.integers(DAY0, DAY0 + DAYS, n).astype(np.int32)}),
            str(root / "fact" / f"part-{i}.parquet"))
    pq.write_table(pa.table({"k": np.arange(50), "w": rng.random(50)}),
                   str(root / "dim" / "part-0.parquet"))
    # a date dimension (consecutive day numbers, a month of 31) and a
    # dimension keyed by ids a hundred million apart
    os.makedirs(root / "days")
    os.makedirs(root / "sparse")
    day = np.arange(DAY0, DAY0 + DAYS, dtype=np.int32)
    pq.write_table(pa.table({"d": day, "month": (day - DAY0) // 31}),
                   str(root / "days" / "part-0.parquet"))
    pq.write_table(pa.table({"k": np.arange(50) * 10**8,
                             "w": rng.random(50)}),
                   str(root / "sparse" / "part-0.parquet"))
    return str(root)


@pytest.fixture(scope="module")
def session():
    s = TpuSession(dict(CONF))
    yield s
    s.shutdown(drain=False)


@pytest.fixture(scope="module")
def query(session, tables):
    """scan -> filter -> join -> aggregate, collected once (compiles)."""
    fact = session.read_parquet(os.path.join(tables, "fact"))
    dim = session.read_parquet(os.path.join(tables, "dim"))
    df = fact.where(col("v") > lit(0.1)).join(dim, on="k") \
        .group_by("g").agg(Sum(col("w")).alias("sw"),
                           CountStar().alias("n"))
    assert len(df.collect()) == 5
    return df


def _collect(df) -> dict:
    """One collect; its record."""
    df.collect()
    return get_registry().recent_queries(1)[0]


def _launches(counters: dict) -> int:
    return sum(v for k, v in counters.items()
               if k.startswith("program.") and k.endswith(".launches"))


# ---------------------------------------------------------------- record

def test_record_holds_the_query_phases(query):
    rec = _collect(query)
    c = rec["counters"]
    assert rec["state"] == "FINISHED"
    assert len(rec["query_id"]) == 16
    assert rec["start_unix_s"] <= rec["end_unix_s"]
    assert set(rec["spill"]) == {"bytes_spilled_to_host",
                                 "bytes_spilled_to_disk", "device_spills"}
    for phase in ("query", "query.plan", "query.execute"):
        assert c[f"span.{phase}.count"] == 1, phase
    assert c["span.query.fetch.count"] >= 1     # one per result batch
    # query holds plan and execute; execute holds the result's fetches
    assert c["span.query.seconds"] >= (c["span.query.plan.seconds"]
                                       + c["span.query.execute.seconds"])
    assert c["span.query.execute.seconds"] >= c["span.query.fetch.seconds"]
    # the seams of this plan: scan workers, both chunked fetches
    for span in ("decode@ParquetScanExec", "stage@ParquetScanExec",
                 "starved@ParquetScanExec",
                 "fetch@JoinExec", "fetch@HashAggregateExec"):
        assert c[f"span.{span}.count"] >= 1, span
    assert c["queries_executed"] == 1
    assert c["sync_wait_s"] > 0 and c["scan_backpressure_s"] >= 0
    # the host side of the scan and of every launch, counted from inside
    assert c["scan.pipelines"] == FACT_FILES + 1    # a partition a file
    assert 0 < c["scan.first_batch_s"] <= c["scan.wait_s"]
    assert c["h2d_put_s"] > 0
    assert c["program.batch_unpack.dispatch_s"] > 0
    assert "compile_count" not in c     # a warm collect compiles nothing


def test_h2d_counters_equal_the_buffers_put(query, monkeypatch):
    import jax
    put = []
    real = jax.device_put

    def spy(x, *a, **k):
        if isinstance(x, np.ndarray):     # _PackBuilder.build's buffers
            put.append(x.nbytes)
        return real(x, *a, **k)
    monkeypatch.setattr(jax, "device_put", spy)
    c = _collect(query)["counters"]
    assert put and c["h2d_calls"] == len(put)
    assert c["h2d_bytes"] == sum(put)
    # what was put is exactly what the unpack program was handed
    assert c["program.batch_unpack.arg_bytes"] == sum(put)
    assert c["program.batch_unpack.launches"] == FACT_FILES + 1


def test_counts_repeat_exactly_over_collects(query):
    a, b = _collect(query)["counters"], _collect(query)["counters"]
    assert _launches(a) == _launches(b) > 0
    for key in ("h2d_calls", "h2d_bytes", "d2h_calls", "d2h_bytes"):
        assert a[key] == b[key] > 0, key
    programs = {k for k in a if k.startswith("program.")}
    assert programs == {k for k in b if k.startswith("program.")}
    # (a launch's host seconds are seconds: they repeat in name only)
    assert all(a[k] == b[k] for k in programs
               if not k.endswith(".dispatch_s"))


@pytest.fixture(scope="module")
def small_batch_session():
    """Coalescing off in effect: a 64-row batch is over the target, so
    the aggregate sees the scan's batches one by one."""
    s = TpuSession(dict(CONF, **{"spark.rapids.sql.batchSizeBytes": "512"}))
    yield s
    s.shutdown(drain=False)


@pytest.mark.parametrize("batches", [1, 5, 8, 20])
def test_aggregate_fetches_once_per_sync_chunk(small_batch_session, batches):
    rows = 64
    n = rows * batches
    df = small_batch_session.from_pydict(
        {"g": [i % 7 for i in range(n)], "v": [1.0] * n},
        T.Schema([T.StructField("g", T.IntegerType()),
                  T.StructField("v", T.DoubleType())]),
        rows_per_batch=rows).group_by("g").agg(Sum(col("v")).alias("s"))
    df.collect()                        # compiles
    c = _collect(df)["counters"]
    flushes = math.ceil(batches / HashAggregateExec._SYNC_CHUNK)
    merges = 1 if batches > 1 else 0    # the merged buffer's row count
    assert c["span.fetch@HashAggregateExec.count"] == flushes + merges
    assert c["program.agg_update.launches"] == batches
    assert c["d2h_calls"] >= flushes + merges + 1   # + the result


def test_a_failed_query_leaves_its_record(tables):
    s = TpuSession(dict(CONF, **{
        "spark.rapids.test.faults": "memory.oom:oom,times=0"}))
    try:
        df = s.read_parquet(os.path.join(tables, "fact")) \
            .group_by("g").agg(Sum(col("v")).alias("s"))
        before = len(get_registry().recent_queries())
        with pytest.raises(Exception) as err:
            df.collect()
        assert not getattr(err.value, "terminal", False)
        rec = get_registry().recent_queries(1)[0]
        assert rec["state"] == "FAILED"
        assert rec["counters"]["span.query.count"] == 1
        assert rec["counters"]["faults.injected.memory.oom"] >= 1
        assert len(get_registry().recent_queries()) == \
            min(before + 1, RECENT_QUERIES)
    finally:
        s.shutdown(drain=False)


def test_record_walks_no_pull_source(query):
    walked = []
    get_registry().register_source("probe_source",
                                   lambda: walked.append(1) or {})
    try:
        _collect(query)
    finally:
        get_registry().unregister_source("probe_source")
    assert not walked


def test_queries_endpoint_serves_the_finished_records(session, query):
    from spark_rapids_tpu.obs.http import ObsHttpServer
    rec = _collect(query)
    server = ObsHttpServer(session, 0)
    try:
        body = server.queries()
    finally:
        server.close()
    assert body["count"] == 0 and body["active"] == {}
    assert body["finished"][-1]["query_id"] == rec["query_id"]
    assert body["finished"][-1]["counters"] == rec["counters"]


def test_ring_is_bounded():
    reg = MetricsRegistry()
    for i in range(RECENT_QUERIES + 6):
        reg.note_query({"query_id": str(i)})
    kept = reg.recent_queries()
    # a 48 s benchmark window of a half-second query, traced collects
    # first, must still be whole when the benchmark reads it
    assert RECENT_QUERIES >= 256 and len(kept) == RECENT_QUERIES
    last = RECENT_QUERIES + 5
    assert kept[0]["query_id"] == "6" and kept[-1]["query_id"] == str(last)
    assert [r["query_id"] for r in reg.recent_queries(3)] == \
        [str(last - 2), str(last - 1), str(last)]
    assert len(reg.recent_queries(10 * RECENT_QUERIES)) == RECENT_QUERIES


def test_span_counts_and_times_once():
    reg = MetricsRegistry()
    with reg.span("outer", query_id="q") as outer:
        with reg.span("inner@SomeExec"):
            pass
    c = reg.counters()
    assert c["span.outer.count"] == c["span.inner@SomeExec.count"] == 1
    assert c["span.outer.seconds"] == outer.seconds
    assert outer.seconds >= c["span.inner@SomeExec.seconds"] >= 0
    assert reg.counters_since(c) == {}
    with pytest.raises(KeyError):
        with reg.span("outer"):
            raise KeyError("propagates, and is still counted")
    assert reg.counters_since(c)["span.outer.count"] == 1


# -------------------------------------------------------------- programs

def test_program_bytes_are_the_avals_sizes():
    import jax.numpy as jnp
    prog = cc.shared_jit(cc.fragment_key("test_bytes", "record"),
                         lambda x, y, scale: (x * scale + y, y[:16]),
                         name="test_record_bytes")
    x = jnp.ones((128, 4), jnp.float32)         # 2048 bytes
    y = jnp.ones((128, 4), jnp.int8)            # 512 bytes
    before = get_registry().counters()
    for _ in range(3):
        prog(x, y, 2)       # the python scalar is a leaf with no bytes
    moved = get_registry().counters_since(before)
    assert moved["program.test_record_bytes.launches"] == 3
    assert moved["program.test_record_bytes.arg_bytes"] == 3 * (2048 + 512)
    assert moved["program.test_record_bytes.result_bytes"] == \
        3 * (2048 * 4 // 4 + 16 * 4)            # f32[128,4] + int8[16,4]
    assert moved["compile_count"] == 1
    assert moved["span.program.compile@test_record_bytes.count"] == 1
    assert prog.signature_count() == 1 and prog.name == "test_record_bytes"
    # the trace's module name is the program's name
    assert "module @jit_test_record_bytes" in prog.fn.lower(x, y, 2).as_text()


def _jit_sites(path: str) -> list:
    """``(name or None, line)`` of every program a module hands to
    SharedJit: ``instrument(fn, NAME)``, ``shared_jit(key, fn,
    name=NAME)``, ``guarded_jit(NAME, ...)``, kernels' ``_shared(NAME,
    fn)``.  A conditional between two literals yields both.  A mesh
    terminal declares its bare program's name as the class attribute
    ``_program_name = NAME``, which the one mesh launcher's site reads:
    the declaration is the site."""
    def literals(node):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return [node.value]
        if isinstance(node, ast.IfExp):
            return literals(node.body) + literals(node.orelse)
        if isinstance(node, ast.Attribute) and node.attr == "_program_name":
            return []           # found where the terminal declares it
        return [None]
    with open(path) as f:
        tree = ast.parse(f.read())
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "_program_name"
                for t in node.targets):
            found.extend((n, node.lineno) for n in literals(node.value))
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        called = fn.attr if isinstance(fn, ast.Attribute) else \
            fn.id if isinstance(fn, ast.Name) else ""
        if called in ("instrument", "SharedJit") and len(node.args) == 2:
            arg = node.args[1]
        elif called in ("guarded_jit", "_guarded_jit", "_shared") \
                and node.args:
            arg = node.args[0]
        elif called == "shared_jit":
            arg = next((k.value for k in node.keywords
                        if k.arg == "name"), ast.Constant(None))
        elif called in ("instrument", "guarded_jit", "_guarded_jit"):
            arg = ast.Constant(None)    # a site that names nothing
        else:
            continue
        found.extend((name, node.lineno) for name in literals(arg))
    return found


JIT_MODULES = sorted(
    os.path.relpath(os.path.join(d, f), PKG)
    for sub in ("exec", "ops", "columnar", "parallel", "memory")
    for d, _, files in os.walk(os.path.join(PKG, sub))
    for f in files if f.endswith(".py") and f != "compile_cache.py"
    and _jit_sites(os.path.join(d, f)))


def test_every_jit_module_is_found():
    assert len(JIT_MODULES) >= 15   # parallel/'s test-only programs went (PR 45)
    for must in ("exec/aggregate.py", "exec/joins.py", "exec/mesh_exec.py",
                 "exec/mesh_region.py", "columnar/batch.py", "ops/kernels.py",
                 "memory/retry.py"):
        assert must in JIT_MODULES


@pytest.mark.parametrize("module", JIT_MODULES)
def test_program_names_are_unique(module):
    """One name per jit site, over every module: a second ``prog`` or an
    unnamed site fails here, by name."""
    mine = _jit_sites(os.path.join(PKG, module))
    # kernels' _shared(name, fn) passes its name on to instrument()
    mine = [(n, ln) for n, ln in mine
            if not (module == "ops/kernels.py" and n is None)]
    names = [n for n, _ in mine]
    assert all(names), f"{module}: a jit site without a literal name " \
        f"at lines {[ln for n, ln in mine if not n]}"
    assert len(set(names)) == len(names), f"{module}: {sorted(names)}"
    others = {n for m in JIT_MODULES if m != module
              for n, _ in _jit_sites(os.path.join(PKG, m))}
    assert not set(names) & others, sorted(set(names) & others)
    # a bare python name would come back: the name says whose it is
    assert not set(names) & {"update", "merge", "final", "filt", "prog",
                             "body", "unpack", "gather", "step", "fn"}


def test_live_programs_carry_their_names(query):
    """Over compile_cache._ALL_SHARED after real queries: the name is
    on the traced function (the trace's ``jit_<name>``), and one python
    function is never behind two names."""
    _collect(query)
    by_code = {}
    live = list(cc._ALL_SHARED)
    assert {"batch_unpack", "filter_batch", "join_build_prep",
            "join_build_table", "join_probe_fast", "join_probe_direct",
            "join_gather", "agg_update", "agg_merge",
            "agg_final"} <= {sj.name for sj in live}
    for sj in live:
        inner = getattr(sj.fn, "__wrapped__", None)
        if inner is None:
            continue
        assert inner.__name__ == sj.name
        code = getattr(inner, "__code__", None)
        if code is not None and PKG in code.co_filename:
            by_code.setdefault((code.co_filename, code.co_firstlineno),
                               set()).add(sj.name)
    assert by_code and all(len(v) == 1 for v in by_code.values()), \
        {k: v for k, v in by_code.items() if len(v) > 1}


# ------------------------------------------------------ the join's probe

@pytest.mark.parametrize("shape", ["dense_dim", "sparse_dim", "q6_shaped"])
def test_probe_counters_say_which_probe_ran(session, tables, shape):
    """``join.probe.direct`` / ``join.probe.search``: one per stream
    batch probed, counted where the host picks the program, so they
    equal that program's launches; ``join.build.table_entries``: the
    direct-address tables built, and one more fetch a fast build."""
    fact = session.read_parquet(os.path.join(tables, "fact"))
    if shape == "q6_shaped":
        # the fact stream through two surrogate-key joins, the inner one
        # against a date dimension filtered to one month (it keeps its
        # capacity: 2048 slots, 31 keys), into a small group-by
        days = session.read_parquet(os.path.join(tables, "days")) \
            .where(col("month") == lit(7)).select("d")
        dim = session.read_parquet(os.path.join(tables, "dim"))
        df = fact.join(days, on="d").join(dim, on="k")
        tables_built, entries = 2, 32 + 64
    else:
        dim = session.read_parquet(os.path.join(
            tables, "dim" if shape == "dense_dim" else "sparse"))
        df = fact.join(dim, on="k")
        tables_built, entries = (1, 64) if shape == "dense_dim" else (0, 0)
    df = df.group_by("g").agg(CountStar().alias("n"))
    assert len(df.collect()) == 5
    c = _collect(df)["counters"]
    ran, idle = ("search", "direct") if shape == "sparse_dim" \
        else ("direct", "search")
    program = {"direct": "join_probe_direct", "search": "join_probe_fast"}
    assert c[f"join.probe.{ran}"] == c[f"program.{program[ran]}.launches"]
    assert c[f"join.probe.{ran}"] >= FACT_FILES
    assert f"join.probe.{idle}" not in c
    assert f"program.{program[idle]}.launches" not in c
    assert c.get("join.build.table_entries", 0) == entries
    assert c.get("program.join_build_table.launches", 0) == tables_built
    builds = 2 if shape == "q6_shaped" else 1
    assert c["program.join_build_prep.launches"] == builds
    # one fetch a build (its key range), and at most one a probe (the
    # totals, chunked by partition)
    assert builds < c["span.fetch@JoinExec.count"] \
        <= builds + c[f"join.probe.{ran}"]


# ------------------------------------------------------- the front-pack

def test_front_pack_counters_follow_the_plan(session, tables):
    """scan (3 files) -> Filter -> Filter -> coalesce -> sort: the two
    filters run as one fused stage a file and compact once
    (``fused.filters_merged``: one filter beyond the first a launch);
    the coalesce places the three packed batches in one launch of
    ``concat_batches`` (``concat.launches`` / ``concat.batches_in``)."""
    df = session.read_parquet(os.path.join(tables, "fact")) \
        .where(col("v") > lit(0.1)).where(col("d") > lit(DAY0 + 100)) \
        .order_by("v")
    ov, meta = df._overridden(quiet=True)

    def descs(node):
        yield node.node_desc()
        for c in node.children:
            yield from descs(c)
    plan = list(descs(meta.exec_node))
    assert any(d.startswith("FusedStageExec[2 ops: FilterExec") and
               d.count("FilterExec") == 2 for d in plan), plan
    assert any(d.startswith("CoalesceBatchesExec") for d in plan), plan
    rows = df.collect()
    assert rows and rows == sorted(rows, key=lambda r: r[2])
    c = _collect(df)["counters"]
    assert c["program.fused_stage_body.launches"] == FACT_FILES
    assert c["fused.filters_merged"] == FACT_FILES
    assert c["concat.launches"] == c["program.concat_batches.launches"] == 1
    assert c["concat.batches_in"] == FACT_FILES


@pytest.mark.parametrize("shape", ["one_filter", "two_filters", "no_filter",
                                   "join_condition"])
def test_compaction_counters_follow_the_plan(session, tables, shape):
    """``compact.launches`` / ``compact.slots``: one count, and the
    batch's capacity, a dispatch of a program whose body compacts -- a
    filter a file, a fused two-filter stage a file (one compaction, not
    two), a join's residual condition a probe chunk; a plan that filters
    nothing moves neither.  What the host already holds: a collect with
    them fetches no more than the same plan's programs ask for."""
    fact = session.read_parquet(os.path.join(tables, "fact"))
    dim = session.read_parquet(os.path.join(tables, "dim"))
    rows = {
        "one_filter": lambda: fact.where(col("v") > lit(0.25)),
        "two_filters": lambda: fact.where(col("v") > lit(0.25))
        .where(col("d") > lit(DAY0 + 7)),
        "no_filter": lambda: fact,
        "join_condition": lambda: fact.join(
            dim, on="k", condition=col("v") > col("w")),
    }[shape]()
    df = rows.group_by("g").agg(CountStar().alias("n"))
    assert len(df.collect()) == 5
    c = _collect(df)["counters"]
    program = {"one_filter": "filter_batch", "two_filters": "fused_stage_body",
               "join_condition": "join_post_filter"}.get(shape)
    if program is None:
        assert "compact.launches" not in c and "compact.slots" not in c
        return
    launches = c[f"program.{program}.launches"]
    assert c["compact.launches"] == launches
    assert launches == FACT_FILES or shape == "join_condition"
    # 4,000 rows a file in a 4,096-slot batch
    assert c["compact.slots"] >= 4096 * launches
    assert c["compact.slots"] % 4096 == 0
    # no fetch beyond the aggregate's and the join's own
    fetches = sum(v for k, v in c.items()
                  if k.startswith("span.fetch@") and k.endswith(".count"))
    assert c["d2h_calls"] == fetches + c["span.query.fetch.count"]
    assert not any(k.startswith("span.fetch@Filter")
                   or k.startswith("span.fetch@FusedStage") for k in c)


# ------------------------------------------------- the scan's pipeline

NAP = 0.2       # seconds the slow side of a pipeline sleeps a batch
BATCHES = 3 * 4     # FACT_FILES files of 4000 rows, 1000 rows a batch


class _Pipeline:
    """One partition of the fact table through ``partition_iter`` (reader
    pool or lazy reader -> the ``scan-prefetch`` thread -> this thread),
    with a reader and a consumer that can be slowed; ``drain`` returns
    the counters that moved and the staging threads' lives."""

    def __init__(self, tables, monkeypatch, reader_type="MULTITHREADED"):
        import threading
        import time
        from spark_rapids_tpu.conf import TpuConf
        from spark_rapids_tpu.io.scan import ParquetScanExec
        self.conf = TpuConf(dict(
            CONF, **{"spark.rapids.sql.reader.batchRows": "1000",
                     "spark.rapids.sql.format.parquet.reader.type":
                         reader_type}))
        self.scan = ParquetScanExec(os.path.join(tables, "fact"),
                                    partitions=1)
        self.reader_nap = 0.0
        self.lives = lives = []
        real = ParquetScanExec._read_file

        def slow_read(scan, path, batch_rows=1 << 16):
            for rb in real(scan, path, batch_rows):
                time.sleep(self.reader_nap)
                yield rb
        monkeypatch.setattr(ParquetScanExec, "_read_file", slow_read)

        class Timed(threading.Thread):
            def run(self):
                t0 = time.perf_counter()
                try:
                    super().run()
                finally:
                    if self.name == "scan-prefetch":
                        lives.append(time.perf_counter() - t0)
        monkeypatch.setattr(threading, "Thread", Timed)
        self.drain()    # batch_unpack compiles for this batch shape

    def drain(self, reader_nap=0.0, consumer_nap=0.0, consumers=1):
        import time
        from spark_rapids_tpu.exec.core import ExecCtx
        self.reader_nap = reader_nap
        del self.lives[:]
        before = get_registry().counters()
        with ExecCtx(backend="device", conf=self.conf) as ctx:
            for _ in range(consumers):
                n = 0
                for _b in self.scan.partition_iter(ctx, 0):
                    n += 1
                    time.sleep(consumer_nap)
                assert n == BATCHES
        return get_registry().counters_since(before), list(self.lives)


@pytest.mark.parametrize("reader_type,naps", [
    # the pool decodes the three files at once: the staging thread is
    # blocked on the first future for one file's four naps
    ("MULTITHREADED", 4),
    # a lazy reader decodes in the staging thread: every batch's nap
    ("PERFILE", BATCHES)])
def test_a_slow_reader_starves_the_staging_thread(tables, monkeypatch,
                                                  reader_type, naps):
    c, _ = _Pipeline(tables, monkeypatch, reader_type).drain(reader_nap=NAP)
    assert c["span.starved@ParquetScanExec.seconds"] >= 0.8 * naps * NAP
    # one wait a batch and one for the end of the input
    assert c["span.starved@ParquetScanExec.count"] == BATCHES + 1
    assert c["scan_backpressure_s"] < NAP
    # the consumer waits for what the staging thread waits for
    assert c["scan.wait_s"] >= 0.8 * naps * NAP
    if reader_type == "PERFILE":    # decode@ nests inside starved@
        assert c["span.starved@ParquetScanExec.seconds"] >= \
            c["span.decode@ParquetScanExec.seconds"] >= naps * NAP


def test_a_slow_consumer_backs_the_staging_thread_up(tables, monkeypatch):
    c, _ = _Pipeline(tables, monkeypatch).drain(consumer_nap=NAP)
    # the queue holds two: all but the first few puts wait a nap each
    assert c["scan_backpressure_s"] >= 0.8 * (BATCHES - 3) * NAP
    assert c["span.starved@ParquetScanExec.seconds"] < NAP
    assert c["scan.wait_s"] < NAP
    assert c["span.stage@ParquetScanExec.count"] == BATCHES


@pytest.mark.parametrize("slow", ["reader", "consumer"])
def test_the_staging_threads_life_is_its_three_counters(tables, monkeypatch,
                                                        slow):
    c, lives = _Pipeline(tables, monkeypatch).drain(**{f"{slow}_nap": NAP})
    (life,) = lives     # the worker, from its start to DONE handed over
    told = (c["span.starved@ParquetScanExec.seconds"]
            + c["span.stage@ParquetScanExec.seconds"]
            + c["scan_backpressure_s"])
    assert life > 3 * NAP
    assert 0.9 * life <= told <= life


@pytest.mark.parametrize("shared", [False, True])
def test_first_batch_counts_once_per_staging(tables, monkeypatch, shared):
    pipe = _Pipeline(tables, monkeypatch)
    pipe.scan.share_output, pipe.scan.share_consumers = shared, 2
    c, lives = pipe.drain(reader_nap=NAP / 4, consumers=2)
    # a shared scan stages its partition once, for both consumers
    stagings = 1 if shared else 2
    assert c["scan.pipelines"] == len(lives) == stagings
    assert c["span.stage@ParquetScanExec.count"] == stagings * BATCHES
    # the first get waits for a file's decode; the later gets wait too
    assert 0.9 * stagings * NAP <= c["scan.first_batch_s"] \
        < c["scan.wait_s"]


def test_h2d_put_seconds_are_the_seconds_inside_the_puts(query, monkeypatch):
    import time
    import jax
    spent = []
    real = jax.device_put

    def spy(x, *a, **k):
        t0 = time.perf_counter()
        try:
            time.sleep(0.002)
            return real(x, *a, **k)
        finally:
            if isinstance(x, np.ndarray):   # _PackBuilder.build's buffers
                spent.append(time.perf_counter() - t0)
    monkeypatch.setattr(jax, "device_put", spy)
    c = _collect(query)["counters"]
    assert len(spent) == c["h2d_calls"]
    assert sum(spent) <= c["h2d_put_s"] <= 1.5 * sum(spent) + 0.05
    # the put is a share of the staging that holds it
    assert c["h2d_put_s"] < c["span.stage@ParquetScanExec.seconds"]


def test_dispatch_seconds_move_once_per_warm_launch():
    import time
    import jax.numpy as jnp
    prog = cc.shared_jit(cc.fragment_key("test_dispatch", "record"),
                         lambda x: x + 1, name="test_record_dispatch")
    key = "program.test_record_dispatch."
    x = jnp.ones((8,), jnp.float32)
    before = get_registry().counters()
    prog(x)     # traces and compiles: program.compile@ holds its seconds
    moved = get_registry().counters_since(before)
    assert moved[key + "launches"] == 1 and key + "dispatch_s" not in moved
    assert moved["span.program.compile@test_record_dispatch.count"] == 1
    real = prog.fn

    def slow(*a, **k):
        time.sleep(0.01)
        return real(*a, **k)
    prog.fn = slow
    before = get_registry().counters()
    for _ in range(3):
        prog(x)
    moved = get_registry().counters_since(before)
    assert moved[key + "launches"] == 3 and "compile_count" not in moved
    assert 0.03 <= moved[key + "dispatch_s"] < 0.03 + 0.1

    def failing(*a, **k):
        raise RuntimeError("a launch that raises is still a launch")
    prog.fn = failing
    before = get_registry().counters()
    with pytest.raises(RuntimeError):
        prog(x)
    moved = get_registry().counters_since(before)
    assert moved[key + "launches"] == 1 and moved[key + "dispatch_s"] >= 0


# ----------------------------------------------------------------- trace

def test_worker_and_fetch_spans_share_the_collects_clock(query, tmp_path):
    """A CPU ``jax.profiler`` trace of one collect, loaded as the
    benchmark loads it: the scan workers' and the fetches' spans are
    there (their names pass its ``…Exec`` filter) and lie inside the
    ``bench.collect`` annotation — one clock."""
    import jax
    from benchmark.harness import reduce_trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(reduce_trace.COLLECT):
            rec = _collect(query)
    finally:
        jax.profiler.stop_trace()
    path = reduce_trace.find_xplane(str(tmp_path))
    planes = reduce_trace.load(path)
    events = [e for ln in planes["host"] for e in ln["events"]]
    (_, c0, cdur), = [e for e in events if e[0] == reduce_trace.COLLECT]
    by_name = {}
    for name, start, dur in events:
        by_name.setdefault(name, []).append((start, start + dur))
    counters = rec["counters"]
    for span in ("decode@ParquetScanExec", "stage@ParquetScanExec",
                 "starved@ParquetScanExec",
                 "fetch@JoinExec", "fetch@HashAggregateExec"):
        assert len(by_name[span]) == counters[f"span.{span}.count"], span
        for start, end in by_name[span]:
            assert c0 <= start <= end <= c0 + cdur, span
    # the worker threads' spans are on other lines than the puller's

    def lines_of(name):
        return {i for i, ln in enumerate(planes["host"])
                if any(e[0] == name for e in ln["events"])}
    lines = lines_of("stage@ParquetScanExec")
    pullers = lines_of(reduce_trace.COLLECT)
    assert lines and not lines & pullers
    # the staging thread's wait for its input opens on that same thread
    # (a scan-prefetch thread a partition), beside its stage@ spans
    assert lines_of("starved@ParquetScanExec") == lines
    # one file a partition: the reader is pulled lazily, so every decode
    # lies inside a starved@ span of its thread
    for ln in (planes["host"][i] for i in lines):
        starved = [(s0, s0 + d) for n, s0, d in ln["events"]
                   if n == "starved@ParquetScanExec"]
        for n, s0, d in ln["events"]:
            if n == "decode@ParquetScanExec":
                assert any(a <= s0 and s0 + d <= b for a, b in starved)
    # the query's own spans carry its id (the benchmark's load drops
    # them: it keeps bench.collect and …Exec names only)
    data = jax.profiler.ProfileData.from_file(path)
    ids = {}
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("query", "query.plan", "query.execute",
                              "query.fetch"):
                    stats = dict(e.stats)
                    # (an id of digits alone would be read as a number)
                    ids.setdefault(e.name, set()).add(
                        str(stats["query_id"]))
                    if e.name != "query":
                        assert stats["parent"] == "query"
    assert set(ids) == {"query", "query.plan", "query.execute",
                        "query.fetch"}
    if not rec["query_id"].replace("e", "").isdigit():
        assert all(v == {rec["query_id"]} for v in ids.values()), ids
    assert not any(n.startswith("query") for n in by_name)
