"""Pod-scale execution: mesh regions, distributed sort, and the
multichip equality gate.

The tentpole contract (ISSUE 7): a plan under
``spark.rapids.tpu.mesh.deviceCount=N`` runs whole pipelines
shard-resident — contiguous scan->filter->project->aggregate/exchange/
sort pipelines compile into ONE per-device ``shard_map`` program
(exec/mesh_region.py), batches cross the device boundary only at region
edges, and results are EXACTLY the single-device plan's.  These tests
pin that contract on the virtual 8-device CPU mesh:

* TPC-H q1/q3/q6/q12/q13/q18 mesh-vs-single equality at deviceCount
  2/4/8 (q13 string-heavy, q18 high-skew);
* q3 under deviceCount=8 moves ZERO ``mesh_gather_fallbacks`` between
  region members and renders MeshRegionExec + counters in EXPLAIN
  ANALYZE;
* compile-cache fragment keys are mesh-shape-aware (mesh-2 and mesh-4
  never share an executable; single-chip keys carry no mesh part);
* a killed mesh slice mid-query recovers to exact rows with exactly
  one stage recompute;
* a bounded [P, C] send buffer that overflows under key skew degrades
  into a counted retry at worst-case capacity — never a truncation.

ISSUE 14 widens the contract: joins are region INTERIOR nodes (q12's
join runs inside one per-device program, replicated-vs-partitioned
counted, zero gather fallbacks), window functions lower to
MeshWindowExec (partitioned and global-ordered, exact at 2/4/8
devices), a slice lost inside a join- or window-bearing region still
recovers to exact rows with one recompute, warm reruns of the new
node kinds compile nothing, and exchange-fed regions chain —
downstream regions consume upstream shards in place
(``mesh_region_chains``); an upstream that degraded to host partitions
is drained by partition instead.

ISSUE 45: a bare terminal and a region launch through ONE
``MeshLauncher`` (exec/mesh_exec.py) — each bare terminal recovers a
lost slice of its own, and the six program names stay apart.
"""
import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.aggregates import CountStar, Sum
from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.session import TpuSession

MESH8 = {"spark.rapids.tpu.mesh.deviceCount": 8}

SCHEMA = T.Schema([
    T.StructField("k", T.IntegerType(), True),
    T.StructField("g", T.StringType(), True),
    T.StructField("v", T.LongType(), True),
    T.StructField("f", T.DoubleType(), True),
])


def _data(rng, n=400, nkeys=17):
    return {
        "k": rng.integers(0, nkeys, n).astype(np.int32),
        "g": np.array([f"g{int(x) % 5}" for x in rng.integers(0, 50, n)],
                      dtype=object),
        "v": rng.integers(-1000, 1000, n).astype(np.int64),
        "f": rng.normal(size=n),
    }


def _classes(node):
    out = [type(node).__name__]
    for c in node.children:
        out.extend(_classes(c))
    return out


def _walk(node):
    yield node
    for c in node.children:
        yield from _walk(c)


def _executed_plan(df):
    """The REALIZED exec tree (post fusion + region formation) — the
    meta-tree explain() renders the pre-region operators."""
    ov, meta = df._overridden(quiet=True)
    return meta.exec_node


# ---------------------------------------------------------------------------
# TPC-H mesh-vs-single equality gate
# ---------------------------------------------------------------------------

# q1 (wide agg) and q13 (string-heavy) take minutes under the 8-way
# virtual mesh on one physical CPU, so like the 2/4-device rungs they
# are marked slow; the 8-device q3/q6/q12/q18 rungs are the tier-1 gate
GATE_QUERIES = (
    pytest.param("q1", marks=pytest.mark.slow),
    "q3", "q6", "q12",
    pytest.param("q13", marks=pytest.mark.slow),
    "q18",
)
DEVICE_COUNTS = (
    pytest.param(2, marks=pytest.mark.slow),
    pytest.param(4, marks=pytest.mark.slow),
    8,
)


@pytest.fixture(scope="module")
def tpch_dir(tmp_path_factory):
    from spark_rapids_tpu.bench.tpch_gen import generate_tpch
    d = str(tmp_path_factory.mktemp("tpch_mesh") / "sf001")
    generate_tpch(d, sf=0.01)
    return d


@pytest.fixture(scope="module")
def single_device_rows(tpch_dir):
    from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
    cache = {}

    def get(query):
        if query not in cache:
            s = TpuSession({})
            cache[query] = build_tpch_query(query, s, tpch_dir).collect()
        return cache[query]
    return get


@pytest.mark.parametrize("devices", DEVICE_COUNTS)
@pytest.mark.parametrize("query", GATE_QUERIES)
def test_tpch_mesh_matches_single_device(tpch_dir, single_device_rows,
                                         query, devices):
    from spark_rapids_tpu.bench.runner import _rows_match
    from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
    s = TpuSession({"spark.rapids.tpu.mesh.deviceCount": devices})
    got = build_tpch_query(query, s, tpch_dir).collect()
    want = single_device_rows(query)
    assert len(got) == len(want), (query, devices, len(got), len(want))
    assert _rows_match(got, want, strict=True), (query, devices)


def test_q3_mesh8_zero_gather_fallbacks(tpch_dir, single_device_rows):
    """Acceptance: full q3 under deviceCount=8 stays region-resident —
    no batch is gathered to the default device between region members,
    verified through the counter EXPLAIN ANALYZE surfaces."""
    from spark_rapids_tpu.bench.runner import _rows_match
    from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
    from spark_rapids_tpu.exec.core import (ExecCtx, _rows_from_host,
                                            device_to_host)
    from spark_rapids_tpu.plan.overrides import explain_analyze
    s = TpuSession(MESH8)
    df = build_tpch_query("q3", s, tpch_dir)
    b0 = get_registry().snapshot()
    plan = _executed_plan(df)
    assert get_registry().delta(b0)["counters"].get("mesh_regions", 0) >= 1
    assert "MeshRegionExec" in _classes(plan)
    b1 = get_registry().snapshot()
    with ExecCtx(backend="device", conf=s.conf) as ctx:
        rows = []
        for b in plan.execute(ctx):
            rows.extend(_rows_from_host(device_to_host(b)))
        analyzed = explain_analyze(plan, ctx)
    delta = get_registry().delta(b1)["counters"]
    assert delta.get("mesh_gather_fallbacks", 0) == 0, delta
    assert "MeshRegionExec" in analyzed
    assert "counters:" in analyzed and "mesh_regions" in analyzed
    # the join strategy decision renders next to the a2a bytes
    assert "mesh_join_replicated" in analyzed or \
        "mesh_join_partitioned" in analyzed, analyzed
    assert _rows_match(rows, single_device_rows("q3"), strict=True)


# ---------------------------------------------------------------------------
# region formation + plan shape
# ---------------------------------------------------------------------------

def test_region_absorbs_filter_into_aggregate(rng):
    s = TpuSession(MESH8)
    df = s.from_pydict(_data(rng), SCHEMA, partitions=4) \
        .where(col("v") > 0).group_by("k") \
        .agg(Sum(col("v")).alias("sv"), CountStar().alias("n"))
    plan = _executed_plan(df)
    names = _classes(plan)
    assert "MeshRegionExec" in names
    # the filter is a region member, not a tree node above the scan
    assert "FilterExec" not in names
    plain = TpuSession({}).from_pydict(_data(rng), SCHEMA, partitions=4)
    region = next(n for n in _walk(plan)
                  if type(n).__name__ == "MeshRegionExec")
    assert "MeshAggregateExec" in region.node_desc()


def test_region_two_filter_stage_matches_single_chip(rng):
    """A region splices ``fused.stage_body``: its two filters and their
    masks, one compaction a shard.  The second condition is NULL
    (``v = 0``) or true on rows the first drops; rows equal the
    single-chip plan's."""
    data = _data(rng)

    def q(s):
        return s.from_pydict(data, SCHEMA, partitions=4) \
            .where(col("v") > 100).where(col("f") / col("v") > -0.001) \
            .group_by("k").agg(Sum(col("v")).alias("sv"),
                               CountStar().alias("n"))
    mesh = q(TpuSession(MESH8))
    region = next(n for n in _walk(_executed_plan(mesh))
                  if type(n).__name__ == "MeshRegionExec")
    assert region.node_desc().count("FilterExec") == 2, region.node_desc()
    before = get_registry().snapshot()
    got = sorted(mesh.collect())
    moved = get_registry().delta(before)["counters"]
    assert moved.get("fused.filters_merged", 0) >= 1, moved
    want = sorted(q(TpuSession({})).collect())
    assert got == want and 0 < len(got) <= 17


@pytest.mark.parametrize("threshold", [990, -990])
def test_region_compaction_keeps_its_cond_under_shard_map(
        rng, monkeypatch, threshold):
    """The compaction's ``cond`` (which bucket to move) inside a region's
    per-device program: every shard picks its branch from its own count.
    The floor is lowered so 2^6-slot shards carry the ``cond``; a filter
    that keeps a few rows (the small bucket) and one that keeps nearly
    all (the full move) both equal the single-chip plan, and the lowered
    ``shard_map`` body still holds the ``case``."""
    import jax

    from spark_rapids_tpu.host.batch import HostBatch
    from spark_rapids_tpu.ops import kernels as dk
    from spark_rapids_tpu.parallel import mesh as pm
    monkeypatch.setattr(dk, "COND_MIN_CAPACITY", 16)
    data = _data(rng)

    def q(s):
        # literals no other test's region shares: a program traced under
        # this floor must not answer for another's fragment key
        return s.from_pydict(data, SCHEMA, partitions=4) \
            .where(col("v") > threshold).where(col("f") > -7.25) \
            .group_by("k").agg(Sum(col("v")).alias("sv"),
                               CountStar().alias("n"))
    before = get_registry().snapshot()
    got = sorted(q(TpuSession(MESH8)).collect())
    moved = get_registry().delta(before)["counters"]
    assert moved.get("compact.launches", 0) >= 1, moved
    assert moved["compact.slots"] >= 8 * 16 * moved["compact.launches"]
    want = sorted(q(TpuSession({})).collect())
    assert got == want and len(got) > 0

    mesh = pm.make_mesh(8)
    shards = [HostBatch.from_pydict(
        {k: v[i::8] for k, v in data.items()}, SCHEMA).to_device(capacity=64)
        for i in range(8)]
    stacked = pm.shard_batches(shards, mesh)

    def region(st):
        b = pm.local_view(st)
        c = b.columns[2]
        return pm.restack(dk.compact(b, c.validity & (c.data > threshold)))
    prog = jax.jit(pm.shard_map(region, mesh=mesh, in_specs=pm.stacked_spec(),
                                out_specs=pm.stacked_spec()))
    text = prog.lower(stacked).as_text()
    assert text.count('"stablehlo.case"') + text.count('"stablehlo.if"') == 1
    assert text.count('"stablehlo.scatter"') == 2
    for got_b, b in zip(pm.split_shards(prog(stacked)), shards):
        c = b.columns[2]
        alone = dk.compact(b, c.validity & (c.data > threshold))
        for x, y in zip(jax.tree.leaves(got_b), jax.tree.leaves(alone)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_regions_disabled_keeps_island_shape_and_rows(rng):
    data = _data(rng)
    son = TpuSession(MESH8)
    soff = TpuSession({**MESH8,
                       "spark.rapids.tpu.mesh.regions.enabled": "false"})

    def q(s):
        return s.from_pydict(data, SCHEMA, partitions=4) \
            .where(col("v") > 0).group_by("k") \
            .agg(Sum(col("v")).alias("sv"))

    assert "MeshRegionExec" in _classes(_executed_plan(q(son)))
    off_names = _classes(_executed_plan(q(soff)))
    assert "MeshRegionExec" not in off_names
    assert sorted(q(son).collect()) == sorted(q(soff).collect())


def test_mesh_devicecount_zero_restores_single_chip_plan(rng):
    data = _data(rng)
    plain = TpuSession({}).from_pydict(data, SCHEMA, partitions=4) \
        .where(col("v") > 0).group_by("k").agg(Sum(col("v")).alias("sv")) \
        .order_by(("sv", False)).limit(5)
    zero = TpuSession({"spark.rapids.tpu.mesh.deviceCount": 0}) \
        .from_pydict(data, SCHEMA, partitions=4) \
        .where(col("v") > 0).group_by("k").agg(Sum(col("v")).alias("sv")) \
        .order_by(("sv", False)).limit(5)
    assert _classes(_executed_plan(plain)) == _classes(_executed_plan(zero))
    assert plain.collect() == zero.collect()


# ---------------------------------------------------------------------------
# mesh sort / TopN
# ---------------------------------------------------------------------------

def test_mesh_sort_total_order_matches_plain(rng):
    data = _data(rng)
    sm, sp = TpuSession(MESH8), TpuSession({})
    dfm = sm.from_pydict(data, SCHEMA, partitions=4) \
        .order_by("v", ("k", False), "g")
    dfp = sp.from_pydict(data, SCHEMA, partitions=4) \
        .order_by("v", ("k", False), "g")
    assert "MeshSortExec" in dfm.explain()
    got, want = dfm.collect(), dfp.collect()
    assert got == want and len(got) == 400


@pytest.mark.parametrize("limit", [5, 64, 10_000])
def test_mesh_topn_matches_plain(rng, limit):
    """limit < rows, limit spanning shard boundaries, limit > rows."""
    data = _data(rng)
    sm, sp = TpuSession(MESH8), TpuSession({})

    def q(s):
        return s.from_pydict(data, SCHEMA, partitions=4) \
            .where(col("v") > 0) \
            .order_by(("v", False), "k").limit(limit)

    assert "MeshSortExec" in q(sm).explain()
    assert q(sm).collect() == q(sp).collect()


def test_mesh_topn_output_no_gather(rng):
    """TopN keeps its rows on device 0: serving the limit moves nothing
    across devices."""
    data = _data(rng)
    s = TpuSession(MESH8)
    df = s.from_pydict(data, SCHEMA, partitions=4) \
        .order_by(("v", False)).limit(7)
    b0 = get_registry().snapshot()
    rows = df.collect()
    delta = get_registry().delta(b0)["counters"]
    assert len(rows) == 7
    assert delta.get("mesh_gather_fallbacks", 0) == 0


# ---------------------------------------------------------------------------
# compile cache: mesh-shape-aware fragment keys
# ---------------------------------------------------------------------------

def test_mesh_key_part_distinguishes_mesh_shapes():
    from spark_rapids_tpu.exec import compile_cache as cc
    from spark_rapids_tpu.parallel.mesh import make_mesh
    assert cc.fragment_key("frag", ("x",), cc.mesh_key_part(2, "data")) != \
        cc.fragment_key("frag", ("x",), cc.mesh_key_part(4, "data"))
    m2, m4 = make_mesh(2), make_mesh(4)
    assert cc.mesh_key_part(m2, "data") != cc.mesh_key_part(m4, "data")
    assert cc.fragment_key("frag", cc.mesh_key_part(m2, "data")) != \
        cc.fragment_key("frag", cc.mesh_key_part(m4, "data"))


def test_single_chip_fragment_keys_carry_no_mesh_part(rng):
    """The mesh key component lives ONLY in mesh program keys:
    single-chip fused-stage keys are byte-stable across sessions and
    mesh confs, so this PR cannot fragment the existing cache."""
    from spark_rapids_tpu.exec.fused import FusedStageExec

    def stage(s):
        df = s.from_pydict(_data(rng), SCHEMA, partitions=2) \
            .where(col("v") > 0).select(col("k"), (col("v") * 2).alias("w"))
        plan = _executed_plan(df)
        return next(n for n in _walk(plan)
                    if isinstance(n, FusedStageExec))

    k_plain = stage(TpuSession({}))._stage_key(True)
    k_plain2 = stage(TpuSession({}))._stage_key(True)
    assert k_plain == k_plain2


def test_region_programs_cached_per_mesh_shape(rng):
    """Warm rerun at a FIXED mesh shape compiles nothing; changing the
    mesh shape misses (mesh-2 and mesh-4 must not share executables)."""
    data = _data(rng)

    def run(n):
        s = TpuSession({"spark.rapids.tpu.mesh.deviceCount": n})
        return s.from_pydict(data, SCHEMA, partitions=4) \
            .where(col("v") > 0).group_by("k") \
            .agg(Sum(col("v")).alias("sv")).collect()

    base = run(4)                       # cold at mesh-4
    b0 = get_registry().snapshot()
    assert run(4) == base               # warm at mesh-4
    warm = get_registry().delta(b0)["counters"]
    assert warm.get("compile_count", 0) == 0, warm
    b1 = get_registry().snapshot()
    assert sorted(run(2)) == sorted(base)   # mesh-2: new mesh shape
    cold2 = get_registry().delta(b1)["counters"]
    assert cold2.get("compile_count", 0) >= 1, cold2


# ---------------------------------------------------------------------------
# chaos: lost mesh slice under a region
# ---------------------------------------------------------------------------

def test_region_slice_lost_recovers_exact_once(rng):
    """Kill a mesh slice mid-query: rows must be EXACTLY the plain
    plan's, recovered through exactly one region-level recompute."""
    from spark_rapids_tpu.exec.core import (ExecCtx, _rows_from_host,
                                            device_to_host)
    data = _data(rng)
    s = TpuSession({**MESH8,
                    "spark.rapids.test.faults":
                    "mesh.slice.lost:lost,op=meshregion,times=1"})
    df = s.from_pydict(data, SCHEMA, partitions=4) \
        .where(col("v") > 0).group_by("k") \
        .agg(Sum(col("v")).alias("sv"), CountStar().alias("n"))
    plan = _executed_plan(df)
    assert "MeshRegionExec" in _classes(plan)
    with ExecCtx(backend="device", conf=s.conf) as ctx:
        rows = []
        for b in plan.execute(ctx):
            rows.extend(_rows_from_host(device_to_host(b)))
        metrics = dict(ctx.catalog.metrics)
    assert metrics.get("stage_recomputes", 0) == 1, metrics
    assert metrics.get("recovery_wall_s", 0) > 0
    plain = TpuSession({}).from_pydict(data, SCHEMA, partitions=4) \
        .where(col("v") > 0).group_by("k") \
        .agg(Sum(col("v")).alias("sv"), CountStar().alias("n"))
    assert sorted(rows) == sorted(plain.collect())


# ---------------------------------------------------------------------------
# bounded [P, C] send buffers: overflow degrades, never truncates
# ---------------------------------------------------------------------------

def _skewed(n=300):
    # every row hashes to ONE destination: the worst case for a
    # bounded per-target send buffer
    return {
        "k": np.full(n, 7, np.int32),
        "g": np.array([f"s{i % 3}" for i in range(n)], dtype=object),
        "v": np.arange(n, dtype=np.int64),
        "f": np.linspace(0.0, 1.0, n),
    }


def test_send_capacity_overflow_degrades_into_retry():
    data = _skewed()
    s = TpuSession({**MESH8,
                    "spark.rapids.tpu.mesh.exchange.sendCapacityRows": 4})
    df = s.from_pydict(data, SCHEMA, partitions=4).repartition(8, "k")
    b0 = get_registry().snapshot()
    rows = df.collect()
    delta = get_registry().delta(b0)["counters"]
    assert delta.get("mesh_send_overflows", 0) >= 1, delta
    plain = TpuSession({}).from_pydict(data, SCHEMA, partitions=4).collect()
    assert sorted(rows) == sorted(plain)


def test_send_capacity_default_never_overflows(rng):
    s = TpuSession(MESH8)
    df = s.from_pydict(_skewed(), SCHEMA, partitions=4).repartition(8, "k")
    b0 = get_registry().snapshot()
    rows = df.collect()
    delta = get_registry().delta(b0)["counters"]
    assert delta.get("mesh_send_overflows", 0) == 0, delta
    assert len(rows) == 300


# ---------------------------------------------------------------------------
# split_shards: region boundary batches stay device-resident
# ---------------------------------------------------------------------------

def test_split_shards_keeps_batches_on_their_devices():
    import jax
    from spark_rapids_tpu.exec.basic import LocalScanExec
    from spark_rapids_tpu.exec.core import ExecCtx, device_to_host
    from spark_rapids_tpu.exec.mesh_exec import place_shards
    from spark_rapids_tpu.parallel.mesh import (make_mesh, shard_batches,
                                                split_shards)
    data = {"k": list(range(64)), "s": [f"v{i % 7}" for i in range(64)]}
    schema = T.Schema([T.StructField("k", T.LongType()),
                       T.StructField("s", T.StringType())])
    scan = LocalScanExec.from_pydict(data, schema, 1, 16)
    with ExecCtx(backend="device") as ctx:
        batches = list(scan.partition_iter(ctx, 0))
    mesh = make_mesh(4)
    shards = place_shards(batches, 4)
    out = split_shards(shard_batches(shards, mesh))
    assert len(out) == 4
    devs = []
    for b in out:
        assert b.columns[0].data.committed
        (d,) = b.columns[0].data.devices()
        devs.append(d)
    assert devs == list(mesh.devices.flat)
    got = []
    for b in out:
        hb = device_to_host(b)
        got.extend(zip(*[c.to_list() for c in hb.columns]))
    assert sorted(got) == sorted(zip(data["k"], data["s"]))


# ---------------------------------------------------------------------------
# joins absorbed into regions (ISSUE 14)
# ---------------------------------------------------------------------------

def test_q12_join_runs_inside_region(tpch_dir, single_device_rows):
    """q12's join is a region MEMBER: one per-device program carries
    scan->filter->join->agg, the replicated-vs-partitioned decision is
    counted, and not one batch falls back to a host gather."""
    from spark_rapids_tpu.bench.runner import _rows_match
    from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
    # the join counters fire on EXECUTION: a result-cache hit from an
    # earlier test's identical q12 run would skip the collect entirely
    s = TpuSession({**MESH8, "spark.rapids.sql.resultCache.enabled": False})
    df = build_tpch_query("q12", s, tpch_dir)
    plan = _executed_plan(df)
    regions = [n for n in _walk(plan)
               if type(n).__name__ == "MeshRegionExec"]
    assert any("MeshJoinExec" in r.node_desc() for r in regions), \
        [r.node_desc() for r in regions]
    b0 = get_registry().snapshot()
    rows = df.collect()
    delta = get_registry().delta(b0)["counters"]
    assert delta.get("mesh_gather_fallbacks", 0) == 0, delta
    assert delta.get("mesh_join_replicated", 0) + \
        delta.get("mesh_join_partitioned", 0) >= 1, delta
    assert _rows_match(rows, single_device_rows("q12"), strict=True)


def test_join_region_slice_lost_recovers_exact_once(tpch_dir,
                                                    single_device_rows):
    """Kill a mesh slice inside q12's join-bearing region: exact rows
    through exactly one region-level recompute."""
    from spark_rapids_tpu.bench.runner import _rows_match
    from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
    from spark_rapids_tpu.exec.core import (ExecCtx, _rows_from_host,
                                            device_to_host)
    s = TpuSession({**MESH8,
                    "spark.rapids.test.faults":
                    "mesh.slice.lost:lost,op=meshregion,times=1"})
    df = build_tpch_query("q12", s, tpch_dir)
    plan = _executed_plan(df)
    assert any("MeshJoinExec" in n.node_desc() for n in _walk(plan)
               if type(n).__name__ == "MeshRegionExec")
    with ExecCtx(backend="device", conf=s.conf) as ctx:
        rows = []
        for b in plan.execute(ctx):
            rows.extend(_rows_from_host(device_to_host(b)))
        metrics = dict(ctx.catalog.metrics)
    assert metrics.get("stage_recomputes", 0) == 1, metrics
    assert _rows_match(rows, single_device_rows("q12"), strict=True)


# ---------------------------------------------------------------------------
# a region's join probes a prepared build (ISSUE 44): the body is the
# one-chip executor's probe selection and gather plan, under shard_map
# ---------------------------------------------------------------------------

MESH4 = {"spark.rapids.tpu.mesh.deviceCount": 4,
         "spark.rapids.sql.resultCache.enabled": False}
PROBES = ("direct", "search", "packed", "sorted")
_JL = T.Schema([T.StructField("a", T.IntegerType(), True),
                T.StructField("b", T.IntegerType(), True),
                T.StructField("s", T.StringType(), True),
                T.StructField("lv", T.LongType(), True)])
_JR = T.Schema([T.StructField("ra", T.IntegerType(), True),
                T.StructField("rb", T.IntegerType(), True),
                T.StructField("rs", T.StringType(), True),
                T.StructField("rv", T.LongType(), True)])


def _join_sides(probe: str, n: int = 330, fan: int = 1):
    """Stream and build of one case: NULL keys on both sides, stream keys
    with no match, build keys held twice (``fan`` times more), and keys
    ``direct_table_size`` calls dense or not.  ``sorted`` joins on the
    strings, ``packed`` on both integers, the others on ``a``."""
    rng = np.random.default_rng(7)
    step = 1_000_003 if probe == "search" else 1   # 40M wide: no table
    a = rng.integers(0, 40, n)
    b = rng.integers(0, 6, n)
    def nullable(x, every):
        return [None if i % every == 0 else int(v) for i, v in enumerate(x)]
    left = {"a": nullable(a * step, 13), "b": nullable(b, 17),
            "s": [None if i % 19 == 0 else f"k{v:02d}"
                  for i, v in enumerate(a)],
            "lv": list(range(n))}
    ra = np.concatenate([np.arange(30)] + [np.array([5, 5, 11])] * fan)
    rb = np.arange(len(ra)) % 6
    right = {"ra": nullable(ra * step, 9), "rb": nullable(rb, 8),
             "rs": [None if i % 7 == 0 else f"k{v:02d}"
                    for i, v in enumerate(ra)],
             "rv": [10 * i for i in range(len(ra))]}
    on = {"sorted": [("s", "rs")],
          "packed": [("a", "ra"), ("b", "rb")]}.get(probe, [("a", "ra")])
    return left, right, on


def _region_join(conf, probe, jt, **sides):
    left, right, on = _join_sides(probe, **sides)
    s = TpuSession(conf)
    # three stream batches over four devices: one shard is empty
    return s.from_pydict(left, _JL, partitions=3) \
        .join(s.from_pydict(right, _JR), on, jt).repartition(4, col("lv"))


def _join_region_of(df):
    regions = [n for n in _walk(_executed_plan(df))
               if type(n).__name__ == "MeshRegionExec"
               and "MeshJoinExec" in n.node_desc()]
    assert len(regions) == 1, _executed_plan(df).node_desc()
    return regions[0]


def _moved_by(df):
    b0 = get_registry().snapshot()
    rows = df.collect()
    return rows, get_registry().delta(b0)["counters"]


@pytest.mark.parametrize("probe", PROBES)
@pytest.mark.parametrize("jt", ["inner", "left", "semi", "anti"])
def test_region_join_matches_one_chip_join(jt, probe):
    """A replicated region join on 4 devices hands on the single-device
    plan's rows, and the counter names the probe its body ran against
    the build prepared outside the program: by address for dense keys
    (one key or two packed), searched for sparse ones, the sort path for
    a string."""
    df = _region_join(MESH4, probe, jt)
    _join_region_of(df)
    rows, moved = _moved_by(df)
    want = _region_join({}, probe, jt).collect()
    assert len(rows) == len(want) > 0
    assert sorted(rows, key=repr) == sorted(want, key=repr)
    ran = {"packed": "direct"}.get(probe, probe)
    assert {k: v for k, v in moved.items()
            if k.startswith("mesh_join.probe.")} == \
        {f"mesh_join.probe.{ran}": 1}, moved
    assert moved.get("mesh_join_replicated") == 1
    assert "mesh_join_partitioned" not in moved
    assert moved.get("join.keys.packed", 0) == (probe == "packed")
    # prepared once, by the one-chip executor's code; nothing fell back
    assert moved.get("program.join_build_prep.launches", 0) == \
        (probe != "sorted")
    assert moved.get("mesh_gather_fallbacks", 0) == 0


@pytest.mark.parametrize("jt", ["inner", "left", "semi", "anti"])
def test_partitioned_region_join_matches_one_chip_join(jt):
    """``buildThresholdBytes=0``: both sides exchange inside the program
    and the co-partitioned shards join by the sort path; no build is
    prepared outside it, so no probe kind is counted."""
    conf = {**MESH4, "spark.rapids.tpu.mesh.join.buildThresholdBytes": 0}
    df = _region_join(conf, "direct", jt)
    _join_region_of(df)
    rows, moved = _moved_by(df)
    want = _region_join({}, "direct", jt).collect()
    assert sorted(rows, key=repr) == sorted(want, key=repr) and rows
    assert moved.get("mesh_join_partitioned") == 1
    assert not [k for k in moved if k.startswith("mesh_join.probe.")]
    assert "program.join_build_prep.launches" not in moved


def test_region_join_past_its_capacity_retries_once_and_truncates_nothing():
    """Every matched stream row comes out many times over, so a shard's
    total passes the static ``out_cap`` the region guessed: ONE retry at
    the measured capacity, every row there, the probe counted once."""
    df = _region_join(MESH4, "direct", "inner", fan=60)
    rows, moved = _moved_by(df)
    want = _region_join({}, "direct", "inner", fan=60).collect()
    assert len(rows) == len(want) > 4 * 128     # more than the shards hold
    assert sorted(rows, key=repr) == sorted(want, key=repr)
    assert moved.get("mesh_join_capacity_retries") == 1, moved
    assert moved.get("program.mesh_region_join.launches") == 2
    assert moved.get("mesh_join.probe.direct") == 1


def test_tpcds_q6_region_probes_four_prepared_builds(tmp_path):
    """The benchmark's q6 (benchmark/queries/tpcds_q6.py) at SF0.1 (at
    SF0.01 no state reaches its ten customers) on 4 virtual devices: ONE
    region holds its four surrogate-key joins, each probes a
    direct-address table made outside the program, none takes the sort
    path, and the rows are the single-device plan's."""
    from benchmark.harness.cell import ROOT, load_module
    q6 = load_module(ROOT, "queries", "tpcds_q6")
    data_dir = str(tmp_path / "sf01")
    load_module(ROOT, "datagen", "tpcds").generate(
        data_dir, 0.1, 2**31 + 44, sorted(q6.TABLES))
    df = q6.build(TpuSession(MESH4), data_dir)
    region = _join_region_of(df)
    assert region.node_desc().count("MeshJoinExec[inner") == 4
    rows, moved = _moved_by(df)
    assert moved.get("mesh_join.probe.direct") == 4, moved
    assert "mesh_join.probe.sorted" not in moved
    assert "mesh_join.probe.search" not in moved
    # the fifth is the island join of item to its category averages (on
    # a string, outside the region: its stream is no fact table)
    assert moved.get("mesh_join_replicated") == 5
    assert moved.get("program.mesh_region_join.launches") == 1
    want = q6.build(TpuSession(
        {"spark.rapids.sql.resultCache.enabled": False}), data_dir).collect()
    # states with equal counts come out in either order
    assert sorted(rows, key=repr) == sorted(want, key=repr) and rows


# ---------------------------------------------------------------------------
# windows under the mesh (MeshWindowExec)
# ---------------------------------------------------------------------------

def _window_df(s, data, global_order=False):
    from spark_rapids_tpu.expr.window import (RowNumber, WindowExpression,
                                              WindowSpec)
    spec = WindowSpec((), ((col("v"), True), (col("k"), True))) \
        if global_order else \
        WindowSpec((col("k"),), ((col("v"), True),))
    return s.from_pydict(data, SCHEMA, partitions=4) \
        .select(col("k"), col("v"),
                WindowExpression(Sum(col("v")), spec).alias("rs"),
                WindowExpression(RowNumber(), spec).alias("rn"))


@pytest.mark.parametrize("devices", DEVICE_COUNTS)
@pytest.mark.parametrize("global_order", (False, True),
                         ids=("partitioned", "global_order"))
def test_mesh_window_matches_single_device(rng, devices, global_order):
    data = _data(rng)
    sm = TpuSession({"spark.rapids.tpu.mesh.deviceCount": devices})
    dfm = _window_df(sm, data, global_order)
    plan = _executed_plan(dfm)
    assert any("MeshWindowExec" in n.node_desc() for n in _walk(plan)), \
        _classes(plan)
    got = sorted(dfm.collect())
    want = sorted(_window_df(TpuSession({}), data, global_order).collect())
    assert got == want


def test_window_region_slice_lost_recovers_exact_once(rng):
    """A filter absorbed under a MeshWindowExec terminal forms a region;
    a slice lost inside it recovers to exact rows with one recompute."""
    from spark_rapids_tpu.exec.core import (ExecCtx, _rows_from_host,
                                            device_to_host)
    data = _data(rng)
    s = TpuSession({**MESH8,
                    "spark.rapids.test.faults":
                    "mesh.slice.lost:lost,op=meshregion,times=1"})
    plan = _executed_plan(_windowed_filter(s, data))
    region = next(n for n in _walk(plan)
                  if type(n).__name__ == "MeshRegionExec")
    assert "MeshWindowExec" in region.node_desc()
    with ExecCtx(backend="device", conf=s.conf) as ctx:
        rows = []
        for b in plan.execute(ctx):
            rows.extend(_rows_from_host(device_to_host(b)))
        metrics = dict(ctx.catalog.metrics)
    assert metrics.get("stage_recomputes", 0) == 1, metrics
    want = _windowed_filter(TpuSession({}), data).collect()
    assert sorted(rows) == sorted(want)


def _windowed_filter(s, data):
    from spark_rapids_tpu.expr.window import (WindowExpression, WindowSpec)
    spec = WindowSpec((col("k"),), ((col("v"), True),))
    return s.from_pydict(data, SCHEMA, partitions=4) \
        .where(col("v") > 0) \
        .select(col("k"), col("v"),
                WindowExpression(Sum(col("v")), spec).alias("rs"))


@pytest.mark.slow
def test_join_and_window_regions_warm_rerun_compile_nothing(rng, tpch_dir):
    """Second run of a join-bearing region program and a mesh window at
    the SAME mesh shape compiles nothing: the new node kinds key into
    the process-wide compile cache like every other mesh program."""
    from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
    data = _data(rng)

    def run_both():
        s = TpuSession(MESH8)
        jrows = build_tpch_query("q12", s, tpch_dir).collect()
        wrows = _window_df(TpuSession(MESH8), data).collect()
        return sorted(jrows), sorted(wrows)

    cold = run_both()
    b0 = get_registry().snapshot()
    warm = run_both()
    moved = get_registry().delta(b0)["counters"]
    assert warm == cold
    assert moved.get("compile_count", 0) == 0, \
        f"warm join/window rerun compiled: {moved}"


# ---------------------------------------------------------------------------
# region chaining: exchange-fed regions consume shards in place
# ---------------------------------------------------------------------------

def _chained_q(s, data, lead_filter=True):
    t = s.from_pydict(data, SCHEMA, partitions=4)
    if lead_filter:         # absorbed: the exchange is a region's terminal
        t = t.where(col("v") != 0)
    return t.repartition(8, col("k")) \
        .where(col("v") > 0) \
        .group_by("k").agg(Sum(col("v")).alias("sv"))


def test_region_chaining_consumes_shards_in_place(rng):
    """An exchange-terminal region feeding a downstream region hands
    its per-device shards over without a host gather/re-shard hop."""
    data = _data(rng)
    s = TpuSession(MESH8)
    df = _chained_q(s, data)
    plan = _executed_plan(df)
    assert _classes(plan).count("MeshRegionExec") == 2, _classes(plan)
    b0 = get_registry().snapshot()
    rows = df.collect()
    delta = get_registry().delta(b0)["counters"]
    assert delta.get("mesh_region_chains", 0) >= 1, delta
    assert delta.get("mesh_gather_fallbacks", 0) == 0, delta
    want = _chained_q(TpuSession({}), data).collect()
    assert sorted(rows) == sorted(want)


# ---------------------------------------------------------------------------
# one launcher (ISSUE 45): bare terminals and regions, each under its name
# ---------------------------------------------------------------------------

# fault op -> (plan node, program name) of each bare terminal
_BARE = {"meshagg": ("MeshAggregateExec", "mesh_aggregate"),
         "meshex": ("MeshExchangeExec", "mesh_exchange"),
         "meshsort": ("MeshSortExec", "mesh_sort"),
         "meshwindow": ("MeshWindowExec", "mesh_window")}


def _bare_q(conf, data, op):
    """A query whose plan holds the terminal ``op`` names BARE: nothing
    absorbed under it, so it launches (and is lost) under its own name.
    Shapes this file has compiled by now: ``meshagg`` is the island plan
    of ``test_regions_disabled_keeps_island_shape_and_rows``; ``meshex``
    is the chained query without its leading filter, a bare exchange
    feeding the region of the filter and the aggregate."""
    if op == "meshagg" and conf:
        conf = {**conf, "spark.rapids.tpu.mesh.regions.enabled": "false"}
    s = TpuSession(conf)
    t = s.from_pydict(data, SCHEMA, partitions=4)
    if op == "meshagg":
        return t.where(col("v") > 0).group_by("k") \
            .agg(Sum(col("v")).alias("sv"))
    if op == "meshex":
        return _chained_q(s, data, lead_filter=False)
    if op == "meshsort":
        return t.order_by("v", ("k", False), "g")
    return _window_df(s, data)


@pytest.mark.parametrize("op", list(_BARE))
def test_standalone_mesh_terminal_slice_lost_recovers(rng, op):
    """No region around it: a bare terminal's own fallback path recovers
    one injected loss of its slice with exact rows, its mesh program
    never launched.  The lost bare exchange degrades to host partitions,
    which the region above it drains instead of chaining."""
    data = _data(rng)
    df = _bare_q({**MESH8, "spark.rapids.test.faults":
                  f"mesh.slice.lost:lost,op={op},times=1"}, data, op)
    node, program = _BARE[op]
    assert node in _classes(_executed_plan(df))
    b0 = get_registry().snapshot()
    got = df.collect()
    delta = get_registry().delta(b0)["counters"]
    want = _bare_q({}, data, op).collect()
    if op != "meshsort":            # a sort's order is part of its answer
        got, want = sorted(got), sorted(want)
    assert got == want
    assert delta.get(f"program.{program}.launches", 0) == 0, delta
    if op == "meshex":
        assert delta.get("mesh_region_chains", 0) == 0, delta
        assert delta.get("program.mesh_region_chain.launches", 0) == 1


def test_one_launcher_keeps_the_six_program_names_apart(rng):
    """A bare terminal of each kind, a region without a join and a region
    with one all launch through the one MeshLauncher, each counted under
    its own program: ``program.<name>.launches`` moves under the six
    names and under no other ``mesh_*`` one (no plan here serves an
    exchange's partitions, which ``mesh_exchange_pick`` would count)."""
    data = _data(rng)
    left, right, on = _join_sides("direct")
    s4 = TpuSession(MESH4)
    joined = s4.from_pydict(left, _JL, partitions=3) \
        .join(s4.from_pydict(right, _JR), on, "inner") \
        .group_by("b").agg(Sum(col("rv")).alias("srv"))
    assert "MeshJoinExec" in _join_region_of(joined).node_desc()
    dfs = [_bare_q(MESH8, data, op) for op in _BARE] + [joined]
    b0 = get_registry().snapshot()
    for df in dfs:
        assert df.collect()
    delta = get_registry().delta(b0)["counters"]
    launched = {k.split(".")[1]: v for k, v in delta.items()
                if k.startswith("program.mesh_") and k.endswith(".launches")}
    assert launched == {
        "mesh_aggregate": 1, "mesh_exchange": 1, "mesh_sort": 1,
        "mesh_window": 1, "mesh_region_chain": 1, "mesh_region_join": 1}, \
        launched
    assert delta.get("mesh_region_chains", 0) == 1, delta
