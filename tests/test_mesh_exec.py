"""Engine-level mesh shuffle/aggregation: DataFrame -> shard_map plan.

VERDICT r1 item 2: the mesh all-to-all data plane must be reachable from
the planner/exec layer.  These tests run real DataFrame queries with
``spark.rapids.tpu.mesh.deviceCount=8`` on the virtual 8-device CPU mesh
and compare against the host oracle (the reference's differential
pattern, asserts.py:290).
"""
import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.aggregates import Average, CountStar, Max, Min, Sum
from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.session import TpuSession

MESH_CONF = {"spark.rapids.tpu.mesh.deviceCount": 8}

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


def _sessions():
    return (TpuSession(MESH_CONF), TpuSession({}))


def _sorted_rows(rows):
    return sorted(rows, key=lambda r: tuple(
        (x is None, str(x)) for x in r))


def _assert_same(mesh_df, plain_df, approx_cols=()):
    got = _sorted_rows(mesh_df.collect())
    want = _sorted_rows(plain_df.collect())
    assert len(got) == len(want), (len(got), len(want))
    for rg, rw in zip(got, want):
        assert len(rg) == len(rw)
        for i, (a, b) in enumerate(zip(rg, rw)):
            if i in approx_cols and a is not None and b is not None:
                assert abs(a - b) <= 1e-9 * max(1.0, abs(b)), (rg, rw)
            else:
                assert a == b, (rg, rw)


def test_mesh_groupby_plan_uses_mesh_exec(rng):
    s, _ = _sessions()
    df = s.from_pydict(_data(rng), SCHEMA, partitions=4) \
        .group_by("k").agg(Sum(col("v")).alias("sv"))
    assert "MeshAggregateExec" in df.explain()


def test_mesh_groupby_matches_plain_engine(rng):
    data = _data(rng)
    sm, sp = _sessions()
    aggs = lambda: (Sum(col("v")).alias("sv"),  # noqa: E731
                    CountStar().alias("n"),
                    Min(col("v")).alias("mn"),
                    Max(col("f")).alias("mx"),
                    Average(col("f")).alias("av"))
    dfm = sm.from_pydict(data, SCHEMA, partitions=4).group_by("k").agg(*aggs())
    dfp = sp.from_pydict(data, SCHEMA, partitions=4).group_by("k").agg(*aggs())
    _assert_same(dfm, dfp, approx_cols=(4, 5))


def test_mesh_groupby_string_key(rng):
    data = _data(rng)
    sm, sp = _sessions()
    dfm = sm.from_pydict(data, SCHEMA, partitions=3) \
        .group_by("g").agg(Sum(col("v")).alias("sv"), CountStar().alias("n"))
    dfp = sp.from_pydict(data, SCHEMA, partitions=3) \
        .group_by("g").agg(Sum(col("v")).alias("sv"), CountStar().alias("n"))
    _assert_same(dfm, dfp)


def test_mesh_groupby_with_nulls_and_filter(rng):
    data = _data(rng)
    sm, sp = _sessions()

    def q(s):
        df = s.from_pydict(data, SCHEMA, partitions=4)
        return df.where(col("v") > 0).group_by("k").agg(
            Sum(col("v")).alias("sv"), CountStar().alias("n"))

    _assert_same(q(sm), q(sp))


def test_mesh_groupby_host_oracle_differential(rng):
    """Device mesh result vs the host backend of the SAME mesh plan."""
    from spark_rapids_tpu.exec.core import collect_host
    data = _data(rng)
    s = TpuSession(MESH_CONF)
    df = s.from_pydict(data, SCHEMA, partitions=4).group_by("k").agg(
        Sum(col("v")).alias("sv"), CountStar().alias("n"))
    dev = _sorted_rows(df.collect())
    _, meta = df._overridden(quiet=True)
    host = _sorted_rows(collect_host(meta.exec_node, s.conf))
    assert dev == host


def test_mesh_repartition_preserves_rows_and_colocates_keys(rng):
    data = _data(rng, n=300)
    s = TpuSession(MESH_CONF)
    df = s.from_pydict(data, SCHEMA, partitions=4).repartition(8, "k")
    assert "MeshExchangeExec" in df.explain()
    rows = df.collect()
    plain = TpuSession({}).from_pydict(data, SCHEMA, partitions=4).collect()
    assert _sorted_rows(rows) == _sorted_rows(plain)

    # key colocation: execute partition-wise and check key disjointness
    from spark_rapids_tpu.exec.core import ExecCtx, device_to_host
    _, meta = df._overridden(quiet=True)
    ctx = ExecCtx(backend="device", conf=s.conf)
    ex = meta.exec_node
    key_sets = []
    for pid in range(ex.num_partitions(ctx)):
        ks = set()
        for b in ex.partition_iter(ctx, pid):
            hb = device_to_host(b)
            ks.update(hb.columns[0].to_list())
        key_sets.append(ks)
    for i in range(len(key_sets)):
        for j in range(i + 1, len(key_sets)):
            assert not (key_sets[i] & key_sets[j] - {None})


def test_mesh_grand_aggregate(rng):
    data = _data(rng)
    sm, sp = _sessions()
    dfm = sm.from_pydict(data, SCHEMA, partitions=4).agg(
        Sum(col("v")).alias("sv"), CountStar().alias("n"))
    dfp = sp.from_pydict(data, SCHEMA, partitions=4).agg(
        Sum(col("v")).alias("sv"), CountStar().alias("n"))
    # grand agg: no group keys -> planner keeps complete mode (no mesh);
    # both engines must agree regardless
    _assert_same(dfm, dfp)


def test_mesh_exchange_arbitrary_partition_count(rng):
    """Round-3: repartition counts != deviceCount still ride the mesh
    (rows route to device pid % mesh; each device serves its subset)."""
    mesh_s, plain_s = _sessions()
    data = _data(rng)
    for n in (3, 8, 13):
        mesh_df = mesh_s.from_pydict(data, SCHEMA, 2, 100).repartition(n, "k")
        plain_df = plain_s.from_pydict(data, SCHEMA, 2, 100).repartition(n, "k")
        ov, meta = mesh_df._overridden(quiet=True)
        assert "MeshExchangeExec" in meta.exec_node.node_desc()
        assert meta.exec_node.num_partitions(None) == n
        _assert_same(mesh_df, plain_df, approx_cols=(3,))


def test_place_shards_no_central_gather():
    """place_shards groups batches per device; union of shard rows ==
    input rows, and no shard sees the full concatenation."""
    import jax.numpy as jnp
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.exec.core import ExecCtx, device_to_host
    from spark_rapids_tpu.exec.basic import LocalScanExec
    from spark_rapids_tpu.exec.mesh_exec import place_shards
    data = {"k": list(range(100)), "s": [f"v{i%7}" for i in range(100)]}
    schema = T.Schema([T.StructField("k", T.LongType()),
                       T.StructField("s", T.StringType())])
    scan = LocalScanExec.from_pydict(data, schema, 1, 25)  # 4 batches
    ctx = ExecCtx(backend="device")
    batches = [b for b in scan.partition_iter(ctx, 0)]
    shards = place_shards(batches, 4)
    assert len(shards) == 4
    caps = {s.capacity for s in shards}
    assert len(caps) == 1               # uniform capacity
    got = []
    for sh in shards:
        hb = device_to_host(sh)
        got.extend(zip(*[c.to_list() for c in hb.columns]))
    assert sorted(got) == sorted(zip(data["k"], data["s"]))
    # no shard was handed every batch (the old central-concat shape)
    assert max(sh.host_num_rows() for sh in shards) < 100


def _dim_df(s):
    dim_schema = T.Schema([T.StructField("k", T.IntegerType(), True),
                           T.StructField("name", T.StringType(), True)])
    return s.from_pydict(
        {"k": list(range(0, 17, 2)),
         "name": [f"n{i}" for i in range(0, 17, 2)]},
        dim_schema, partitions=1)


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti", "right"])
def test_mesh_join_matches_oracle(rng, how):
    """MeshJoinExec: replicated build + per-device probe shards, every
    join type, vs the host oracle."""
    from spark_rapids_tpu.exec.core import collect_host
    sm, _ = _sessions()
    fact = sm.from_pydict(_data(rng), SCHEMA, partitions=4,
                          rows_per_batch=64)
    out = fact.join(_dim_df(sm), on="k", how=how)
    assert "MeshJoinExec" in out.explain()
    dev = _sorted_rows(out.collect())
    ov, meta = out._overridden(quiet=True)
    host = _sorted_rows(collect_host(meta.exec_node, sm.conf))
    assert dev == host and len(dev) > 0


def test_mesh_join_outputs_per_device(rng):
    """Probe outputs land on distinct mesh devices (no central probe)."""
    import jax
    from spark_rapids_tpu.exec.core import ExecCtx
    sm, _ = _sessions()
    fact = sm.from_pydict(_data(rng), SCHEMA, partitions=4,
                          rows_per_batch=64)
    out = fact.join(_dim_df(sm), on="k", how="inner")
    ov, meta = out._overridden(quiet=True)
    with ExecCtx(backend="device", conf=sm.conf) as ctx:
        node = meta.exec_node
        devs = set()
        for pid in range(node.num_partitions(ctx)):
            for b in node.partition_iter(ctx, pid):
                d = list(b.columns[0].data.devices())[0]
                devs.add(d)
        assert len(devs) > 1, f"all probe output on one device: {devs}"


def test_mesh_join_then_mesh_aggregate(rng):
    """The flagship shape: mesh join feeding a mesh group-by (q6-like
    scan -> join -> agg end to end under the mesh conf)."""
    from spark_rapids_tpu.exec.core import collect_host
    sm, _ = _sessions()
    fact = sm.from_pydict(_data(rng), SCHEMA, partitions=4,
                          rows_per_batch=64)
    out = fact.join(_dim_df(sm), on="k", how="inner") \
        .group_by("name").agg(Sum(col("v")).alias("sv"),
                              CountStar().alias("cnt"))
    plan = out.explain()
    assert "MeshJoinExec" in plan and "MeshAggregateExec" in plan
    dev = _sorted_rows(out.collect())
    ov, meta = out._overridden(quiet=True)
    host = _sorted_rows(collect_host(meta.exec_node, sm.conf))
    assert dev == host and len(dev) > 0


def test_mesh_full_join_stays_in_process(rng):
    sm, _ = _sessions()
    fact = sm.from_pydict(_data(rng), SCHEMA, partitions=2,
                          rows_per_batch=64)
    out = fact.join(_dim_df(sm), on="k", how="full")
    plan = out.explain()
    assert "MeshJoinExec" not in plan and "JoinExec" in plan


def test_mesh_grand_aggregate_over_join(rng):
    """q96 shape under mesh: joins feeding a GRAND aggregate (no group
    keys) must lower to the mesh program — per-device join outputs in
    the single-device complete path mixed devices (matrix finding)."""
    from spark_rapids_tpu.exec.core import collect_host
    sm, _ = _sessions()
    fact = sm.from_pydict(_data(rng), SCHEMA, partitions=4,
                          rows_per_batch=64)
    out = fact.join(_dim_df(sm), on="k", how="inner") \
        .agg(CountStar().alias("cnt"), Sum(col("v")).alias("sv"))
    plan = out.explain()
    assert "MeshAggregateExec" in plan
    dev = out.collect()
    ov, meta = out._overridden(quiet=True)
    assert dev == collect_host(meta.exec_node, sm.conf)
    assert dev[0][0] > 0


def test_mesh_join_feeding_non_mesh_consumer(rng):
    """Review repro: a non-mesh device operator above mesh outputs (a
    full join stays in-process) must not mix devices inside its jitted
    programs — the planner aligns mesh outputs at the boundary."""
    from spark_rapids_tpu.exec.core import collect_host
    sm, _ = _sessions()
    fact = sm.from_pydict(_data(rng), SCHEMA, partitions=4,
                          rows_per_batch=64)
    dim2_schema = T.Schema([T.StructField("k", T.IntegerType(), True),
                            T.StructField("w", T.DoubleType(), True)])
    dim2 = sm.from_pydict({"k": [0, 1, 2, 99],
                           "w": [0.5, 1.5, 2.5, 9.9]}, dim2_schema)
    out = fact.join(_dim_df(sm), on="k", how="inner") \
        .join(dim2, on="k", how="full")
    plan = out.explain()
    assert "MeshJoinExec" in plan and "JoinExec[full" in plan
    dev = _sorted_rows(out.collect())
    ov, meta = out._overridden(quiet=True)
    host = _sorted_rows(collect_host(meta.exec_node, sm.conf))
    assert dev == host and len(dev) > 0


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_mesh_join_partitioned_matches_oracle(rng, how):
    """Partitioned mesh join (VERDICT r3 item 5): threshold 0 forces the
    all-to-all-both-sides path (GpuShuffledHashJoinExec.scala:162
    analog); result must equal the host oracle for every join type."""
    from spark_rapids_tpu.exec.core import collect_host
    sm = TpuSession({**MESH_CONF,
                     "spark.rapids.tpu.mesh.join.buildThresholdBytes": 0})
    fact = sm.from_pydict(_data(rng), SCHEMA, partitions=4,
                          rows_per_batch=64)
    out = fact.join(_dim_df(sm), on="k", how=how)
    assert "MeshJoinExec" in out.explain()
    dev = _sorted_rows(out.collect())
    ov, meta = out._overridden(quiet=True)
    host = _sorted_rows(collect_host(meta.exec_node, sm.conf))
    assert dev == host and len(dev) > 0


@pytest.mark.parametrize("threshold", [None, 0])
@pytest.mark.parametrize("how", ["inner", "left", "semi"])
def test_mesh_join_on_two_keys_streams(rng, how, threshold):
    """A MeshJoinExec island on two integral keys takes the packed-key
    probes through the methods it inherits (JoinExec._prepare_build),
    with the build replicated and with it partitioned: no stream shard
    is sorted together with the build."""
    from spark_rapids_tpu.exec.core import collect_host
    from spark_rapids_tpu.obs.registry import get_registry
    conf = dict(MESH_CONF)
    if threshold is not None:
        conf["spark.rapids.tpu.mesh.join.buildThresholdBytes"] = threshold
    sm = TpuSession(conf)
    fact = sm.from_pydict(_data(rng), SCHEMA, partitions=4,
                          rows_per_batch=64)
    dim_schema = T.Schema([T.StructField("dk", T.IntegerType(), True),
                           T.StructField("dv", T.LongType(), True),
                           T.StructField("name", T.StringType(), True)])
    keys = [(k, v) for k in range(0, 17, 2) for v in (-7, 0, 1 << 40)]
    dim = sm.from_pydict(
        {"dk": [k for k, _ in keys] + [None], "dv": [v for _, v in keys] + [3],
         "name": [f"n{i}" for i in range(len(keys) + 1)]},
        dim_schema, partitions=1)
    fact = fact.select(col("k"), (col("v") % 2 * (1 << 40)).alias("v2"),
                       col("g"))
    out = fact.join(dim, on=[("k", "dk"), ("v2", "dv")], how=how)
    assert "MeshJoinExec" in out.explain()
    before = get_registry().counters()
    dev = _sorted_rows(out.collect())
    moved = get_registry().counters_since(before)
    assert moved.get("join.keys.packed", 0) >= 1
    assert "join.probe.sorted" not in moved
    assert "join.keys.unpackable" not in moved
    assert moved.get("join.probe.search", 0) >= 1
    ov, meta = out._overridden(quiet=True)
    host = _sorted_rows(collect_host(meta.exec_node, sm.conf))
    assert dev == host and len(dev) > 0


def test_mesh_join_partitioned_large_build(rng):
    """Build side larger than one device's fair shard still joins
    correctly: every build row is present exactly once across the mesh
    after the all-to-all (no replication)."""
    from spark_rapids_tpu.exec.core import collect_host
    sm = TpuSession({**MESH_CONF,
                     "spark.rapids.tpu.mesh.join.buildThresholdBytes": 0})
    n = 3000   # build side BIGGER than the stream side
    build_schema = T.Schema([T.StructField("k", T.IntegerType(), True),
                             T.StructField("w", T.LongType(), True)])
    build = sm.from_pydict(
        {"k": rng.integers(0, 97, n).astype(np.int32),
         "w": rng.integers(0, 10**6, n).astype(np.int64)},
        build_schema, partitions=4, rows_per_batch=256)
    probe = sm.from_pydict(_data(rng, n=300, nkeys=97), SCHEMA,
                           partitions=2, rows_per_batch=64)
    out = probe.join(build, on="k", how="inner") \
        .group_by("k").agg(Sum(col("w")).alias("sw"),
                           CountStar().alias("cnt"))
    assert "MeshJoinExec" in out.explain()
    dev = _sorted_rows(out.collect())
    ov, meta = out._overridden(quiet=True)
    host = _sorted_rows(collect_host(meta.exec_node, sm.conf))
    assert dev == host and len(dev) > 0


def test_mesh_join_threshold_keeps_replicated(rng):
    """A tiny build under the default threshold stays on the replicated
    path (no exchange nodes execute for the build side)."""
    from spark_rapids_tpu.exec.core import ExecCtx
    sm, _ = _sessions()
    fact = sm.from_pydict(_data(rng), SCHEMA, partitions=2,
                          rows_per_batch=64)
    out = fact.join(_dim_df(sm), on="k", how="inner")
    ov, meta = out._overridden(quiet=True)
    node = meta.exec_node
    from spark_rapids_tpu.exec.mesh_exec import MeshJoinExec
    while not isinstance(node, MeshJoinExec):
        node = node.children[0]
    with ExecCtx(backend="device", conf=sm.conf) as ctx:
        list(node.partition_iter(ctx, 0))
        assert node._use_partitioned(ctx) is False
        # neither exchange computed its outputs (replicated path only)
        for ex in node._exchanges:
            assert ("meshex", id(ex), ctx.backend) not in ctx.cache


def test_graft_entry_dryrun_multichip():
    """The driver's multichip entry point: replicated and partitioned
    mesh joins into a mesh aggregate over the 8 virtual devices, rows
    checked against the single-process engine inside the dryrun."""
    import __graft_entry__ as g
    g.dryrun_multichip(8)
