"""Whole-stage fusion + process-wide compile cache (exec/fused.py,
exec/compile_cache.py, plan/overrides.py _fuse_stages).

Three axes, mirroring the chaos-suite discipline of exact-result
assertions:

- correctness: fused and unfused plans return IDENTICAL rows on the
  TPC-H ladder queries, and the fusion pass is shape-reversible via
  ``spark.rapids.sql.fusion.enabled=false``;
- cache keys: same fragment → one shared program (hit); a changed
  literal, dtype, or non-child attribute (LIKE pattern — absent from
  ``repr``, the motivating case for structural fingerprints) → distinct
  keys; a changed capacity bucket reuses the SAME wrapper and is
  counted as a new compile at the signature level;
- resilience: an OOM storm inside a fused stage still converges through
  split-and-retry with exact results (fused bodies are elementwise, so
  row-halves reproduce identical rows in order).
"""
import pytest

from spark_rapids_tpu import TpuSession
from spark_rapids_tpu import types as T
from spark_rapids_tpu.exec import compile_cache as cc
from spark_rapids_tpu.exec.fused import FusedStageExec
from spark_rapids_tpu.obs.registry import get_registry

_LADDER = ["q1", "q3", "q6", "q12", "q18"]


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    from spark_rapids_tpu.bench.tpch_gen import generate_tpch
    d = str(tmp_path_factory.mktemp("tpch_fusion") / "sf001")
    generate_tpch(d, sf=0.01)
    return d


def _plan_of(df):
    ov, meta = df._overridden(quiet=True)
    return meta.exec_node


def _exec_classes(node, acc=None):
    acc = acc if acc is not None else []
    acc.append(type(node).__name__)
    for c in node.children:
        _exec_classes(c, acc)
    return acc


def _tpch_rows(data_dir, query, conf=None):
    from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
    s = TpuSession(dict(conf or {}))
    df = build_tpch_query(query, s, data_dir)
    plan = _plan_of(df)
    return sorted(df.collect(), key=str), plan


# ---------------------------------------------------------------------------
# correctness: fused == unfused, and the pass is reversible
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("query", _LADDER)
def test_fused_vs_unfused_exact(data_dir, query):
    fused_rows, fused_plan = _tpch_rows(data_dir, query)
    plain_rows, plain_plan = _tpch_rows(
        data_dir, query, {"spark.rapids.sql.fusion.enabled": "false"})
    assert fused_rows == plain_rows
    assert "FusedStageExec" not in _exec_classes(plain_plan)


def test_fusion_changes_and_restores_plan_shape(data_dir):
    """q3's filter/project chain feeding a join build side must fuse,
    and disabling fusion must restore the per-operator chain.  q6 (single filter under the
    aggregate) has no run of >=2 and must come out UNTOUCHED: fusion
    never wraps a lone operator."""
    _, fused_plan = _tpch_rows(data_dir, "q3")
    fused_classes = _exec_classes(fused_plan)
    assert "FusedStageExec" in fused_classes
    _, plain_plan = _tpch_rows(
        data_dir, "q3", {"spark.rapids.sql.fusion.enabled": "false"})
    plain_classes = _exec_classes(plain_plan)
    assert "FusedStageExec" not in plain_classes
    # the pass replaces runs, never reorders survivors
    survivors = [c for c in fused_classes if c != "FusedStageExec"]
    assert all(c in plain_classes for c in survivors)
    assert len(plain_classes) > len(fused_classes)

    _, q6_fused = _tpch_rows(data_dir, "q6")
    _, q6_plain = _tpch_rows(
        data_dir, "q6", {"spark.rapids.sql.fusion.enabled": "false"})
    assert _exec_classes(q6_fused) == _exec_classes(q6_plain)
    assert "FusedStageExec" not in _exec_classes(q6_fused)


def test_fused_stage_desc_names_replaced_ops():
    """EXPLAIN ANALYZE annotation: the fused node renders the pipeline
    it replaced."""
    s = TpuSession({})
    schema = T.Schema([T.StructField("a", T.LongType()),
                       T.StructField("b", T.DoubleType())])
    from spark_rapids_tpu.expr.core import col
    df = s.from_pydict({"a": [1, 2, 3, 4], "b": [1., 2., 3., 4.]}, schema)
    q = df.filter(col("a") > 1).select((col("b") * 2).alias("c"))
    plan = _plan_of(q)
    fused = [n for n in _walk(plan) if isinstance(n, FusedStageExec)]
    assert fused, _exec_classes(plan)
    desc = fused[0].node_desc()
    assert "FilterExec" in desc and "ProjectExec" in desc
    assert "2 ops" in desc


def _walk(node):
    yield node
    for c in node.children:
        yield from _walk(c)


def test_min_operators_conf():
    """A lone filter below the threshold is left unfused."""
    s = TpuSession({"spark.rapids.sql.fusion.minOperators": "3"})
    schema = T.Schema([T.StructField("a", T.LongType()),
                       T.StructField("b", T.DoubleType())])
    from spark_rapids_tpu.expr.core import col
    df = s.from_pydict({"a": [1, 2, 3, 4], "b": [1., 2., 3., 4.]}, schema)
    q = df.filter(col("a") > 1).select((col("b") * 2).alias("c"))
    assert "FusedStageExec" not in _exec_classes(_plan_of(q))


# ---------------------------------------------------------------------------
# one compaction a stage: filters and their masks, rows packed at the end
# ---------------------------------------------------------------------------

_XY = T.Schema([T.StructField("x", T.DoubleType(), True),
                T.StructField("y", T.DoubleType(), True),
                T.StructField("tag", T.StringType(), True)])


def _xy_data(n=240):
    # y is 0 or NULL on two rows in five: ``x / y`` is NULL there, and
    # ``x / y > 1`` is true on rows with a small y that ``y > 2`` drops
    x = [None if i % 11 == 0 else float(i % 13) - 2.0 for i in range(n)]
    y = [None if i % 5 == 0 else float(i % 5 - 1) for i in range(n)]
    return {"x": x, "y": y, "tag": [f"t{i % 7}" for i in range(n)]}


def _chain(df, name):
    from spark_rapids_tpu.expr.core import col
    ratio = col("x") / col("y")
    if name == "filter_filter":
        return df.filter(col("y") != 0.0).filter(ratio > 1.0)
    if name == "filter_filter_second_true_on_dropped":
        return df.filter(col("y") > 2.0).filter(ratio > 1.0)
    if name == "filter_project_filter":
        return df.filter(col("y") != 0.0) \
            .select(ratio.alias("r"), col("tag"), col("x")) \
            .filter(col("r") > 1.0)
    if name == "filter_project_narrowing":
        return df.filter(col("y") != 0.0).select(ratio.alias("r"))
    if name == "filter_filter_empty":
        return df.filter(col("y") != 0.0).filter(ratio > 1e9)
    assert name == "filter_project_filter_empty", name
    return df.filter(col("y") != 0.0) \
        .select(ratio.alias("r"), col("tag")).filter(col("r") > 1e9)


_CHAINS = ("filter_filter", "filter_filter_second_true_on_dropped",
           "filter_project_filter", "filter_project_narrowing",
           "filter_filter_empty", "filter_project_filter_empty")


@pytest.mark.parametrize("chain", _CHAINS)
def test_fused_chain_equals_unfused_row_for_row(chain):
    """A later member sees the real values of rows an earlier filter
    dropped (not zeros): a condition that is NULL or true there must not
    bring them back, and row order is the input's."""
    data = _xy_data()
    fused = _chain(TpuSession({}).from_pydict(data, _XY), chain)
    plain = _chain(TpuSession({"spark.rapids.sql.fusion.enabled": "false"})
                   .from_pydict(data, _XY), chain)
    assert "FusedStageExec" in _exec_classes(_plan_of(fused))
    assert "FusedStageExec" not in _exec_classes(_plan_of(plain))
    got, want = fused.collect(), plain.collect()
    assert got == want
    assert (len(got) == 0) == chain.endswith("_empty"), len(got)


def test_two_filter_stage_compacts_once():
    """The lowered body of Filter -> Project -> Filter -> Project holds
    exactly ONE compaction of the stage's output columns (two of the
    input's three): as many scatters as one ``compact`` -- the index
    scatter, once a branch where a ``cond`` picks the bucket, never one a
    leaf or one a filter -- and as many gathers (one a dtype a branch)."""
    import jax

    from spark_rapids_tpu.exec.fused import filters_merged, stage_body
    from spark_rapids_tpu.expr.core import col
    from spark_rapids_tpu.host.batch import HostBatch
    from spark_rapids_tpu.ops import kernels as dk
    q = TpuSession({}).from_pydict(_xy_data(), _XY) \
        .filter(col("y") != 0.0) \
        .select((col("x") / col("y")).alias("r"), col("tag"), col("x")) \
        .filter(col("r") > 1.0).select(col("r"), col("tag"))
    stage = next(n for n in _walk(_plan_of(q))
                 if isinstance(n, FusedStageExec))
    assert filters_merged(stage.fused_ops) == 1
    body = stage_body(stage.fused_ops)

    def count(fn, arg, op):
        return jax.jit(fn).lower(arg).as_text().count(f'"stablehlo.{op}"')
    for cap, branches in ((None, 1), (dk.COND_MIN_CAPACITY, 2)):
        batch = HostBatch.from_pydict(_xy_data(), _XY).to_device(
            capacity=cap)
        out = jax.eval_shape(body, batch)
        assert len(out.columns) == 2

        def one(b):
            return dk.compact(b, b.row_mask())
        assert count(one, out, "scatter") == branches
        assert count(body, batch, "scatter") == branches
        assert count(body, batch, "gather") == count(one, out, "gather") > 0
        assert count(body, batch, "sort") == 0


# ---------------------------------------------------------------------------
# cache keys
# ---------------------------------------------------------------------------

def _bound_filter_cond(lit):
    from spark_rapids_tpu.expr.core import bind, col
    schema = T.Schema([T.StructField("a", T.LongType())])
    return bind(col("a") > lit, schema)


def test_same_fragment_hits():
    cond = _bound_filter_cond(5)
    k1 = cc.fragment_key("filter", cond)
    k2 = cc.fragment_key("filter", _bound_filter_cond(5))
    assert k1 == k2
    before = get_registry().snapshot()
    j1 = cc.shared_jit(k1, lambda b: b, name="test_identity")
    j2 = cc.shared_jit(k2, lambda b: b, name="test_identity")
    assert j1 is j2
    moved = get_registry().delta(before)["counters"]
    assert moved.get("fusion_cache_hits", 0) >= 1


def test_changed_literal_misses():
    assert cc.fragment_key("filter", _bound_filter_cond(5)) != \
        cc.fragment_key("filter", _bound_filter_cond(6))


def test_changed_dtype_misses():
    # same repr territory (5 vs 5.0 at least differs; int64 vs int32
    # literal dtype does NOT appear in repr — the fingerprint must see it)
    from spark_rapids_tpu.expr.core import Literal
    a = Literal(5, T.LongType())
    b = Literal(5, T.IntegerType())
    assert cc.fragment_key("lit", a) != cc.fragment_key("lit", b)


def test_changed_schema_misses():
    s1 = T.Schema([T.StructField("a", T.LongType())])
    s2 = T.Schema([T.StructField("a", T.IntegerType())])
    assert cc.fragment_key("project", s1) != cc.fragment_key("project", s2)


def test_like_pattern_in_key():
    """Regression for repr-lossiness: LIKE stores its pattern as a
    non-child attribute, so two conditions with identical reprs must
    still get distinct programs."""
    from spark_rapids_tpu.expr.core import bind, col
    from spark_rapids_tpu.expr.strings import Like
    schema = T.Schema([T.StructField("s", T.StringType())])
    a = bind(Like(col("s"), "%foo%"), schema)
    b = bind(Like(col("s"), "%bar%"), schema)
    assert cc.fragment_key("filter", a) != cc.fragment_key("filter", b)


def test_capacity_bucket_is_signature_level():
    """One python-level wrapper serves every capacity bucket; a NEW
    bucket is a new jax executable and moves compile_count exactly
    once — re-dispatching an old bucket moves nothing."""
    import jax.numpy as jnp
    key = cc.fragment_key("test_capacity_bucket", "x")
    j = cc.shared_jit(key, lambda x: x + 1, name="test_add_one")
    reg = get_registry()

    def compiles(arr):
        before = reg.snapshot()
        j(arr)
        return reg.delta(before)["counters"].get("compile_count", 0)

    assert compiles(jnp.zeros(8)) == 1       # first bucket
    assert compiles(jnp.zeros(16)) == 1      # new bucket -> one compile
    assert compiles(jnp.zeros(8)) == 0       # old bucket -> pure reuse
    assert compiles(jnp.zeros(16)) == 0
    assert j.signature_count() == 2


def test_fingerprint_orders_and_none():
    """Resolved sort orders (plain objects) and None inputs fingerprint
    structurally, not by repr or identity."""
    assert cc.fingerprint(None) == cc.fingerprint(None)
    assert cc.fingerprint([1, None]) != cc.fingerprint([1, 0])
    assert cc.fingerprint((1, 2)) != cc.fingerprint([1, 2])


def test_opaque_state_never_falsely_shares():
    """Closure state the fingerprint cannot canonicalize (a callable)
    must produce distinct keys per instance — losing sharing is safe,
    sharing wrong programs is not."""
    k1 = cc.fragment_key("udf", lambda x: x + 1)
    k2 = cc.fragment_key("udf", lambda x: x + 2)
    assert k1 != k2


# ---------------------------------------------------------------------------
# second run of the same query compiles nothing
# ---------------------------------------------------------------------------

def test_second_run_zero_new_compiles(data_dir):
    # result cache off: this test pins the COMPILE cache, so the second
    # run must actually reach the executor instead of being served rows
    off = {"spark.rapids.sql.resultCache.enabled": "false"}
    _tpch_rows(data_dir, "q6", off)  # warm
    before = get_registry().snapshot()
    rows, _ = _tpch_rows(data_dir, "q6", off)
    moved = get_registry().delta(before)["counters"]
    assert moved.get("compile_count", 0) == 0, moved
    assert moved.get("fusion_cache_misses", 0) == 0, moved
    assert moved.get("fusion_cache_hits", 0) >= 1, moved
    assert rows


def test_shared_input_disables_donation():
    """One source feeding TWO fused stages (a CTE scanned once, consumed
    twice) must not donate: either stage's donation would delete the
    shared batch's buffers under its sibling.  An exclusive branch keeps
    donating, and the gated plan still returns exact rows."""
    from spark_rapids_tpu.expr.core import col

    s = TpuSession({})
    schema = T.Schema([T.StructField("a", T.LongType()),
                       T.StructField("b", T.DoubleType())])
    n = 200
    base = s.from_pydict(
        {"a": list(range(n)), "b": [float(i) for i in range(n)]}, schema)
    b1 = base.where(col("a") % 2 == 0).select(
        col("a"), (col("b") * 2).alias("c"))
    b2 = base.where(col("a") % 3 == 0).select(
        col("a"), (col("b") + 1).alias("d"))
    ov, meta = b1.join(b2, on="a")._overridden(quiet=True)
    fused = [x for x in _walk(meta.exec_node)
             if isinstance(x, FusedStageExec)]
    assert len(fused) == 2
    assert [f.donate_ok for f in fused] == [False, False]
    assert len({id(f.children[0]) for f in fused}) == 1  # truly shared

    ov2, meta2 = b1._overridden(quiet=True)
    solo = [x for x in _walk(meta2.exec_node)
            if isinstance(x, FusedStageExec)]
    assert len(solo) == 1 and solo[0].donate_ok

    rows = sorted(b1.join(b2, on="a").collect())
    assert rows == [(a, float(a) * 2, a, float(a) + 1)
                    for a in range(0, n, 6)]


# ---------------------------------------------------------------------------
# OOM storm inside a fused stage
# ---------------------------------------------------------------------------

def test_oom_split_and_retry_inside_fused_stage():
    """The storm fires at dispatch BEFORE the fused program consumes
    (donates) the batch, so split-and-retry halves it exactly as in the
    unfused engine — results stay exact and splits are recorded."""
    from spark_rapids_tpu.exec.core import (ExecCtx, _rows_from_host,
                                            collect_host, device_to_host)
    from spark_rapids_tpu.expr.core import col

    s = TpuSession({
        "spark.rapids.test.faults": "memory.oom.until_rows:oom,until_rows=64",
    })
    schema = T.Schema([T.StructField("a", T.LongType()),
                       T.StructField("b", T.DoubleType())])
    n = 500
    df = s.from_pydict(
        {"a": list(range(n)), "b": [float(i) * 0.5 for i in range(n)]},
        schema)
    q = df.filter(col("a") % 3 != 0).select(
        (col("b") * 2).alias("c"), col("a")).filter(col("a") < 400)
    ov, meta = q._overridden(quiet=True)
    assert any(isinstance(x, FusedStageExec) for x in _walk(meta.exec_node))
    with ExecCtx(backend="device", conf=s.conf) as ctx:
        rows = []
        for b in meta.exec_node.execute(ctx):
            rows.extend(_rows_from_host(device_to_host(b)))
        splits = ctx.catalog.metrics["oom_splits"]
    expect = sorted((float(i) * 0.5 * 2, i) for i in range(n)
                    if i % 3 != 0 and i < 400)
    assert sorted(rows) == expect
    assert splits > 0, splits
