"""Exec layer tests: differential CPU-oracle vs TPU path.

Mirrors the reference's SparkQueryCompareTestSuite pattern
(tests/.../SparkQueryCompareTestSuite.scala:153-167) and the pytest
integration harness (integration_tests asserts.py:290).
"""
import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exec import (CoalesceBatchesExec, FilterExec,
                                   GlobalLimitExec, HashAggregateExec,
                                   LocalLimitExec, LocalScanExec, ProjectExec,
                                   RangeExec, RequireSingleBatch, SortExec,
                                   TargetSize, UnionExec, collect_device,
                                   collect_host)
from spark_rapids_tpu.expr.aggregates import (Average, Count, CountStar, Max,
                                              Min, Sum)
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.testing import assert_tpu_and_cpu_equal


def _scan(rng, n=100, parts=1, rows_per_batch=None, with_nulls=True):
    def nullify(vals, frac=0.15):
        if not with_nulls:
            return list(vals)
        mask = rng.random(len(vals)) < frac
        return [None if m else v for v, m in zip(vals, mask)]

    schema = T.Schema([
        T.StructField("i32", T.IntegerType()),
        T.StructField("i64", T.LongType()),
        T.StructField("f64", T.DoubleType()),
        T.StructField("s", T.StringType()),
        T.StructField("k", T.IntegerType()),
    ])
    data = {
        "i32": nullify(rng.integers(-100, 100, n).tolist()),
        "i64": nullify(rng.integers(-10**9, 10**9, n).tolist()),
        "f64": nullify((rng.random(n) * 200 - 100).tolist()),
        "s": nullify([f"str_{v}" for v in rng.integers(0, 30, n)]),
        "k": nullify(rng.integers(0, 8, n).tolist()),
    }
    return LocalScanExec.from_pydict(data, schema, partitions=parts,
                                     rows_per_batch=rows_per_batch)


def test_project_filter(rng):
    scan = _scan(rng, 200, rows_per_batch=64)
    plan = ProjectExec(
        [(col("i32") + col("k")).alias("a"),
         (col("f64") * 2.0).alias("b"),
         col("s")],
        FilterExec(col("i32") > lit(0), scan))
    assert_tpu_and_cpu_equal(plan)


def test_filter_all_and_none(rng):
    scan = _scan(rng, 50)
    assert_tpu_and_cpu_equal(FilterExec(col("i32") > lit(-1000), scan))
    assert collect_device(FilterExec(col("i32") > lit(10**6), scan)) == []


def test_range():
    plan = RangeExec(0, 1000, 3, partitions=4, rows_per_batch=128)
    rows = collect_host(plan)
    assert [r[0] for r in rows] == list(range(0, 1000, 3))
    assert_tpu_and_cpu_equal(plan, ignore_order=False)


def test_union(rng):
    a, b = _scan(rng, 40), _scan(rng, 25)
    assert_tpu_and_cpu_equal(UnionExec([a, b]))


def test_limits(rng):
    scan = _scan(rng, 100, parts=2, rows_per_batch=16)
    assert len(collect_device(LocalLimitExec(10, scan))) == 20  # per partition
    assert len(collect_device(GlobalLimitExec(13, scan))) == 13
    assert_tpu_and_cpu_equal(GlobalLimitExec(13, scan))


def test_coalesce_batches(rng):
    scan = _scan(rng, 300, rows_per_batch=10)
    plan = CoalesceBatchesExec(TargetSize(1 << 14), scan)
    assert_tpu_and_cpu_equal(plan)
    single = CoalesceBatchesExec(RequireSingleBatch, scan)
    assert_tpu_and_cpu_equal(single)


@pytest.mark.parametrize("rows_per_batch", [None, 37])
def test_groupby_aggregate(rng, rows_per_batch):
    scan = _scan(rng, 200, rows_per_batch=rows_per_batch)
    plan = HashAggregateExec(
        [col("k")],
        [col("k"),
         Sum(col("i32")).alias("sum_i32"),
         Count(col("f64")).alias("cnt_f64"),
         CountStar().alias("cnt"),
         Min(col("i64")).alias("min_i64"),
         Max(col("f64")).alias("max_f64"),
         Average(col("i32")).alias("avg_i32")],
        scan)
    assert_tpu_and_cpu_equal(plan)


def test_grand_aggregate(rng):
    scan = _scan(rng, 150, rows_per_batch=40)
    plan = HashAggregateExec(
        [],
        [Sum(col("f64")).alias("s"), CountStar().alias("c"),
         Average(col("i64")).alias("a")],
        scan)
    rows = assert_tpu_and_cpu_equal(plan)
    assert len(rows) == 1


def test_grand_aggregate_empty_input(rng):
    scan = _scan(rng, 20)
    empty = FilterExec(col("i32") > lit(10**6), scan)
    plan = HashAggregateExec(
        [], [Sum(col("i32")).alias("s"), CountStar().alias("c")], empty)
    rows = assert_tpu_and_cpu_equal(plan)
    assert rows == [(None, 0)]


def test_agg_expression_over_aggs(rng):
    scan = _scan(rng, 120, rows_per_batch=50)
    plan = HashAggregateExec(
        [col("k")],
        [col("k"),
         (Sum(col("i32")) + CountStar()).alias("mix"),
         (Sum(col("f64")) / CountStar()).alias("manual_avg")],
        scan)
    assert_tpu_and_cpu_equal(plan)


def test_partial_final_split(rng):
    """partial -> final reproduces complete-mode results (the exchange
    seam used by distributed aggregation)."""
    scan = _scan(rng, 200, rows_per_batch=29)
    results = [col("k"), Sum(col("i32")).alias("s"), CountStar().alias("c"),
               Average(col("f64")).alias("a")]
    complete = HashAggregateExec([col("k")], results, scan)
    partial = HashAggregateExec([col("k")], results, scan, mode="partial")
    final = HashAggregateExec.final_from_partial(partial, partial)
    from spark_rapids_tpu.testing import _sort_key
    cpu_c = sorted(collect_host(complete), key=_sort_key)
    cpu_s = sorted(collect_host(final), key=_sort_key)
    assert cpu_c == cpu_s
    assert_tpu_and_cpu_equal(final)


def test_sort(rng):
    scan = _scan(rng, 150, rows_per_batch=41)
    plan = SortExec([("k", True), ("i32", False), ("s", True)], scan,
                    global_sort=True)
    assert_tpu_and_cpu_equal(plan, ignore_order=False)


@pytest.mark.parametrize("slots,kept,fetches,shrinks", [
    (1 << 17, 90, 1, 1),        # what a filter left of a large batch
    (1 << 17, (1 << 16) + 1, 1, 0),     # over half full: sorted as it is
    (1 << 12, 90, 0, 0)])       # small: not worth the fetch
def test_total_sort_right_sizes_a_sparse_input(slots, kept, fetches,
                                               shrinks):
    """A total sort, and the fetch of its rows, run at batch capacity:
    one whose input is what a filter left of a far larger batch (q51:
    92k rows in 2^23 slots) sorts it in its rows' own bucket."""
    from spark_rapids_tpu.exec.core import ExecCtx, device_to_host
    from spark_rapids_tpu.obs.registry import get_registry
    schema = T.Schema([T.StructField("k", T.IntegerType(), True),
                       T.StructField("v", T.LongType(), True)])
    n = slots - 5
    k = np.random.default_rng(5).permutation(n).astype(np.int32)
    scan = LocalScanExec.from_pydict({"k": k, "v": k.astype(np.int64) * 3},
                                     schema, rows_per_batch=slots)
    plan = SortExec([("k", False)],
                    FilterExec(col("k") < lit(kept), scan), global_sort=True)
    before = get_registry().counters()
    with ExecCtx(backend="device") as ctx:
        out, = list(plan.partition_iter(ctx, 0))
        rows = device_to_host(out).to_rows()
    moved = get_registry().counters_since(before)
    assert rows == [(i, 3 * i) for i in reversed(range(kept))]
    assert moved.get("span.fetch@SortExec.count", 0) == fetches
    assert moved.get("program.batch_shrink.launches", 0) == shrinks
    from spark_rapids_tpu.columnar.batch import round_capacity
    assert out.capacity == (round_capacity(kept) if shrinks else slots)


def test_sort_nulls_and_nans(rng):
    schema = T.Schema([T.StructField("x", T.DoubleType())])
    vals = [1.0, None, float("nan"), -0.0, 0.0, float("inf"),
            float("-inf"), None, 2.5, float("nan")]
    scan = LocalScanExec.from_pydict({"x": vals}, schema)
    for asc in (True, False):
        plan = SortExec([("x", asc)], scan, global_sort=True)
        assert_tpu_and_cpu_equal(plan, ignore_order=False)


def test_string_groupby(rng):
    scan = _scan(rng, 100, rows_per_batch=33)
    plan = HashAggregateExec(
        [col("s")], [col("s"), CountStar().alias("c"),
                     Sum(col("i32")).alias("si")], scan)
    assert_tpu_and_cpu_equal(plan)


def test_multi_key_groupby(rng):
    scan = _scan(rng, 200, rows_per_batch=67)
    plan = HashAggregateExec(
        [col("k"), col("s")],
        [col("k"), col("s"), CountStar().alias("c"),
         Max(col("i64")).alias("m")],
        scan)
    assert_tpu_and_cpu_equal(plan)


def test_groupby_float_key_zero_and_null():
    """Regression: host oracle must not merge 0.0 with null groups."""
    schema = T.Schema([T.StructField("x", T.DoubleType())])
    scan = LocalScanExec.from_pydict({"x": [0.0, None, -0.0, 1.5, None]},
                                     schema)
    plan = HashAggregateExec([col("x")], [col("x"), CountStar().alias("c")],
                             scan)
    rows = assert_tpu_and_cpu_equal(plan)
    assert sorted(rows, key=lambda r: (r[0] is None, r[0])) == \
        [(0.0, 2), (1.5, 1), (None, 2)]


def test_complete_agg_multi_partition(rng):
    """Regression: complete-mode agg collapses multi-partition input."""
    scan = _scan(rng, 100, parts=4, rows_per_batch=10)
    plan = HashAggregateExec([], [CountStar().alias("c")], scan)
    rows = assert_tpu_and_cpu_equal(plan)
    assert rows == [(100,)]


def test_coalesce_goal_insertion(rng):
    """The planner inserts CoalesceBatchesExec per children_coalesce_goal
    (reference GpuTransitionOverrides.insertCoalesce :224-244): an
    aggregation over many small scan batches sees batched input."""
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.expr.aggregates import Sum

    s = TpuSession({})
    schema = T.Schema([T.StructField("k", T.IntegerType()),
                       T.StructField("v", T.LongType())])
    df = s.from_pydict(
        {"k": [int(x) for x in rng.integers(0, 5, 200)],
         "v": list(range(200))}, schema, partitions=1, rows_per_batch=10)
    out = df.group_by("k").agg(Sum(col("v")).alias("sv"))
    plan = out.explain()
    assert "CoalesceBatchesExec" in plan
    dev = sorted(out.collect())
    ov, meta = out._overridden(quiet=True)
    from spark_rapids_tpu.exec.core import collect_host as _ch
    assert dev == sorted(_ch(meta.exec_node, s.conf))


def test_global_sort_total_order_across_partitions(rng):
    """order_by establishes a TOTAL order even over multi-partition
    input (SF1 regression: per-partition sort + partition-ordered limit
    returned the wrong top-k when the child kept join partitioning)."""
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.exec.core import collect_host as _ch

    s = TpuSession({"spark.sql.shuffle.partitions": 5})
    schema = T.Schema([T.StructField("k", T.IntegerType()),
                       T.StructField("s", T.StringType())])
    n = 500
    df = s.from_pydict(
        {"k": [int(x) for x in rng.integers(0, 1000, n)],
         "s": [None if i % 7 == 0 else f"s{i%13}" for i in range(n)]},
        schema, partitions=4, rows_per_batch=32)
    out = df.order_by(("s", True), ("k", True)).limit(20)
    dev = out.collect()
    ov, meta = out._overridden(quiet=True)
    host = _ch(meta.exec_node, s.conf)
    assert dev == host                       # ordered compare, not a set
    # the global top-20 by (s asc nulls-first, k asc), from all rows
    allr = sorted(df.collect(),
                  key=lambda r: (r[1] is not None, r[1] or "", r[0]))
    assert dev == allr[:20]
