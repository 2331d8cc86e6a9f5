"""Join differential tests: device sort-merge kernel vs CPU oracle.

Mirrors the reference's join coverage (integration_tests join_test.py:
all join types x key types x nulls; tests/GpuHashJoinSuite) with fuzzed
key data including nulls, NaN, -0.0 and duplicate keys.
"""
import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exec import (CrossJoinExec, JoinExec, LocalScanExec,
                                   collect_device, collect_host)
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.expr.cast import Cast
from spark_rapids_tpu.testing import assert_tpu_and_cpu_equal

L_SCHEMA = T.Schema([
    T.StructField("lk", T.IntegerType(), True),
    T.StructField("lv", T.LongType(), True),
    T.StructField("ls", T.StringType(), True),
])
R_SCHEMA = T.Schema([
    T.StructField("rk", T.IntegerType(), True),
    T.StructField("rv", T.DoubleType(), True),
])


def _sides(rng, nl=120, nr=90, key_range=25):
    lk = [None if rng.random() < 0.08 else int(x)
          for x in rng.integers(0, key_range, nl)]
    rk = [None if rng.random() < 0.08 else int(x)
          for x in rng.integers(0, key_range, nr)]
    left = LocalScanExec.from_pydict({
        "lk": lk,
        "lv": [int(x) for x in rng.integers(-50, 50, nl)],
        "ls": [f"s{x}" if x % 4 else None for x in rng.integers(0, 30, nl)],
    }, L_SCHEMA, rows_per_batch=37)
    right = LocalScanExec.from_pydict({
        "rk": rk,
        "rv": [None if rng.random() < 0.1 else float(np.round(x, 2))
               for x in rng.normal(size=nr)],
    }, R_SCHEMA, rows_per_batch=41)
    return left, right


@pytest.mark.parametrize("jt", ["inner", "left", "right", "full", "semi",
                                "anti"])
def test_join_types_match_oracle(rng, jt):
    left, right = _sides(rng)
    plan = JoinExec(left, right, [col("lk")], [col("rk")], jt)
    rows = assert_tpu_and_cpu_equal(plan)
    assert rows  # non-degenerate


@pytest.mark.parametrize("jt,condition", [
    ("inner", False), ("left", False), ("right", False), ("full", False),
    ("semi", False), ("anti", False), ("inner", True)])
def test_join_output_carries_its_row_count(rng, jt, condition):
    """A join's output batch carries the count the host fetched to size
    its gather (``known_rows``), which must BE its row count; a residual
    condition filters after that fetch, and the count then stays on the
    device."""
    from spark_rapids_tpu.exec.core import ExecCtx
    left, right = _sides(rng)
    cond = (col("lv") > lit(0)) if condition else None
    plan = JoinExec(left, right, [col("lk")], [col("rk")], jt,
                    condition=cond)
    with ExecCtx(backend="device") as ctx:
        batches = list(plan.execute(ctx))
        assert batches
        known = [b.known_rows for b in batches]
        if condition:
            assert any(k is None for k in known)
        for b, k in zip(batches, known):
            assert k is None or k == int(b.num_rows)
        if not condition and jt != "full":
            assert None not in known


def test_inner_join_row_semantics(rng):
    left = LocalScanExec.from_pydict(
        {"lk": [1, 2, 2, None], "lv": [10, 20, 21, 30],
         "ls": ["a", "b", "c", "d"]}, L_SCHEMA)
    right = LocalScanExec.from_pydict(
        {"rk": [2, 2, 3, None], "rv": [0.5, 0.6, 0.7, 0.8]}, R_SCHEMA)
    plan = JoinExec(left, right, [col("lk")], [col("rk")], "inner")
    rows = sorted(collect_host(plan))
    # 2x2 match for key 2; nulls never match
    assert len(rows) == 4
    assert all(r[0] == 2 for r in rows)
    assert sorted(collect_device(plan)) == rows


def test_left_join_keeps_null_keys(rng):
    left = LocalScanExec.from_pydict(
        {"lk": [None, 5], "lv": [1, 2], "ls": ["x", "y"]}, L_SCHEMA)
    right = LocalScanExec.from_pydict(
        {"rk": [7], "rv": [1.0]}, R_SCHEMA)
    plan = JoinExec(left, right, [col("lk")], [col("rk")], "left")
    rows = sorted(collect_host(plan), key=lambda r: str(r))
    assert len(rows) == 2
    assert all(r[3] is None and r[4] is None for r in rows)
    assert_tpu_and_cpu_equal(plan)


def test_full_join_unmatched_both_sides(rng):
    left, right = _sides(rng, nl=60, nr=60, key_range=40)
    plan = JoinExec(left, right, [col("lk")], [col("rk")], "full")
    cpu = assert_tpu_and_cpu_equal(plan)
    # full join row count >= max side count
    assert len(cpu) >= 60


def test_join_on_expression_keys(rng):
    left, right = _sides(rng)
    plan = JoinExec(left, right, [Cast(col("lk"), T.LongType())],
                    [Cast(col("rk"), T.LongType())], "inner")
    assert_tpu_and_cpu_equal(plan)


def test_join_multi_key_with_strings(rng):
    schema_a = T.Schema([T.StructField("k1", T.IntegerType(), True),
                         T.StructField("s1", T.StringType(), True)])
    schema_b = T.Schema([T.StructField("k2", T.IntegerType(), True),
                         T.StructField("s2", T.StringType(), True)])
    n = 80
    a = LocalScanExec.from_pydict({
        "k1": [int(x) for x in rng.integers(0, 5, n)],
        "s1": [f"g{x}" for x in rng.integers(0, 4, n)]}, schema_a)
    b = LocalScanExec.from_pydict({
        "k2": [int(x) for x in rng.integers(0, 5, n)],
        "s2": [f"g{x}" for x in rng.integers(0, 4, n)]}, schema_b)
    plan = JoinExec(a, b, [col("k1"), col("s1")], [col("k2"), col("s2")],
                    "inner")
    rows = assert_tpu_and_cpu_equal(plan)
    for r in rows:
        assert r[0] == r[2] and r[1] == r[3]


def test_join_nan_and_negzero_keys(rng):
    sa = T.Schema([T.StructField("k", T.DoubleType(), True)])
    sb = T.Schema([T.StructField("k2", T.DoubleType(), True)])
    a = LocalScanExec.from_pydict(
        {"k": [float("nan"), -0.0, 1.5, None]}, sa)
    b = LocalScanExec.from_pydict(
        {"k2": [float("nan"), 0.0, 2.5, None]}, sb)
    plan = JoinExec(a, b, [col("k")], [col("k2")], "inner")
    rows = collect_host(plan)
    # NaN==NaN and -0.0==0.0; nulls never match
    assert len(rows) == 2
    assert_tpu_and_cpu_equal(plan)


def test_inner_join_with_condition(rng):
    left, right = _sides(rng)
    plan = JoinExec(left, right, [col("lk")], [col("rk")], "inner",
                    condition=col("lv") > lit(0))
    cpu = assert_tpu_and_cpu_equal(plan)
    assert all(r[1] > 0 for r in cpu)


def test_cross_join_with_condition(rng):
    left, right = _sides(rng, nl=20, nr=15)
    plan = CrossJoinExec(left, right)
    cpu = assert_tpu_and_cpu_equal(plan)
    assert len(cpu) == 20 * 15
    plan2 = CrossJoinExec(left, right, condition=col("lv") > col("rv"))
    assert_tpu_and_cpu_equal(plan2)


def test_join_empty_sides(rng):
    left = LocalScanExec.from_pydict(
        {"lk": [], "lv": [], "ls": []}, L_SCHEMA)
    right = LocalScanExec.from_pydict(
        {"rk": [1, 2], "rv": [0.1, 0.2]}, R_SCHEMA)
    for jt in ("inner", "left", "full", "semi", "anti", "right"):
        plan = JoinExec(left, right, [col("lk")], [col("rk")], jt)
        assert_tpu_and_cpu_equal(plan)


def test_condition_rejected_for_outer():
    left = LocalScanExec.from_pydict(
        {"lk": [1], "lv": [1], "ls": ["a"]}, L_SCHEMA)
    right = LocalScanExec.from_pydict({"rk": [1], "rv": [1.0]}, R_SCHEMA)
    with pytest.raises(ValueError):
        JoinExec(left, right, [col("lk")], [col("rk")], "left",
                 condition=col("lv") > lit(0))


def test_session_right_join_asymmetric_schemas():
    """Session-level right join with different schemas per side
    (regression: the planner's rewrite passes reassigned exec children
    in meta order, clobbering JoinExec's internal side swap — columns
    came back misaligned and rows were a left join's)."""
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.exec.core import collect_host as _ch
    s = TpuSession({})
    fact_schema = T.Schema([T.StructField("k", T.IntegerType(), True),
                            T.StructField("g", T.StringType(), True),
                            T.StructField("v", T.LongType(), True)])
    dim_schema = T.Schema([T.StructField("k", T.IntegerType(), True),
                           T.StructField("name", T.StringType(), True)])
    fact = s.from_pydict({"k": [1, 2, 3, 4] * 10, "g": ["a"] * 40,
                          "v": list(range(40))}, fact_schema,
                         partitions=2, rows_per_batch=8)
    dim = s.from_pydict({"k": [1, 2, 9], "name": ["x", "y", "z"]},
                        dim_schema)
    out = fact.join(dim, on="k", how="right")
    dev = sorted(out.collect(), key=str)
    ov, meta = out._overridden(quiet=True)
    host = sorted(_ch(meta.exec_node, s.conf), key=str)
    assert dev == host
    # k=9 is unmatched: null-extended fact side, dim columns present
    assert (None, None, None, 9, "z") in dev
    # every matched row keeps fact columns aligned (g is the string)
    matched = [r for r in dev if r[0] is not None]
    assert all(r[1] == "a" and r[4] in ("x", "y") for r in matched)
    assert len(matched) == 20


# ------------------------------------------------------------------
# The streaming probe's two ways to find a key's run in the sorted build
# (ops/join.py): by address in a table where the build's keys are dense,
# by binary search otherwise.  Same contract, same rows.

I32 = np.iinfo(np.int32)
I64 = np.iinfo(np.int64)
_Q6_DAYS = list(range(2451000, 2451031))


def _probe_cases():
    """name -> (stream keys, build keys, key type, path that must run,
    filter keeping only the first N build rows or None)."""
    rng = np.random.default_rng(11)
    ri = lambda lo, hi, n: [int(x) for x in rng.integers(lo, hi, n)]
    holes = lambda ks: [None if rng.random() < 0.1 else k for k in ks]
    it, lt = T.IntegerType(), T.LongType()
    return {
        "unique_dense": (ri(90, 160, 64), list(range(100, 150)), it,
                         "direct", None),
        "dense_dups": (ri(0, 25, 64), ri(0, 25, 60), it, "direct", None),
        "nulls_both_sides": (holes(ri(0, 25, 64)), holes(ri(0, 25, 60)), it,
                             "direct", None),
        "stream_out_of_range": (
            [I32.min, I32.max, 9, 41, 10, 40, -1, 0] + ri(0, 50, 56),
            list(range(10, 41)), it, "direct", None),
        # key - kmin wraps for the far keys unless the range test is first
        "int64_wrap": (
            [I64.min, I64.max, I64.min + 1, 5_000_000_007,
             -5_000_000_007, 0] + ri(5_000_000_000, 5_000_000_040, 58),
            [5_000_000_000 + k for k in range(0, 40, 2)] * 2, lt,
            "direct", None),
        # table entries past kmax wrap around the dtype's end
        "build_at_int32_max": ([I32.max, I32.max - 5, I32.min, 7] * 16,
                               [I32.max - k for k in range(6)], it,
                               "direct", None),
        "build_at_int64_min": ([I64.min, I64.min + 3, I64.max, 0] * 16,
                               [I64.min + k for k in range(5)], lt,
                               "direct", None),
        "int64_whole_range": ([I64.min, I64.max, 0, 1] * 16,
                              [I64.min, I64.max, 0], lt, "search", None),
        "negative": (ri(-30, 20, 64), ri(-20, 11, 40), it, "direct", None),
        # a dense int16 range wider than int16 itself holds
        "int16_wide": ([-30000, 30000, 0, 12, -12, 29999] * 10,
                       [-30000, 0, 30000, 0], T.ShortType(), "direct", None),
        "one_row_build": (ri(0, 6, 64), [3], it, "direct", None),
        "empty_build": (ri(0, 6, 64), [], it, "search", None),
        "all_null_build": (ri(0, 6, 64), [None] * 9, it, "search", None),
        "sparse_build": (ri(0, 50, 60) + [7, 40_000_000, 900_000_000, 8],
                         [7, 40_000_000, 900_000_000, 7], it, "search",
                         None),
        # q6's innermost join: date_dim filtered to one month keeps its
        # capacity (2^17 slots, 31 keys)
        "capacity_far_above_rows": (
            ri(2450990, 2451050, 64),
            _Q6_DAYS + list(range(2415022, 2415022 + 73_049 - 31)), it,
            "direct", 31),
    }


_PROBE_CASES = _probe_cases()


@pytest.mark.parametrize("jt", ["inner", "left", "semi", "anti", "full"])
@pytest.mark.parametrize("case", list(_PROBE_CASES))
def test_direct_and_search_probe_agree(case, jt, monkeypatch):
    from spark_rapids_tpu.exec import joins as J
    from spark_rapids_tpu.exec.basic import FilterExec
    from spark_rapids_tpu.obs.registry import get_registry
    lkeys, rkeys, ktype, path, keep = _PROBE_CASES[case]
    lschema = T.Schema([T.StructField("lk", ktype, True),
                        T.StructField("lv", T.LongType(), True)])
    rschema = T.Schema([T.StructField("rk", ktype, True),
                        T.StructField("rv", T.LongType(), True)])
    left = LocalScanExec.from_pydict(
        {"lk": lkeys, "lv": list(range(len(lkeys)))}, lschema,
        rows_per_batch=37)          # 37 + 27 rows: padding in both batches
    right = LocalScanExec.from_pydict(
        {"rk": rkeys, "rv": list(range(len(rkeys)))}, rschema)
    if keep is not None:
        right = FilterExec(col("rv") < lit(keep), right)
    plan = JoinExec(left, right, [col("lk")], [col("rk")], jt)

    # every direct probe is checked against the search probe of the same
    # stream batch and build: same runs, same counts, same total
    seen, probes = {}, []
    real_table, real_direct = J._jit_build_table, J._jit_probe_direct

    def spy_table(prep, size):
        seen["prep"], seen["size"] = prep, size
        return real_table(prep, size)

    def checked_direct(lb, build, lkey, join_type):
        (start, cnt, perm, out_cnt), total = real_direct(
            lb, build, lkey, join_type)
        (s2, c2, p2, o2), t2 = J._jit_probe_fast(
            lb, seen["prep"], lkey, join_type)
        hit = np.asarray(c2) > 0   # a run's start means nothing at cnt 0
        assert np.array_equal(cnt, c2) and np.array_equal(out_cnt, o2)
        assert np.array_equal(np.asarray(start)[hit], np.asarray(s2)[hit])
        assert np.array_equal(perm, p2) and int(total) == int(t2)
        probes.append(int(total))
        return (start, cnt, perm, out_cnt), total
    monkeypatch.setattr(J, "_jit_build_table", spy_table)
    monkeypatch.setattr(J, "_jit_probe_direct", checked_direct)

    before = get_registry().counters()
    rows = collect_device(plan)
    moved = get_registry().counters_since(before)
    other = "search" if path == "direct" else "direct"
    assert moved.get(f"join.probe.{path}") == 2          # 2 stream batches
    assert f"join.probe.{other}" not in moved
    if path == "direct":
        assert len(probes) == 2
        assert moved["join.build.table_entries"] == seen["size"]
        if keep is not None:
            assert seen["size"] == 32 and seen["prep"][0].shape == (1 << 17,)
    else:
        assert "join.build.table_entries" not in moved and not probes

    # the same plan, made to search: the same rows in the same order
    monkeypatch.setattr(J, "direct_table_size", lambda *a: None)
    assert collect_device(plan) == rows
    assert sorted(collect_host(plan), key=repr) == sorted(rows, key=repr)


def test_direct_table_rule():
    from spark_rapids_tpu.ops.join import direct_table_size
    assert direct_table_size(31, 2451000, 2451030, 1 << 17) == 32
    assert direct_table_size(1, 5, 5, 8) == 8
    assert direct_table_size(0, I32.max, I32.max, 8) is None
    # q6's filtered item (102k ids in 2^15 rows) and customer at 2^19
    assert direct_table_size(30_000, 1, 102_000, 1 << 15) == 1 << 17
    assert direct_table_size(500_000, 1, 500_000, 1 << 19) == 1 << 19
    # the floor, then eight entries a build slot
    assert direct_table_size(3, 0, (1 << 20) - 1, 8) == 1 << 20
    assert direct_table_size(3, 0, 1 << 20, 8) is None
    assert direct_table_size(3, 0, 1 << 20, 1 << 18) == 1 << 21
    assert direct_table_size(3, 0, 1 << 21, 1 << 18) is None
    assert direct_table_size(2, I64.min, I64.max, 1 << 20) is None
