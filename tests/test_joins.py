"""Join differential tests: device sort-merge kernel vs CPU oracle.

Mirrors the reference's join coverage (integration_tests join_test.py:
all join types x key types x nulls; tests/GpuHashJoinSuite) with fuzzed
key data including nulls, NaN, -0.0 and duplicate keys.
"""
import datetime as dt

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
        # ``counts``: [total, aligned] (ops/join.probe_counts)
        (start, cnt, perm, out_cnt), counts = real_direct(
            lb, build, lkey, join_type)
        (s2, c2, p2, o2), counts2 = J._jit_probe_fast(
            lb, seen["prep"], lkey, join_type)
        hit = np.asarray(c2) > 0   # a run's start means nothing at cnt 0
        assert np.array_equal(cnt, c2) and np.array_equal(out_cnt, o2)
        assert np.array_equal(np.asarray(start)[hit], np.asarray(s2)[hit])
        assert np.array_equal(perm, p2) and np.array_equal(counts, counts2)
        probes.append(int(counts[0]))
        return (start, cnt, perm, out_cnt), counts
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


# ------------------------------------------------------------------
# The aligned gather plan (ops/join.join_indices_from_probe, PR 43): a
# stream batch whose every live row comes out exactly once keeps its own
# columns in their slots and gathers only the build's.  The executor
# picks it batch by batch from the flag each probe returns beside its
# total; the batch it hands on is the expanding plan's, array for array.

#: probe kind -> (key fields, key tuple of build row ``i``, the counter
#: that says the kind ran)
_ALIGNED_PROBES = {
    "direct": ([T.IntegerType()], lambda i: (100 + 2 * i,),
               "join.probe.direct"),
    "search": ([T.LongType()], lambda i: (7 + 1_000_003 * i,),
               "join.probe.search"),
    "packed": ([T.IntegerType(), T.LongType()],
               lambda i: (i % 10, 5_000_000_000 + i // 10),
               "join.keys.packed"),
    "sort": ([T.StringType()], lambda i: (f"k{i}",), "join.probe.sorted"),
}
_N_BUILD, _BATCH = 150, 64


def _aligned_plan(probe, jt, fill, stream_ids, build_ids=None):
    """Two stream batches of 64 slots (``fill`` "sparse": a filter keeps
    the first 20 rows of each, so the gather's capacity is under the
    batch's) joined to one build batch; ``stream_ids`` / ``build_ids``
    name build rows (None: a NULL key; >= ``_N_BUILD``: in no build)."""
    from spark_rapids_tpu.exec.basic import FilterExec
    ktypes, key_of, _ = _ALIGNED_PROBES[probe]
    build_ids = list(range(_N_BUILD)) if build_ids is None else build_ids

    def side(p, ids, extra):
        keys = [key_of(i) if i is not None else (None,) * len(ktypes)
                for i in ids]
        fields = [T.StructField(f"{p}k{j}", t, True)
                  for j, t in enumerate(ktypes)]
        data = {f.name: [k[j] for k in keys] for j, f in enumerate(fields)}
        for name, (t, values) in extra.items():
            fields.append(T.StructField(p + name, t, True))
            data[p + name] = values
        return data, T.Schema(fields), [col(f"{p}k{j}")
                                        for j in range(len(ktypes))]
    n = len(stream_ids)
    ldata, lschema, lkeys = side("l", stream_ids, {
        "v": (T.LongType(), [None if i % 9 == 4 else i * 7 - 300
                             for i in range(n)]),
        "s": (T.StringType(), [None if i % 5 == 2 else f"s{i % 13}" * 2
                               for i in range(n)]),
        "pos": (T.IntegerType(), [i % _BATCH for i in range(n)])})
    rdata, rschema, rkeys = side("r", build_ids, {
        "v": (T.DoubleType(), [None if i % 6 == 1 else i / 4
                               for i in range(len(build_ids))]),
        "s": (T.StringType(), [None if i % 7 == 3 else f"reason {i}"
                               for i in range(len(build_ids))])})
    left = LocalScanExec.from_pydict(ldata, lschema, rows_per_batch=_BATCH)
    if fill == "sparse":
        left = FilterExec(col("lpos") < lit(20), left)
    right = LocalScanExec.from_pydict(rdata, rschema)
    return JoinExec(left, right, lkeys, rkeys, jt)


def _run_checking_gathers(plan, monkeypatch):
    """Collect ``plan`` on the device with every aligned ``join_gather``
    launch checked against the expanding plan of the same arguments.
    Returns (rows, [(aligned, cl, out_cap) a launch], counters moved)."""
    import jax
    from spark_rapids_tpu.exec import joins as J
    from spark_rapids_tpu.obs.registry import get_registry
    real, launches = J._jit_gather, []

    def checked(lb, rb, probe_arrays, cl, join_type, out_cap, *rest,
                aligned=False, **kw):
        run = lambda a: real(lb, rb, probe_arrays, cl, join_type, out_cap,
                             *rest, aligned=a, **kw)
        out = run(aligned)
        launches.append((aligned, cl, out_cap))
        if aligned:
            a, b = (jax.tree_util.tree_leaves(o) for o in (out, run(False)))
            assert len(a) == len(b)
            for x, y in zip(a, b):
                assert x.dtype == y.dtype and x.shape == y.shape
                assert np.array_equal(x, y, equal_nan=True)
        return out
    monkeypatch.setattr(J, "_jit_gather", checked)
    before = get_registry().counters()
    rows = collect_device(plan)
    return rows, launches, get_registry().counters_since(before)


@pytest.mark.parametrize("fill", ["full", "sparse"])
@pytest.mark.parametrize("probe", list(_ALIGNED_PROBES))
@pytest.mark.parametrize("jt", ["left", "inner", "full", "semi"])
def test_aligned_gather_is_the_expanding_one_array_for_array(
        jt, probe, fill, monkeypatch):
    rng = np.random.default_rng(43)
    ids = [int(x) for x in rng.integers(0, _N_BUILD, 2 * _BATCH)]
    if jt in ("left", "full"):      # NULL keys and keys in no build
        ids = [None if i % 10 == 3 else _N_BUILD + i if i % 10 == 7 else x
               for i, x in enumerate(ids)]
    plan = _aligned_plan(probe, jt, fill, ids)
    rows, launches, moved = _run_checking_gathers(plan, monkeypatch)

    live = 20 if fill == "sparse" else _BATCH
    assert launches == [(True, _BATCH, 32 if fill == "sparse" else _BATCH)] * 2
    assert moved["join.gather.aligned"] == 2
    assert moved["join.probe.rows_out"] == 2 * live
    assert moved.get(_ALIGNED_PROBES[probe][2]) in (1, 2)
    # one fetch a build that streams, ONE a flush (the flag rides with the
    # totals), one for the full join's tail
    assert moved["span.fetch@JoinExec.count"] == \
        (probe != "sort") + 1 + (jt == "full")
    assert len(rows) == 2 * live + (
        _N_BUILD - len({i for b in (ids[:live], ids[_BATCH:_BATCH + live])
                        for i in b if i is not None and i < _N_BUILD})
        if jt == "full" else 0)
    assert sorted(collect_host(plan), key=repr) == sorted(rows, key=repr)


@pytest.mark.parametrize("probe", list(_ALIGNED_PROBES))
@pytest.mark.parametrize("case,jt,aligned", [
    ("duplicated_build_key", "left", 0),   # one stream row a batch expands
    ("inner_drops_a_row", "inner", 0),     # one stream row a batch unmatched
    ("one_batch_of_two", "left", 1)])      # the flag is a batch's own
def test_a_batch_that_expands_or_drops_keeps_the_expanding_plan(
        case, jt, aligned, probe, monkeypatch):
    rng = np.random.default_rng(7)
    ids = [int(x) for x in rng.integers(1, _N_BUILD, 2 * _BATCH)]
    build_ids = list(range(_N_BUILD))
    hit = (5,) if case == "one_batch_of_two" else (5, _BATCH + 5)
    for at in hit:
        ids[at] = _N_BUILD + 1 if case == "inner_drops_a_row" else 0
    if case != "inner_drops_a_row":
        build_ids.append(0)         # build row 0's key, a second time
    plan = _aligned_plan(probe, jt, "full", ids, build_ids)
    rows, launches, moved = _run_checking_gathers(plan, monkeypatch)
    assert moved.get("join.gather.aligned", 0) == aligned
    assert [a for a, _cl, _cap in launches] == [False, bool(aligned)]
    dups, drops = (0, 2) if case == "inner_drops_a_row" else (len(hit), 0)
    assert len(rows) == moved["join.probe.rows_out"] == \
        2 * _BATCH + dups - drops
    assert sorted(collect_host(plan), key=repr) == sorted(rows, key=repr)


def test_aligned_gather_cleans_what_lies_under_a_null():
    """A stream column may hold anything under a NULL or past its rows
    (an expression's result does): the aligned plan zeroes it as the
    expanding plan's gather does, and pads where the output's capacity
    is over the batch's."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.batch import ColumnBatch
    from spark_rapids_tpu.columnar.column import DeviceColumn
    from spark_rapids_tpu.host.batch import HostBatch
    from spark_rapids_tpu.ops.join import (gather_join_output,
                                           join_indices_from_probe,
                                           join_probe, probe_counts)
    clean = HostBatch.from_pydict({
        "lk": [3, None, 5, 1, 9, 3], "lv": [10, 11, None, 13, 14, 15],
        "ls": ["a", None, "ccc", "dd", None, "f"]}, L_SCHEMA).to_device()
    dirty = ColumnBatch([
        DeviceColumn(jnp.where(c.validity[(...,) + (None,) * (c.data.ndim - 1)],
                               c.data, 77).astype(c.data.dtype),
                     c.validity, c.dtype,
                     None if c.lengths is None
                     else jnp.where(c.validity, c.lengths, 1))
        for c in clean.columns], clean.num_rows, clean.schema)
    rb = HostBatch.from_pydict({"rk": [1, 3, 4, 5], "rv": [.5, None, 2., 3.]},
                               R_SCHEMA).to_device()
    probe, total = join_probe(dirty, rb, (0,), (0,), "left")
    assert list(np.asarray(probe_counts(probe[3], dirty.row_mask(), total))) \
        == [6, 1]
    for out_cap in (dirty.capacity, 4 * dirty.capacity):
        outs = [gather_join_output(
            dirty, rb, *join_indices_from_probe(
                dirty.capacity, probe, "left", out_cap, aligned=aligned),
            None, True)
            for aligned in (False, True)]
        a, b = (jax.tree_util.tree_leaves(o) for o in outs)
        assert len(a) == len(b)
        for x, y in zip(a, b):
            assert x.dtype == y.dtype and np.array_equal(x, y, equal_nan=True)
        assert not np.asarray(outs[1].columns[0].data)[1]   # the 77 is gone


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


# ------------------------------------------------------------------
# probe_fast's two branches (ops/join.py): a merge of the stream batch
# into the sorted build, or steps through it.  One contract: the same
# arrays from both, whatever the shapes would choose.

def _merge_cases():
    """name -> (key types, stream key tuples, build key tuples).  Batches
    are padded to a capacity above their rows; the build's padding slots
    are rewritten to the dtype's maximum by its preparation."""
    it, lt = T.IntegerType(), T.LongType()
    one = lambda ks: [(k,) for k in ks]
    return {
        "build_runs_int32": ((it,), one([5, 7, 7, 8, 1, 9, 3, 5, 5, 5, 2]),
                             one([5] * 9 + [7, 7, 3] + [8] * 4 + [5, 1])),
        "build_runs_int64": ((lt,), one([5, 1 << 40, 7, -(1 << 40), 5]),
                             one([1 << 40] * 5 + [5, 5, -(1 << 40), 6] * 3)),
        "stream_below_and_above": (
            (it,), one([I32.min, -1, 9, 10, 25, 40, 41, I32.max]),
            one(list(range(10, 41, 5)) * 2)),
        # a genuine maximum-valued key sits below nv, the padding above it
        "int32_max_beside_padding": (
            (it,), one([I32.max, I32.max - 1, 0, I32.max]),
            one([I32.max, 3, I32.max, I32.max - 2, None])),
        "int64_max_beside_padding": (
            (lt,), one([I64.max, I64.min, I64.max - 1, 0]),
            one([I64.max, I64.min, I64.max, None, 0])),
        "empty_build": ((lt,), one([1, 2, I64.max, None]), []),
        "all_null_stream": ((it,), one([None] * 9), one([0, 1, 2, 2])),
        "all_null_both": ((lt,), one([None] * 5), one([None] * 3)),
        # packed: (6, 11) and (1, 15) each hold ONE key outside the
        # build's ranges ([2, 5] x [10, 14]) and would wrap into a run
        "two_keys_one_out_of_range": (
            (it, lt),
            [(2, 10), (6, 11), (1, 15), (5, 14), (3, 9), (3, None),
             (None, 12), (4, 12), (2, 10), (5, 15)],
            [(2, 10), (5, 14), (4, 12), (4, 12), (3, 11), (None, 10),
             (2, 10), (4, None)]),
        "two_keys_wide": ((lt, lt),
                          [(1 << 30, 1 << 31), (0, 0), (1 << 30, 0), (7, 7)],
                          [(0, 0), (1 << 30, 1 << 31), (7, 7), (7, 7)]),
    }


_MERGE_CASES = _merge_cases()


def _probe_batches(ktypes, lrows, rrows, lcap=None, rcap=None):
    """Device batches of key columns plus a payload, padded past their
    rows, and the key column indices."""
    from spark_rapids_tpu.columnar.batch import round_capacity
    from spark_rapids_tpu.host.batch import HostBatch

    def batch(prefix, rows, cap):
        schema = T.Schema([T.StructField(f"{prefix}k{i}", t, True)
                           for i, t in enumerate(ktypes)]
                          + [T.StructField(prefix + "v", T.LongType(), True)])
        cols = {f"{prefix}k{i}": [r[i] for r in rows]
                for i in range(len(ktypes))}
        cols[prefix + "v"] = list(range(len(rows)))
        return HostBatch.from_pydict(cols, schema).to_device(
            capacity=cap or 2 * round_capacity(len(rows) + 1))
    return (batch("l", lrows, lcap), batch("r", rrows, rcap),
            tuple(range(len(ktypes))))


def _assert_branches_agree(lb, rb, keys, jt, monkeypatch):
    """Both branches of probe_fast on one stream batch and one prepared
    build: equal but for ``start`` where ``cnt`` is 0.  Returns the
    merge's arrays."""
    from spark_rapids_tpu.ops import join as OJ
    if len(keys) > 1:
        build, packing = OJ.build_prepare_packed(rb, keys)
        lkey = keys
    else:
        build, packing, lkey = OJ.build_prepare_fast(rb, 0), None, 0
    got = {}
    for branch, ratio in (("merge", 1 << 40), ("steps", 0)):
        monkeypatch.setattr(OJ, "MERGE_MAX_BUILD_RATIO", ratio)
        assert OJ.probe_merges(lb.capacity, rb.capacity) == (branch == "merge")
        (start, cnt, perm, out_cnt, none), total = OJ.probe_fast(
            lb, lkey, *build, jt, packing)
        assert none is None
        got[branch] = [np.asarray(x) for x in (start, cnt, perm, out_cnt)] \
            + [int(total)]
    (s1, c1, p1, o1, t1), (s2, c2, p2, o2, t2) = got["merge"], got["steps"]
    assert np.array_equal(c1, c2) and np.array_equal(o1, o2) and t1 == t2
    assert np.array_equal(p1, p2)
    assert np.array_equal(s1[c1 > 0], s2[c1 > 0])
    assert s1.dtype == s2.dtype == c1.dtype == c2.dtype == np.int32
    assert s1.shape == c1.shape == (lb.capacity,)
    return got["merge"]


@pytest.mark.parametrize("jt", ["inner", "left", "semi", "anti", "full"])
@pytest.mark.parametrize("case", list(_PROBE_CASES))
def test_merge_and_steps_agree(case, jt, monkeypatch):
    from spark_rapids_tpu.columnar.batch import round_capacity
    lkeys, rkeys, ktype, _, keep = _PROBE_CASES[case]
    # a filtered build keeps its capacity: few rows, many padding slots
    rcap = None if keep is None else round_capacity(len(rkeys))
    lb, rb, keys = _probe_batches(
        (ktype,), [(k,) for k in lkeys],
        [(k,) for k in (rkeys if keep is None else rkeys[:keep])], rcap=rcap)
    start, cnt, perm, out_cnt, total = _assert_branches_agree(
        lb, rb, keys, jt, monkeypatch)
    # the runs themselves, from the keys: every stream key's matches
    build = [k for k in (rkeys if keep is None else rkeys[:keep])
             if k is not None]
    want = [0 if k is None else build.count(k) for k in lkeys]
    assert cnt[:len(lkeys)].tolist() == want and not cnt[len(lkeys):].any()
    skey = sorted(build)
    for i, (k, n) in enumerate(zip(lkeys, want)):
        if n:
            assert skey[start[i]:start[i] + n] == [k] * n


@pytest.mark.parametrize("jt", ["inner", "left", "anti"])
@pytest.mark.parametrize("case", list(_MERGE_CASES))
def test_merge_and_steps_agree_on_the_edges(case, jt, monkeypatch):
    ktypes, lrows, rrows = _MERGE_CASES[case]
    lb, rb, keys = _probe_batches(ktypes, lrows, rrows)
    assert lb.capacity > len(lrows) and rb.capacity > len(rrows)
    start, cnt, perm, out_cnt, total = _assert_branches_agree(
        lb, rb, keys, jt, monkeypatch)
    whole = [r for r in rrows if None not in r]
    want = [0 if None in r else whole.count(r) for r in lrows]
    assert cnt[:len(lrows)].tolist() == want and not cnt[len(lrows):].any()
    n = len(lrows)
    assert total == {"inner": sum(want), "left": sum(max(w, 1) for w in want),
                     "anti": sum(w == 0 for w in want)}[jt]
    assert not out_cnt[n:].any()
    # perm leads from a run in the sorted build back to the build's rows
    for i, (r, w) in enumerate(zip(lrows, want)):
        if w:
            rows = sorted(int(perm[start[i] + k]) for k in range(w))
            assert rows == [j for j, b in enumerate(rrows) if b == r]


def test_probe_shape_rule():
    """The branch is a function of the two capacities alone."""
    from spark_rapids_tpu.ops import join as OJ
    assert OJ.probe_merges(1 << 20, 1 << 22)        # q93 at SF10
    assert OJ.probe_merges(1 << 20, 1 << 19)
    assert OJ.probe_merges(1 << 20, 1 << 17)
    assert OJ.probe_merges(1 << 19, 1 << 15)        # q93 at SF0.1
    assert not OJ.probe_merges(1 << 14, 1 << 22)
    # a remnant against a fact-sized build must not sort 4M rows
    assert not OJ.probe_merges(1 << 10, 1 << 22)
    r = OJ.MERGE_MAX_BUILD_RATIO
    assert 16 <= r <= 64
    assert OJ.probe_merges(1 << 10, r << 10)
    assert not OJ.probe_merges(1 << 10, (r << 10) + 1)


@pytest.mark.parametrize("build_rows,merged", [(200, True), (5000, False)])
def test_executor_counts_the_merged_batches(build_rows, merged, monkeypatch):
    """``join.probe.search.merged`` moves with ``join.probe.search`` where
    the shapes take the merge, and not where they take the steps: the
    executor applies probe_fast's rule to the capacities probe_fast sees."""
    from spark_rapids_tpu.exec import joins as J
    from spark_rapids_tpu.obs.registry import get_registry
    from spark_rapids_tpu.ops import join as OJ
    schema = lambda p: T.Schema([T.StructField(p + "k", T.LongType(), True),
                                 T.StructField(p + "v", T.LongType(), True)])
    rk = [k * 1_000_003 for k in range(build_rows)]     # sparse: searched
    lk = [rk[7], 5, rk[199], rk[7], None, rk[0], -3, rk[150]]
    left = LocalScanExec.from_pydict(
        {"lk": lk, "lv": list(range(len(lk)))}, schema("l"))
    right = LocalScanExec.from_pydict(
        {"rk": rk, "rv": list(range(len(rk)))}, schema("r"))
    plan = JoinExec(left, right, [col("lk")], [col("rk")], "inner")
    asked = []
    monkeypatch.setattr(J, "probe_merges", lambda cl, cr: (
        asked.append((cl, cr)), OJ.probe_merges(cl, cr))[1])
    before = get_registry().counters()
    rows = collect_device(plan)
    moved = get_registry().counters_since(before)
    assert sorted(r[1] for r in rows) == [0, 2, 3, 5, 7]
    assert moved.get("join.probe.search") == 1
    assert moved.get("join.probe.search.merged", 0) == int(merged)
    (cl, cr), = asked           # the capacities probe_fast sees
    assert cl == 8 and cr >= build_rows
    assert (cr <= OJ.MERGE_MAX_BUILD_RATIO * cl) == merged


# ------------------------------------------------------------------
# A join on several integral keys streams: the keys are packed into one
# mixed-radix int64 key from the build side's own ranges (ops/join.py
# build_prepare_packed), and the single-key probes do the rest.

def _packed_cases():
    """name -> (key types, stream key tuples, build key tuples, path that
    must run, filter keeping only the first N build rows or None).  Every
    stream side reaches one below and one above the build's range of
    each key, so a packing that subtracts before it tests the range
    wraps into a neighbouring run and invents a match."""
    rng = np.random.default_rng(33)
    it, lt, dt = T.IntegerType(), T.LongType(), T.DateType()

    def draw(n, *ranges, holes=0.08):
        return [tuple(None if rng.random() < holes
                      else int(rng.integers(lo, hi + 1)) for lo, hi in ranges)
                for _ in range(n)]
    big = 1 << 40
    wide = [-big, -3, 0, 7, big, big + 1]

    def far(n):       # an int32 key beside an int64 one: 11 x 2^41 pairs
        return [(None if rng.random() < 0.08 else int(rng.integers(-6, 6)),
                 None if rng.random() < 0.08 else int(rng.choice(wide)))
                for _ in range(n)]
    grid = [(a, b) for a in range(-3, 4) for b in range(10, 15)]
    return {
        # the stream holds (a, hi + 1) and (a, lo - 1) for every a: the
        # build holds their wrapped neighbours (a + 1, lo) and (a - 1, hi)
        "int_int_dense": ((it, it),
                          [(a, b) for a in range(-4, 5) for b in (9, 15)]
                          + draw(46, (-4, 4), (9, 15)),
                          grid + draw(25, (-3, 3), (10, 14)), "direct", None),
        "int_long_sparse": ((it, lt),
                            [(-6, -big - 1), (5, big + 2), (-5, big + 2),
                             (6, -big)] + far(60),
                            [(-5, -big), (5, big + 1), (-4, -big)] + far(50),
                            "search", None),
        "date_int": ((dt, it),
                     draw(64, (10956, 10990), (-2, 3)),
                     draw(50, (10957, 10989), (-1, 2)), "direct", None),
        "three_keys": ((it, lt, it),
                       draw(64, (0, 4), (99, 103), (-2, 1)),
                       draw(60, (1, 3), (100, 102), (-1, 0)), "direct",
                       None),
        "long_long_sparse": ((lt, lt),
                             draw(64, (-2, 1 << 21), (0, 1 << 20)),
                             draw(50, (0, 1 << 21), (1, 1 << 20)), "search",
                             None),
        "one_row_build": ((it, lt), draw(64, (0, 3), (5, 7)),
                          [(2, 6), (1, 5), (3, 7)], "direct", 1),
        "empty_build": ((it, lt), draw(64, (0, 3), (5, 7)),
                        [(2, 6), (1, 5)], "search", 0),
        "null_keyed_build": ((it, it), draw(64, (0, 3), (5, 7)),
                             [(None, 6), (2, None), (None, None)], "search",
                             None),
    }


_PACKED_CASES = _packed_cases()


def _packed_sides(case):
    from spark_rapids_tpu.exec.basic import FilterExec
    ktypes, lrows, rrows, path, keep = _PACKED_CASES[case]
    names = [f"k{i}" for i in range(len(ktypes))]
    lschema = T.Schema([T.StructField("l" + n, t, True)
                        for n, t in zip(names, ktypes)]
                       + [T.StructField("lv", T.LongType(), True)])
    rschema = T.Schema([T.StructField("r" + n, t, True)
                        for n, t in zip(names, ktypes)]
                       + [T.StructField("rv", T.LongType(), True)])

    def side(prefix, rows, schema, **kw):
        data = {prefix + n: [r[i] for r in rows]
                for i, n in enumerate(names)}
        data[prefix + "v"] = list(range(len(rows)))
        return LocalScanExec.from_pydict(data, schema, **kw)
    left = side("l", lrows, lschema, rows_per_batch=37)
    right = side("r", rrows, rschema)
    if keep is not None:
        right = FilterExec(col("rv") < lit(keep), right)
        rrows = rrows[:keep]
    return (left, right, [col("l" + n) for n in names],
            [col("r" + n) for n in names], lrows, rrows, path)


def _pandas_pairs(lrows, rrows, jt):
    """(lv, rv) of every output row, from pandas: an inner merge of the
    rows whose keys are all there, and the join types' rules around it."""
    import pandas as pd
    keys = list(range(len(lrows[0])))

    def frame(rows, name):
        df = pd.DataFrame([list(r) for r in rows], columns=keys,
                          dtype=object)
        df[name] = range(len(rows))
        return df.dropna(subset=keys)
    m = frame(lrows, "lv").merge(frame(rrows, "rv"), on=keys)
    inner = sorted(zip(m.lv.tolist(), m.rv.tolist()))
    lhit, rhit = {a for a, _ in inner}, {b for _, b in inner}
    lmiss = [(a, None) for a in range(len(lrows)) if a not in lhit]
    rmiss = [(None, b) for b in range(len(rrows)) if b not in rhit]
    return {"inner": inner, "left": inner + lmiss,
            "right": inner + rmiss, "full": inner + lmiss + rmiss,
            "semi": [(a, None) for a in sorted(lhit)],
            "anti": lmiss}[jt]


@pytest.mark.parametrize("jt", ["inner", "left", "right", "semi", "anti",
                                "full"])
@pytest.mark.parametrize("case", list(_PACKED_CASES))
def test_packed_multi_key_join(case, jt, monkeypatch):
    from spark_rapids_tpu.obs.registry import get_registry
    left, right, lk, rk, lrows, rrows, path = _packed_sides(case)
    plan = JoinExec(left, right, lk, rk, jt)
    before = get_registry().counters()
    rows = collect_device(plan)
    moved = get_registry().counters_since(before)
    assert moved.get("join.keys.packed") == 1
    assert "join.keys.unpackable" not in moved
    assert "join.probe.sorted" not in moved
    if jt == "right":       # runs side-swapped: the stream is `right`
        assert moved.get("join.probe.direct", 0) \
            + moved.get("join.probe.search", 0) == 1
    else:
        other = "search" if path == "direct" else "direct"
        assert moved.get(f"join.probe.{path}") == 2      # 2 stream batches
        assert f"join.probe.{other}" not in moved
    # the probes' totals: every row but a full join's unmatched build rows
    tail = sum(1 for a, _ in _pandas_pairs(lrows, rrows, "full")
               if a is None) if jt == "full" else 0
    assert moved.get("join.probe.rows_out", 0) == len(rows) - tail
    assert moved.get("join.full.unmatched_rows", 0) == tail

    nk = len(lk)
    lv = nk
    rv = None if jt in ("semi", "anti") else 2 * nk + 1
    got = sorted(((r[lv], None if rv is None else r[rv]) for r in rows),
                 key=repr)
    assert got == sorted(_pandas_pairs(lrows, rrows, jt), key=repr)

    def keys(cells):        # a date comes back as one, it went in as days
        return tuple((c - dt.date(1970, 1, 1)).days
                     if isinstance(c, dt.date) else c for c in cells)
    for r in rows:          # the columns of a row come from its rows
        if r[lv] is not None:
            assert keys(r[:nk]) == lrows[r[lv]]
        if rv is not None and r[rv] is not None:
            assert keys(r[nk + 1:rv]) == rrows[r[rv]]

    # the same plan on the sort path, and on the host: the same rows
    assert sorted(collect_host(plan), key=repr) == sorted(rows, key=repr)
    monkeypatch.setattr(JoinExec, "_use_fast_path", lambda self: False)
    before = get_registry().counters()
    assert sorted(collect_device(plan), key=repr) == sorted(rows, key=repr)
    moved = get_registry().counters_since(before)
    assert moved.get("join.probe.sorted") and "join.keys.packed" not in moved


@pytest.mark.parametrize("stream_batch", [7, 16, 64])
def test_two_key_full_outer_join_over_stream_batches(stream_batch):
    """q51's join: both sides unique on (item, day), some pairs on both
    sides, some on one, NULL keys on both; the stream comes in several
    batches, so a build row is matched by whichever batch holds its
    pair and the tail is what no batch matched."""
    from spark_rapids_tpu.obs.registry import get_registry
    rng = np.random.default_rng(51)
    pairs = [(int(i), int(d)) for i in range(1, 9) for d in range(10957, 10963)]
    pick = rng.permutation(len(pairs))
    lrows = [pairs[i] for i in pick[:30]] + [(None, 10957), (3, None)]
    rrows = [pairs[i] for i in pick[18:]] + [(None, 10957), (None, None)]
    schema = lambda p: T.Schema([  # noqa: E731
        T.StructField(p + "item", T.IntegerType(), True),
        T.StructField(p + "day", T.DateType(), True),
        T.StructField(p + "v", T.LongType(), True)])

    def side(p, rows, **kw):
        return LocalScanExec.from_pydict(
            {p + "item": [r[0] for r in rows], p + "day": [r[1] for r in rows],
             p + "v": list(range(len(rows)))}, schema(p), **kw)
    plan = JoinExec(side("l", lrows, rows_per_batch=stream_batch),
                    side("r", rrows), [col("litem"), col("lday")],
                    [col("ritem"), col("rday")], "full")
    before = get_registry().counters()
    rows = collect_device(plan)
    moved = get_registry().counters_since(before)
    want = _pandas_pairs(lrows, rrows, "full")
    assert sorted(((r[2], r[5]) for r in rows), key=repr) \
        == sorted(want, key=repr)
    matched = sum(1 for a, b in want if a is not None and b is not None)
    left_only = sum(1 for a, b in want if b is None)
    right_only = sum(1 for a, b in want if a is None)
    assert (matched, left_only, right_only) == (12, 20, 20)
    assert moved["join.keys.packed"] == 1
    assert moved["join.full.unmatched_rows"] == right_only
    assert moved["join.probe.rows_out"] == matched + left_only
    assert moved["join.probe.direct"] == -(-len(lrows) // stream_batch)
    # NULL-extended on the side that has no partner
    for r in rows:
        assert (r[0] is None and r[1] is None) or r[2] is not None
    assert sorted(collect_host(plan), key=repr) == sorted(rows, key=repr)


@pytest.mark.parametrize("why", ["product_past_int64", "string_key",
                                 "double_key", "boolean_key"])
def test_unpackable_keys_stay_on_the_sort_path(why):
    from spark_rapids_tpu.obs.registry import get_registry
    ktype, lvals, rvals = {
        "product_past_int64": (T.LongType(), [I64.min, 5, I64.max, 0],
                               [0, I64.max, I64.min, 5]),
        "string_key": (T.StringType(), ["a", "b", None, "c"],
                       ["c", "b", "d", None]),
        "double_key": (T.DoubleType(), [1.5, 2.0, None, -0.0],
                       [0.0, 2.0, 3.5, None]),
        "boolean_key": (T.BooleanType(), [True, False, None, True],
                        [True, None, True, True]),
    }[why]
    first = [1, 2, 3, 4]
    lschema = T.Schema([T.StructField("a", T.LongType(), True),
                        T.StructField("b", ktype, True)])
    rschema = T.Schema([T.StructField("c", T.LongType(), True),
                        T.StructField("d", ktype, True)])
    if why == "product_past_int64":     # both keys span the whole int64
        first, rfirst = lvals, rvals
    else:
        rfirst = [4, 2, 9, 3]
    left = LocalScanExec.from_pydict({"a": first, "b": lvals}, lschema)
    right = LocalScanExec.from_pydict({"c": rfirst, "d": rvals}, rschema)
    plan = JoinExec(left, right, [col("a"), col("b")],
                    [col("c"), col("d")], "left")
    before = get_registry().counters()
    rows = collect_device(plan)
    moved = get_registry().counters_since(before)
    assert moved.get("join.keys.unpackable") == 1
    assert moved.get("join.probe.sorted") == 1
    assert not any(k in moved for k in (
        "join.keys.packed", "join.probe.direct", "join.probe.search"))
    assert sorted(rows, key=repr) == sorted(collect_host(plan), key=repr)
    assert len(rows) == 4 and any(r[2] is not None for r in rows)


def test_packed_key_span_rule():
    from spark_rapids_tpu.ops.join import packed_key_span
    assert packed_key_span(5, [1, 10, -2, 2]) == 50
    assert packed_key_span(1, [7, 7, 3, 3, 9, 9]) == 1
    # q93 at SF10: 56,920 items x 9.6M tickets, 40 bits
    assert packed_key_span(2_880_404, [1, 56_920, 1, 9_601_343]) \
        == 56_920 * 9_601_343
    assert packed_key_span(2, [0, (1 << 31) - 1, 0, (1 << 31) - 1]) == 1 << 62
    assert packed_key_span(2, [0, (1 << 31) - 1, 0, (1 << 32) - 1]) is None
    assert packed_key_span(2, [I64.min, I64.max, 0, 0]) is None
    # an empty build packs whatever its (meaningless) ranges say
    assert packed_key_span(0, [I64.max, I64.min, I64.max, I64.min]) == 1


def test_builds_of_other_ranges_run_the_same_programs():
    """The ranges and radices of a packing are arguments of the programs,
    not part of them: a build with other keys compiles nothing."""
    from spark_rapids_tpu.exec import joins as J
    from spark_rapids_tpu.obs.registry import get_registry
    schema = lambda p: T.Schema([
        T.StructField(p + "a", T.IntegerType(), True),
        T.StructField(p + "b", T.LongType(), True),
        T.StructField(p + "v", T.LongType(), True)])

    def run(shift, stride):
        rng = np.random.default_rng(abs(shift))
        ra = [int(x) + shift for x in rng.integers(0, 9, 50)]
        rb = [int(x) * stride - shift for x in rng.integers(0, 40, 50)]
        pick = rng.integers(0, 50, 64)
        left = LocalScanExec.from_pydict(
            {"la": [ra[i] for i in pick], "lb": [rb[i] for i in pick],
             "lv": list(range(64))}, schema("l"))
        right = LocalScanExec.from_pydict(
            {"ra": ra, "rb": rb, "rv": list(range(50))}, schema("r"))
        plan = JoinExec(left, right, [col("la"), col("lb")],
                        [col("ra"), col("rb")], "inner")
        rows = collect_device(plan)
        assert len(rows) >= 64 and all(
            (r[0], r[1]) == (r[3], r[4]) for r in rows)

    run(3, 1 << 33)                     # warms every program at this shape
    programs = (J._jit_build_prep, J._jit_probe_fast, J._jit_gather)
    seen = [p.signature_count() for p in programs]
    before = get_registry().counters()
    run(-1_000_000, 1 << 35)
    run(77, 1 << 30)
    moved = get_registry().counters_since(before)
    assert moved.get("join.probe.search") == 2 and \
        moved.get("join.keys.packed") == 2
    assert "compile_count" not in moved
    assert [p.signature_count() for p in programs] == seen
