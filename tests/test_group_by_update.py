"""``ops/segmented.group_by_update``: the aggregate update's sort-free
path for at most 64 groups, and its fall-back to ``sorted_group_by``
inside the same program.

Every case compares the group rows with ``hk.host_group_by`` row by row
WITHOUT sorting either side (ascending by key, nulls first, on both
branches), and leaf by leaf with ``sorted_group_by`` (same capacity,
dtypes and validity canonicalisation).  The exec-level cases read the
``agg.update.dense`` / ``agg.update.sorted`` counters from the per-query
record.
"""
import datetime
import math
import os

import numpy as np
import pytest

import jax

from spark_rapids_tpu import TpuSession
from spark_rapids_tpu import types as T
from spark_rapids_tpu.exec.core import device_to_host, host_to_device
from spark_rapids_tpu.host.batch import HostBatch
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.ops import host_kernels as hk
from spark_rapids_tpu.ops import segmented
from spark_rapids_tpu.ops.segmented import (AggSpec, group_by_update,
                                            sorted_group_by)

N = 600
NAN = float("nan")


def _with_nulls(rng, values, share=0.12):
    return [None if rng.random() < share else v for v in values]


def _table(rng, n=N) -> HostBatch:
    """One column per key type, and the inputs of every covered op."""
    schema = T.Schema([
        T.StructField("i", T.IntegerType(), True),
        T.StructField("s", T.StringType(), True),
        T.StructField("d", T.DoubleType(), True),
        T.StructField("b", T.BooleanType(), True),
        T.StructField("t", T.DateType(), True),
        T.StructField("x", T.LongType(), True),
        T.StructField("y", T.DoubleType(), True),
        T.StructField("u", T.LongType(), True),
        T.StructField("w", T.StringType(), True),
    ])
    day = datetime.date(1998, 9, 2)
    return HostBatch.from_pydict({
        "i": _with_nulls(rng, rng.integers(-2, 3, n).tolist()),
        # "a" / "ab" / "abc" share a prefix; "" is not null
        "s": _with_nulls(rng, rng.choice(["a", "ab", "abc", "b", ""],
                                         n).tolist()),
        # no negative key but -0.0: the host oracle orders doubles by
        # their bits, which puts a negative after NaN
        "d": _with_nulls(rng, rng.choice(
            [NAN, -0.0, 0.0, 1.5, 2.25, math.inf], n).tolist()),
        "b": _with_nulls(rng, (rng.random(n) < 0.5).tolist()),
        "t": _with_nulls(rng, [day - datetime.timedelta(int(k))
                               for k in rng.integers(0, 4, n)]),
        "x": _with_nulls(rng, rng.integers(-1000, 1000, n).tolist()),
        "y": _with_nulls(rng, rng.choice(
            [NAN, -0.0, 3.25, -7.5, 1e12, -math.inf], n).tolist()),
        "u": np.arange(n).tolist(),           # unique: the fallback's key
        "w": _with_nulls(rng, rng.choice(["p", "q", "rs"], n).tolist()),
    }, schema)


I, S, D, B, DT, X, Y, U, W = range(9)

#: every op the dense path covers, over an int, a double and a string
COVERED = [
    AggSpec("sum", X), AggSpec("sum", Y), AggSpec("avg", X),
    AggSpec("avg", Y), AggSpec("count", Y), AggSpec("count_star", 0),
    AggSpec("min", X), AggSpec("max", X), AggSpec("min", Y),
    AggSpec("max", Y), AggSpec("min", B), AggSpec("max", B),
    AggSpec("first", Y), AggSpec("last", X), AggSpec("first", W),
    AggSpec("last", W), AggSpec("first_non_null", Y),
    AggSpec("last_non_null", X), AggSpec("first_non_null", W),
    AggSpec("last_non_null", W),
]


def _same(a, b) -> bool:
    if a is None or b is None:
        return a is b
    if isinstance(a, float) or isinstance(b, float):
        a, b = float(a), float(b)
        if math.isnan(a) or math.isnan(b):
            return math.isnan(a) and math.isnan(b)
        return a == b or math.isclose(a, b, rel_tol=1e-12)
    return a == b


def _assert_rows(got: list, want: list) -> None:
    """Row by row, in the order they came."""
    assert len(got) == len(want), (len(got), len(want))
    for i, (g, w) in enumerate(zip(got, want)):
        assert len(g) == len(w) and all(map(_same, g, w)), (i, g, w)


def _run(db, keys, aggs, presorted=False):
    out, dense = jax.jit(
        lambda b: group_by_update(b, keys, aggs, presorted))(db)
    return out, bool(dense)


def _check(hb: HostBatch, keys, aggs, capacity=None, dense=True,
           presorted=False) -> None:
    db = host_to_device(hb, capacity)
    out, took_dense = _run(db, keys, aggs, presorted)
    assert took_dense is dense
    _assert_rows(device_to_host(out).to_rows(),
                 hk.host_group_by(hb, keys, aggs).to_rows())
    ref = jax.jit(lambda b: sorted_group_by(b, keys, aggs, presorted))(db)
    assert out.schema == ref.schema and out.capacity == db.capacity
    for mine, theirs in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        assert mine.shape == theirs.shape and mine.dtype == theirs.dtype
        mine, theirs = np.asarray(mine), np.asarray(theirs)
        if mine.dtype.kind == "f":
            np.testing.assert_allclose(mine, theirs, rtol=1e-12)
        else:
            np.testing.assert_array_equal(mine, theirs)


def _many_groups(groups: int, n: int = 400) -> HostBatch:
    """``groups`` distinct (i, s) keys, rows in a shuffled order."""
    rng = np.random.default_rng(groups)
    schema = T.Schema([T.StructField("i", T.LongType(), True),
                       T.StructField("s", T.StringType(), True),
                       T.StructField("v", T.DoubleType(), True)])
    g = np.concatenate([np.arange(groups),
                        rng.integers(0, groups, n - groups)])
    rng.shuffle(g)
    return HostBatch.from_pydict({
        "i": [None if k == 7 else int(k) // 2 for k in g],
        "s": [("even", "odd")[int(k) % 2] for k in g],
        "v": rng.random(n).tolist()}, schema)


CASES = {
    "int_key": lambda t: _check(t, [I], COVERED),
    "string_key": lambda t: _check(t, [S], COVERED),
    "double_key_nan_negzero": lambda t: _check(t, [D], COVERED),
    "bool_key": lambda t: _check(t, [B], COVERED),
    "date_key": lambda t: _check(t, [DT], COVERED),
    "two_keys": lambda t: _check(t, [S, I], COVERED),
    "three_keys_past_64": lambda t: _check(t, [S, I, D], COVERED,
                                           dense=False),
    "grand": lambda t: _check(t, [], COVERED),
    "num_rows_below_capacity": lambda t: _check(t, [I, B], COVERED,
                                                capacity=4096),
    "presorted": lambda t: _check(
        HostBatch([c.take(np.argsort(
            np.where(t.columns[I].validity, t.columns[I].data, -99),
            kind="stable")) for c in t.columns], t.schema),
        [I], COVERED, presorted=True),
    "empty_keyed": lambda t: _check(
        HostBatch([c.take(np.zeros(0, np.int64)) for c in t.columns],
                  t.schema), [S], COVERED),
    "empty_grand": lambda t: _check(
        HostBatch([c.take(np.zeros(0, np.int64)) for c in t.columns],
                  t.schema), [], COVERED),
    "groups_64_dense": lambda t: _check(
        _many_groups(64), [0, 1], [AggSpec("sum", 2),
                                   AggSpec("count_star", 0)]),
    "groups_65_fallback": lambda t: _check(
        _many_groups(65), [0, 1], [AggSpec("sum", 2),
                                   AggSpec("count_star", 0)], dense=False),
    "unique_key_fallback": lambda t: _check(t, [U], COVERED, dense=False),
    "percentile_sorts": lambda t: _check(
        t, [I], [AggSpec("percentile", Y, 0.5), AggSpec("count", Y)],
        dense=False),
    "string_min_sorts": lambda t: _check(
        t, [I], [AggSpec("min", W), AggSpec("max", W)], dense=False),
}


@pytest.fixture(scope="module")
def table():
    return _table(np.random.default_rng(25))


@pytest.mark.parametrize("case", sorted(CASES))
def test_group_by_update_matches_host_and_sort_path(table, case):
    CASES[case](table)


def test_all_padding_batch_has_no_groups(table):
    """num_rows == 0 at a real capacity: no group on a keyed update, the
    default row on a grand one — and the stale rows never count."""
    db = host_to_device(table, 1024)
    hollow = type(db)(db.columns, jax.numpy.asarray(0, jax.numpy.int32),
                      db.schema)
    out, dense = _run(hollow, [S], COVERED)
    assert dense and int(out.num_rows) == 0
    for leaf in jax.tree.leaves(out.columns):
        assert not np.asarray(leaf).any()
    out, dense = _run(hollow, [], [AggSpec("count_star", 0),
                                   AggSpec("sum", X)])
    assert dense
    assert device_to_host(out).to_rows() == [(0, None)]


def test_strings_differing_only_in_length_are_two_groups():
    """``"a"`` and ``"a\\0"`` pad to the same bytes; the length tells
    them apart, as in ``_cols_differ`` (the host oracle's numpy strings
    drop the trailing NUL, so the sort path is the reference here)."""
    schema = T.Schema([T.StructField("s", T.StringType(), True),
                       T.StructField("v", T.LongType(), True)])
    hb = HostBatch.from_pydict(
        {"s": ["a\0", "a", "a\0", "a", "a"], "v": [1, 2, 4, 8, 16]}, schema)
    db = host_to_device(hb)
    out, dense = _run(db, [0], [AggSpec("sum", 1)])
    ref = sorted_group_by(db, [0], [AggSpec("sum", 1)])
    assert dense and int(out.num_rows) == 2
    for mine, theirs in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    assert sorted(np.asarray(out.columns[1].data)[:2].tolist()) == [5, 26]


def test_table_never_wider_than_the_batch():
    """A capacity under 64 holds fewer rows than the table has slots:
    every row its own group still fits, so the dense branch answers."""
    schema = T.Schema([T.StructField("k", T.LongType(), True)])
    hb = HostBatch.from_pydict({"k": list(range(8, 0, -1))}, schema)
    db = host_to_device(hb, 8)
    assert db.capacity < segmented._DENSE_MAX_GROUPS
    out, dense = _run(db, [0], [AggSpec("count_star", 0)])
    assert dense
    assert device_to_host(out).to_rows() == [(k, 1) for k in range(1, 9)]


# ---------------------------------------------------------------------------
# the sort branch's body alone (scans and gathers at the segment
# boundaries): every op x the shapes a boundary can be wrong at
# ---------------------------------------------------------------------------

def _body_table(n: int) -> HostBatch:
    rng = np.random.default_rng(n)
    schema = T.Schema([
        T.StructField("k", T.LongType(), True),      # few groups, a null one
        T.StructField("s", T.StringType(), True),
        T.StructField("one", T.IntegerType(), True),  # one group of every row
        T.StructField("u", T.LongType(), True),      # every row its own group
        T.StructField("x", T.LongType(), True),
        T.StructField("c", T.DoubleType(), True),    # whole cents
        T.StructField("y", T.DoubleType(), True),    # thousandths: not whole
        T.StructField("z", T.DoubleType(), True),
        T.StructField("b", T.BooleanType(), True),
        T.StructField("w", T.StringType(), True),
    ])
    return HostBatch.from_pydict({
        "k": _with_nulls(rng, rng.integers(-3, 4, n).tolist()),
        "s": _with_nulls(rng, rng.choice(["a", "ab", "", "b"], n).tolist()),
        "one": [7] * n,
        "u": rng.permutation(n).tolist(),
        "x": _with_nulls(rng, rng.integers(-10**12, 10**12, n).tolist()),
        "c": _with_nulls(rng, (rng.integers(-5000, 5000, n) / 100).tolist()),
        "y": _with_nulls(rng, (rng.integers(1, 99999, n) / 1000).tolist()),
        "z": _with_nulls(rng, rng.choice(
            [NAN, -0.0, 0.0, -2.5, 1e300, math.inf, -math.inf], n).tolist()),
        "b": _with_nulls(rng, (rng.random(n) < 0.5).tolist()),
        "w": _with_nulls(rng, rng.choice(["p", "q", "rs", ""], n).tolist()),
    }, schema)


BK, BS, ONE, BU, BX, BC, BY, BZ, BB, BW = range(10)

BODY_OPS = {
    "counts": [AggSpec("count_star", 0), AggSpec("count", BX)],
    "sum_int": [AggSpec("sum", BX), AggSpec("avg", BX)],
    "sum_whole_cents": [AggSpec("sum", BC), AggSpec("avg", BC)],
    "sum_not_whole": [AggSpec("sum", BY), AggSpec("avg", BY)],
    "min_max_int": [AggSpec("min", BX), AggSpec("max", BX)],
    "min_max_double": [AggSpec("min", BZ), AggSpec("max", BZ)],
    "min_max_bool": [AggSpec("min", BB), AggSpec("max", BB)],
    "min_max_string": [AggSpec("min", BW), AggSpec("max", BW)],
    "first_last": [AggSpec("first", BZ), AggSpec("last", BX),
                   AggSpec("first", BW), AggSpec("last", BW),
                   AggSpec("first_non_null", BZ),
                   AggSpec("last_non_null", BX),
                   AggSpec("first_non_null", BW),
                   AggSpec("last_non_null", BW)],
    "percentile": [AggSpec("percentile", BY, 0.3), AggSpec("count", BY)],
}

_BODY_N, _BODY_CAP = 48, 64


def _sorted_by_key(t: HostBatch) -> HostBatch:
    k = t.columns[BK]
    order = np.argsort(np.where(k.validity, k.data, -99), kind="stable")
    return HostBatch([c.take(order) for c in t.columns], t.schema)


#: name -> (rows, keys, presorted, the device batch's num_rows forced to 0)
BODY_SHAPES = {
    "empty": (0, [BK], False, False),
    "all_padding": (_BODY_N, [BK], False, True),
    "num_rows_is_capacity": (_BODY_CAP, [BK], False, False),
    "one_group": (_BODY_N, [ONE], False, False),
    "every_row_a_group": (_BODY_N, [BU], False, False),
    "every_row_a_group_full": (_BODY_CAP, [BU], False, False),
    "null_key_group": (_BODY_N, [BK], False, False),
    "two_keys_with_string": (_BODY_N, [BS, BK], False, False),
    "presorted": (_BODY_N, [BK], True, False),
    "grand": (_BODY_N, [], False, False),
    "grand_all_padding": (_BODY_N, [], False, True),
}


@pytest.mark.parametrize("ops", sorted(BODY_OPS))
@pytest.mark.parametrize("shape", sorted(BODY_SHAPES))
def test_sorted_group_by_matches_host(shape, ops):
    rows, keys, presorted, hollow = BODY_SHAPES[shape]
    hb = _body_table(rows)
    if presorted:
        hb = _sorted_by_key(hb)
    db = host_to_device(hb, _BODY_CAP)
    assert db.capacity == _BODY_CAP
    if hollow:      # stale rows under num_rows == 0 must never count
        db = type(db)(db.columns, jax.numpy.asarray(0, jax.numpy.int32),
                      db.schema)
        hb = HostBatch([c.take(np.zeros(0, np.int64)) for c in hb.columns],
                       hb.schema)
    aggs = BODY_OPS[ops]
    out = jax.jit(lambda b: sorted_group_by(b, keys, aggs, presorted))(db)
    assert out.capacity == _BODY_CAP
    _assert_rows(device_to_host(out).to_rows(),
                 hk.host_group_by(hb, keys, aggs).to_rows())
    # canonical: nothing is left in the slots past the groups
    for leaf in jax.tree.leaves(out.columns):
        assert not np.asarray(leaf)[int(out.num_rows):].any()


# ---------------------------------------------------------------------------
# through the session: which branch ran is in the query's record
# ---------------------------------------------------------------------------

CONF = {"spark.rapids.sql.resultCache.enabled": "false",
        "spark.rapids.sql.test.enabled": "true"}
#: blocking fetches of a TPC-H q1 collect at sf 0.01 at the parent commit
#: (a93499f, measured there): the flag rides in the count's fetch
Q1_D2H_CALLS_AT_PARENT = 3


@pytest.fixture(scope="module")
def tpch_dir(tmp_path_factory):
    from spark_rapids_tpu.bench.tpch_gen import generate_tpch
    d = str(tmp_path_factory.mktemp("tpch_group_by_update") / "sf001")
    generate_tpch(d, sf=0.01, tables=["lineitem"])
    return d


@pytest.fixture(scope="module")
def session():
    s = TpuSession(dict(CONF))
    yield s
    s.shutdown(drain=False)


def _counters_of(df) -> tuple:
    rows = df.collect()
    return rows, get_registry().recent_queries(1)[0]["counters"]


def test_tpch_q1_updates_without_the_sort(session, tpch_dir):
    from spark_rapids_tpu.bench.tpch_queries import build_tpch_query
    rows, counters = _counters_of(build_tpch_query("q1", session, tpch_dir))
    assert len(rows) == 4
    assert counters["agg.update.dense"] >= 1
    assert counters.get("agg.update.sorted", 0) == 0
    assert counters["d2h_calls"] == Q1_D2H_CALLS_AT_PARENT


def test_unique_key_group_by_takes_the_sort(session, tpch_dir):
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.expr.core import col
    li = session.read_parquet(os.path.join(tpch_dir, "lineitem"),
                              columns=["l_orderkey", "l_quantity"])
    rows, counters = _counters_of(
        li.group_by("l_orderkey").agg(Sum(col("l_quantity")).alias("q")))
    assert len(rows) > segmented._DENSE_MAX_GROUPS
    assert counters["agg.update.sorted"] >= 1
    assert counters.get("agg.update.dense", 0) == 0


@pytest.mark.parametrize("max_cap,updates", [(1 << 30, 1), (1 << 14, 4),
                                             (1 << 12, 15)])
def test_an_oversized_batch_is_updated_half_by_half(session, tpch_dir,
                                                    monkeypatch, max_cap,
                                                    updates):
    """A batch of more slots than ``_UPDATE_MAX_CAP`` is halved by slots
    (static slices, ``kernels.halve_capacity``) until no piece has more,
    one update a piece that is not known to be empty, and the pieces'
    buffers merge like any batches': the same groups, a key that
    straddles a cut among them, and no fetch added to learn a count."""
    from spark_rapids_tpu.exec.aggregate import HashAggregateExec
    from spark_rapids_tpu.expr.aggregates import Count, Sum
    from spark_rapids_tpu.expr.core import col
    li = session.read_parquet(os.path.join(tpch_dir, "lineitem"),
                              columns=["l_orderkey", "l_quantity"])
    df = li.group_by("l_orderkey").agg(Sum(col("l_quantity")).alias("q"),
                                       Count(col("l_quantity")).alias("n"))
    if "want" not in _SPLIT:
        _SPLIT["want"], whole = _counters_of(df)
        _SPLIT["d2h"] = whole["d2h_calls"]
        _SPLIT["rows"] = whole["agg.update.rows"]
        assert whole["agg.update.sorted"] == 1
    # one scan batch of 2^16 slots, its last 4096 holding no row
    assert 14 << 12 < _SPLIT["rows"] <= 15 << 12
    monkeypatch.setattr(HashAggregateExec, "_UPDATE_MAX_CAP", max_cap)
    rows, counters = _counters_of(df)
    assert sorted(rows) == sorted(_SPLIT["want"])
    assert counters["agg.update.sorted"] == updates
    assert counters["agg.update.rows"] == _SPLIT["rows"]
    # the pieces' counts ride in the chunked fetches that were there;
    # past eight pieces a second chunk and its merge fetch their own
    assert counters["d2h_calls"] <= _SPLIT["d2h"] + (updates > 1) + updates // 8


_SPLIT: dict = {}


def test_halves_of_a_batch_are_its_slots_and_count_their_rows():
    from spark_rapids_tpu.ops import kernels as dk
    hb = HostBatch.from_pydict(
        {"k": [1, 2, None, 4, 5], "s": ["a", None, "ccc", "dd", "e"]},
        T.Schema([T.StructField("k", T.IntegerType()),
                  T.StructField("s", T.StringType())]))
    b = host_to_device(hb)
    assert b.capacity == 8
    lo, hi = dk.halve_capacity(b)
    assert (lo.capacity, hi.capacity) == (4, 4)
    assert (lo.known_rows, hi.known_rows) == (4, 1) \
        if b.known_rows is not None else (None, None)
    assert device_to_host(lo).to_rows() == hb.to_rows()[:4]
    assert device_to_host(hi).to_rows() == hb.to_rows()[4:]
    # all rows in the lower half: the upper half is empty, not negative
    lo2, hi2 = dk.halve_capacity(lo)
    assert device_to_host(lo2).to_rows() == hb.to_rows()[:2]
    lo3, hi3 = dk.halve_capacity(dk.pad_capacity(hi, 8))
    assert int(lo3.num_rows) == 1 and int(hi3.num_rows) == 0
