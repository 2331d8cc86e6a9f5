"""Core columnar round-trip + kernel tests (filter/sort/concat/groupby).

Reference test analogs: GpuCoalesceBatchesSuite, HashAggregatesSuite,
GpuSortExec coverage in tests/ (SURVEY §4.1).
"""
import numpy as np
import pyarrow as pa
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar import ColumnBatch
from spark_rapids_tpu.columnar.batch import round_capacity
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu import ops
from spark_rapids_tpu.ops.segmented import AggSpec, sorted_group_by
from spark_rapids_tpu.ops.sort import SortOrder


def _rb(**cols):
    return pa.RecordBatch.from_pydict(dict(cols))


def test_arrow_roundtrip_numeric():
    rb = _rb(a=pa.array([1, 2, None, 4], type=pa.int32()),
             b=pa.array([1.5, None, 3.5, -0.0], type=pa.float64()))
    batch = ColumnBatch.from_arrow(rb)
    assert batch.capacity == 8
    out = batch.to_arrow()
    assert out.column(0).to_pylist() == [1, 2, None, 4]
    assert out.column(1).to_pylist() == [1.5, None, 3.5, -0.0]


def test_arrow_roundtrip_strings():
    rb = _rb(s=pa.array(["hello", "", None, "worldly"]))
    batch = ColumnBatch.from_arrow(rb)
    out = batch.to_arrow()
    assert out.column(0).to_pylist() == ["hello", "", None, "worldly"]


def test_arrow_roundtrip_bool_date_ts():
    rb = _rb(f=pa.array([True, None, False], type=pa.bool_()),
             d=pa.array([0, 1000, None], type=pa.date32()),
             t=pa.array([0, 123456789, None], type=pa.timestamp("us")))
    out = ColumnBatch.from_arrow(rb).to_arrow()
    assert out.column(0).to_pylist() == [True, None, False]
    assert out.column(1).to_pylist()[1] == pa.scalar(1000, pa.date32()).as_py()
    assert out.column(2).to_pylist()[2] is None


def test_compact_filter():
    rb = _rb(a=pa.array([1, 2, 3, 4, 5], type=pa.int64()))
    batch = ColumnBatch.from_arrow(rb)
    keep = jnp.asarray([True, False, True, False, True, True, True, True])
    out = ops.compact(batch, keep)
    assert out.host_num_rows() == 3
    assert out.to_arrow().column(0).to_pylist() == [1, 3, 5]


def _compact_input(cap, n, seed, junk=False):
    """``n`` rows in ``cap`` slots of (int32, float64, int64, an all-null
    int32, a string in 4 bytes, a string in 16), nulls scattered;
    ``junk`` fills the padding with data and sets its validity."""
    from spark_rapids_tpu import types as T
    rng = np.random.default_rng(seed)
    real = np.arange(cap) < n

    def leaf(values, valid):
        fill = np.full_like(values, 7) if junk else np.zeros_like(values)
        shaped = valid.reshape((cap,) + (1,) * (values.ndim - 1))
        return np.where(shaped, values, fill)
    cols, fields = [], []
    for name, dtype, values in (
            ("a", T.IntegerType(), rng.integers(1, 99, cap).astype(np.int32)),
            ("v", T.DoubleType(), rng.normal(size=cap)),
            ("k", T.LongType(), rng.integers(1, 1 << 40, cap)),
            ("n", T.IntegerType(), np.zeros(cap, np.int32))):
        valid = real & (rng.random(cap) > 0.2) & (name != "n")
        cols.append(DeviceColumn(
            jnp.asarray(leaf(values, valid)),
            jnp.asarray(valid | (junk & ~real)), dtype))
        fields.append(T.StructField(name, dtype, True))
    for name, width in (("s", 4), ("t", 16)):
        valid = real & (rng.random(cap) > 0.2)
        lengths = rng.integers(0, width + 1, cap).astype(np.int32)
        chars = rng.integers(97, 123, (cap, width)).astype(np.uint8)
        chars = np.where(np.arange(width)[None, :] < lengths[:, None],
                         chars, 0).astype(np.uint8)
        cols.append(DeviceColumn(
            jnp.asarray(leaf(chars, valid)),
            jnp.asarray(valid | (junk & ~real)), T.StringType(),
            jnp.asarray(leaf(lengths, valid))))
        fields.append(T.StructField(name, T.StringType(), True))
    return ColumnBatch(cols, jnp.asarray(n, jnp.int32), T.Schema(fields))


def _compact_reference(batch, keep):
    """NumPy: the kept real rows in order at the front, zeros behind,
    data and lengths zeroed where invalid."""
    cap = batch.capacity
    keep = np.asarray(keep) & (np.arange(cap) < int(batch.num_rows))
    idx = np.flatnonzero(keep)
    out = []
    for c in batch.columns:
        leaves = []
        valid = np.asarray(c.validity)[idx]
        for x in (c.validity, c.data, c.lengths):
            if x is None:
                leaves.append(None)
                continue
            x = np.asarray(x)
            packed = np.zeros_like(x)
            packed[:len(idx)] = np.where(
                valid.reshape((-1,) + (1,) * (x.ndim - 1)), x[idx], 0)
            leaves.append(packed)
        out.append(tuple(leaves))
    return len(idx), out


def _keep_spread(cap, count, seed=5):
    keep = np.zeros(cap, bool)
    keep[np.random.default_rng(seed).choice(cap, count, replace=False)] = True
    return keep


_BIG = ops.kernels.COND_MIN_CAPACITY        # the least capacity with a cond
_EIGHTH = _BIG // ops.kernels.SMALL_BUCKET_DIVISOR
_COMPACT_CASES = {
    # name: (capacity, num_rows, junk padding, keep mask)
    "keep_none": (_BIG, _BIG - 3, False, lambda: np.zeros(_BIG, bool)),
    "keep_one": (_BIG, _BIG - 3, False,
                 lambda: np.arange(_BIG) == _BIG - 4),
    "keep_exactly_an_eighth": (_BIG, _BIG, False,
                               lambda: _keep_spread(_BIG, _EIGHTH)),
    "keep_an_eighth_and_one": (_BIG, _BIG, False,
                               lambda: _keep_spread(_BIG, _EIGHTH + 1)),
    "keep_all": (_BIG, _BIG, False, lambda: np.ones(_BIG, bool)),
    "keep_first_rows": (_BIG, _BIG - 3, False,
                        lambda: np.arange(_BIG) < 1000),
    "junk_padding_few": (_BIG, 5000, True, lambda: np.arange(_BIG) % 3 == 0),
    "junk_padding_most": (_BIG, _BIG - 100, True,
                          lambda: np.ones(_BIG, bool)),
    "under_the_static_floor": (64, 50, True, lambda: np.arange(64) % 2 == 1),
    "under_the_static_floor_none": (8, 8, False, lambda: np.zeros(8, bool)),
}


@pytest.mark.parametrize("case", sorted(_COMPACT_CASES))
def test_compact_matches_numpy_reference(case):
    """Rows, order, ``num_rows``, zeroed padding and every leaf's dtype
    and shape, whichever branch of the ``cond`` the count picks; traced
    inside a program it is the same leaves."""
    cap, n, junk, mask = _COMPACT_CASES[case]
    batch = _compact_input(cap, n, seed=len(case), junk=junk)
    keep = jnp.asarray(mask())
    out = ops.compact(batch, keep)
    rows, want = _compact_reference(batch, keep)
    assert int(out.num_rows) == rows
    assert out.capacity == cap and out.schema == batch.schema
    for c, src, leaves in zip(out.columns, batch.columns, want):
        for got, was, ref in zip((c.validity, c.data, c.lengths),
                                 (src.validity, src.data, src.lengths),
                                 leaves):
            assert (got is None) == (ref is None)
            if ref is not None:
                assert got.dtype == was.dtype and got.shape == was.shape
                np.testing.assert_array_equal(np.asarray(got), ref)
    traced = jax.jit(ops.compact)(batch, keep)
    for got, eager in zip(jax.tree.leaves(traced), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(eager))


def test_compact_branches_agree_leaf_for_leaf():
    """The small-bucket move and the full move, called directly on an
    input both can hold, give the same leaves; a program over a capacity
    at or above the floor holds both under one ``cond``, one below it
    neither the ``cond`` nor a second scatter."""
    from spark_rapids_tpu.ops.kernels import _move_rows
    batch = _compact_input(_BIG, _BIG - 7, seed=11, junk=True)
    keep = jnp.asarray(_keep_spread(_BIG, _EIGHTH - 5)) & batch.row_mask()
    scan = jnp.cumsum(keep.astype(jnp.int32))
    args = (batch.columns, keep, scan - 1, scan[-1])
    small = _move_rows(*args, slots=_EIGHTH)
    full = _move_rows(*args, slots=_BIG)
    whole = ops.compact(batch, keep).columns
    for a, b, c in zip(*(jax.tree.leaves(x) for x in (small, full, whole))):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))

    def lowered(cap):
        b = _compact_input(cap, cap, seed=1)
        return jax.jit(ops.compact).lower(b, b.row_mask()).as_text()
    # one index scatter and one gather a dtype (bool, s32, f64, s64, u8)
    # a branch; no sort
    big, little = lowered(_BIG), lowered(_BIG // 2)

    def ops_named(text, op):
        return text.count(f'"stablehlo.{op}"')
    assert ops_named(big, "case") + ops_named(big, "if") == 1
    assert ops_named(big, "scatter") == 2
    assert ops_named(big, "gather") == 2 * 5
    assert ops_named(little, "case") + ops_named(little, "if") == 0
    assert ops_named(little, "scatter") == 1
    assert ops_named(little, "gather") == 5
    assert "stablehlo.sort" not in big + little


def test_slice_limit():
    rb = _rb(a=pa.array(list(range(6)), type=pa.int32()))
    out = ops.slice_batch(ColumnBatch.from_arrow(rb), 4)
    assert out.to_arrow().column(0).to_pylist() == [0, 1, 2, 3]


def test_concat_batches():
    b1 = ColumnBatch.from_arrow(_rb(a=pa.array([1, None], type=pa.int32()),
                                    s=pa.array(["x", "yy"])))
    b2 = ColumnBatch.from_arrow(_rb(a=pa.array([3], type=pa.int32()),
                                    s=pa.array([None], type=pa.string())))
    out = ops.concat_batches([b1, b2])
    assert out.host_num_rows() == 3
    t = out.to_arrow()
    assert t.column(0).to_pylist() == [1, None, 3]
    assert t.column(1).to_pylist() == ["x", "yy", None]


def _concat_input(n, cap, seed, width=3, null_every=3, junk=False):
    """``n`` rows of (int32, float64, string of up to ``width`` bytes) in
    ``cap`` slots; ``null_every`` = 1 makes every value null.  ``junk``
    fills the padding slots with data and sets their validity: a batch no
    kernel of the engine makes, and one concat must still canonicalize."""
    rng = np.random.default_rng(seed)
    null = (np.arange(n) % null_every) == 0
    strs = ["x" * int(k) for k in rng.integers(0, width + 1, n)]
    if n:
        strs[-1] = "w" * width      # the widest string is really there
    b = ColumnBatch.from_arrow(_rb(
        a=pa.array(rng.integers(-99, 99, n).astype(np.int32), mask=null),
        v=pa.array(rng.normal(size=n), mask=np.roll(null, 1)),
        s=pa.array(strs, type=pa.string(), mask=np.roll(null, 2))),
        capacity=cap)
    if not junk:
        return b
    pad = ~b.row_mask()
    cols = [DeviceColumn(
        jnp.where(pad[(...,) + (None,) * (c.data.ndim - 1)],
                  jnp.ones((), c.data.dtype) * 7, c.data),
        c.validity | pad, c.dtype,
        None if c.lengths is None else jnp.where(pad, 2, c.lengths))
        for c in b.columns]
    return ColumnBatch(cols, b.num_rows, b.schema)


def _concat_reference(batches, cap):
    """NumPy: the real rows of each input in list order, zero padding,
    data and lengths zeroed where invalid; ``(validity, data, lengths)``
    per column."""
    ns = [int(b.num_rows) for b in batches]
    out = []
    for parts in zip(*(b.columns for b in batches)):
        def stack(leaves, width=None):
            rows = [np.asarray(x)[:n] for x, n in zip(leaves, ns)]
            if width is not None:
                rows = [np.pad(r, ((0, 0), (0, width - r.shape[1])))
                        for r in rows]
            cat = np.concatenate(rows)
            return np.concatenate(
                [cat, np.zeros((cap - len(cat),) + cat.shape[1:], cat.dtype)])
        validity = stack([p.validity for p in parts])
        if parts[0].is_var_width:
            data = stack([p.data for p in parts],
                         max(p.max_len for p in parts))
            out.append((validity, np.where(validity[:, None], data, 0),
                        np.where(validity,
                                 stack([p.lengths for p in parts]), 0)))
        else:
            data = stack([p.data for p in parts])
            out.append((validity, np.where(validity, data, 0), None))
    return sum(ns), out


_CONCAT_CASES = {
    # name: (inputs as _concat_input kwargs, out_capacity)
    "fixed_and_strings": ([dict(n=5, cap=8, width=2), dict(n=16, cap=16, width=9),
                           dict(n=3, cap=32, width=4)], None),
    "all_null": ([dict(n=6, cap=8, null_every=1),
                  dict(n=2, cap=8, null_every=1)], None),
    "empty_first": ([dict(n=0, cap=8), dict(n=7, cap=8), dict(n=4, cap=16)],
                    None),
    "empty_middle": ([dict(n=7, cap=8), dict(n=0, cap=16), dict(n=4, cap=8)],
                     None),
    "empty_last": ([dict(n=7, cap=8), dict(n=4, cap=8), dict(n=0, cap=32)],
                   None),
    "all_empty": ([dict(n=0, cap=8), dict(n=0, cap=8)], None),
    "out_capacity_larger": ([dict(n=8, cap=8), dict(n=1, cap=8)], 128),
    "small_after_large": ([dict(n=2, cap=64), dict(n=3, cap=8)], None),
    "junk_padding": ([dict(n=3, cap=16, junk=True), dict(n=0, cap=8, junk=True),
                      dict(n=5, cap=8, junk=True)], None),
}


@pytest.mark.parametrize("case", sorted(_CONCAT_CASES))
def test_concat_batches_matches_numpy_reference(case):
    specs, out_capacity = _CONCAT_CASES[case]
    batches = [_concat_input(seed=i, **kw) for i, kw in enumerate(specs)]
    out = ops.concat_batches(batches, out_capacity=out_capacity)
    cap = out_capacity or round_capacity(sum(b.capacity for b in batches))
    assert out.capacity == cap and out.schema == batches[0].schema
    rows, want = _concat_reference(batches, cap)
    assert int(out.num_rows) == rows
    for c, (validity, data, lengths) in zip(out.columns, want):
        np.testing.assert_array_equal(np.asarray(c.validity), validity)
        np.testing.assert_array_equal(np.asarray(c.data), data)
        if lengths is not None:
            np.testing.assert_array_equal(np.asarray(c.lengths), lengths)
    # traced (a caller already inside a program) = launched
    traced = jax.jit(lambda bs: ops.concat_batches(bs, out_capacity))(batches)
    for got, eager in zip(jax.tree.leaves(traced), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(eager))


def test_concat_batches_refuses_a_capacity_that_would_clamp():
    """``dynamic_update_slice`` clamps a start that would overrun the
    output; with ``out_capacity`` >= the summed capacities none can."""
    batches = [_concat_input(n=1, cap=8, seed=0),
               _concat_input(n=1, cap=8, seed=1)]
    with pytest.raises(ValueError, match="out_capacity"):
        ops.concat_batches(batches, out_capacity=8)
    assert ops.concat_batches(batches, out_capacity=16).capacity == 16


def test_concat_batches_places_without_sort_or_gather():
    """Inputs are front-packed, so concatenation is placement by offset:
    the lowered program holds no sort, no gather and no scatter."""
    batches = [ColumnBatch.from_arrow(_rb(
        a=pa.array(np.arange(n, dtype=np.int32)),
        v=pa.array(np.arange(n, dtype=np.float64))), capacity=cap)
        for n, cap in ((5, 8), (16, 16), (3, 32))]
    text = jax.jit(lambda bs: ops.concat_batches(bs)).lower(batches).as_text()
    for op in ("sort", "gather", "scatter"):
        assert f"stablehlo.{op}" not in text, op
    # one placement a leaf (validity + data) and input
    assert text.count("stablehlo.dynamic_update_slice") == 2 * 2 * 3


@pytest.mark.parametrize("asc", [True, False])
def test_sort_ints_nulls(asc):
    rb = _rb(a=pa.array([5, None, 1, 3, None, 2], type=pa.int32()))
    batch = ColumnBatch.from_arrow(rb)
    out = ops.sort_batch(batch, [SortOrder(0, ascending=asc)])
    got = out.to_arrow().column(0).to_pylist()
    if asc:  # Spark: asc -> nulls first
        assert got == [None, None, 1, 2, 3, 5]
    else:    # desc -> nulls last
        assert got == [5, 3, 2, 1, None, None]


def test_sort_floats_nan_and_negzero():
    vals = [1.0, float("nan"), -1.0, 0.0, -0.0, float("inf"), float("-inf")]
    batch = ColumnBatch.from_arrow(_rb(a=pa.array(vals, type=pa.float64())))
    got = ops.sort_batch(batch, [SortOrder(0)]).to_arrow().column(0).to_pylist()
    assert got[0] == float("-inf")
    assert got[1] == -1.0
    assert got[2] == 0.0 and got[3] == 0.0
    assert got[4] == 1.0
    assert got[5] == float("inf")
    assert np.isnan(got[6])  # NaN largest, Spark semantics


def test_sort_strings():
    batch = ColumnBatch.from_arrow(_rb(s=pa.array(["pear", "apple", None, "ap", "banana"])))
    got = ops.sort_batch(batch, [SortOrder(0)]).to_arrow().column(0).to_pylist()
    assert got == [None, "ap", "apple", "banana", "pear"]


def test_sort_multi_key():
    batch = ColumnBatch.from_arrow(_rb(
        k=pa.array([2, 1, 2, 1], type=pa.int32()),
        v=pa.array([1.0, 5.0, 0.5, 4.0], type=pa.float64())))
    out = ops.sort_batch(batch, [SortOrder(0, True), SortOrder(1, False)])
    t = out.to_arrow()
    assert t.column(0).to_pylist() == [1, 1, 2, 2]
    assert t.column(1).to_pylist() == [5.0, 4.0, 1.0, 0.5]


@pytest.mark.parametrize("directions", [(True,) * 5,
                                        (False, True, False, True, False)],
                         ids=["ascending", "mixed"])
def test_sort_long_key_in_passes_is_the_one_sort(directions, monkeypatch):
    """A key of more operands than one ``lax.sort`` takes
    (``MAX_SORT_KEYS``) is ordered by stable passes: the same
    permutation as the single sort of all operands, ties, nulls, NaNs
    and padding included, and the order the host oracle gives."""
    from spark_rapids_tpu.ops import host_kernels as hk
    from spark_rapids_tpu.ops import sort as sort_mod
    rng = np.random.default_rng(11)
    n = 500

    def nulls(values, share=0.1):
        return [None if rng.random() < share else v for v in values]
    names = [f"Customer#{i:09d}" for i in rng.integers(0, 12, n)]
    rb = _rb(name=pa.array(nulls(names)),
             a=pa.array(nulls(rng.integers(0, 3, n).tolist()), pa.int32()),
             b=pa.array(nulls(rng.integers(-2, 2, n).tolist()), pa.int64()),
             d=pa.array(nulls(rng.integers(9000, 9003, n).tolist()),
                        pa.date32()),
             p=pa.array(nulls(rng.choice([1.5, -0.0, 0.0, float("nan"),
                                          2.25], n).tolist()), pa.float64()),
             row=pa.array(np.arange(n), pa.int32()))
    batch = ColumnBatch.from_arrow(rb, capacity=1 << 10)
    orders = [SortOrder(i, asc) for i, asc in enumerate(directions)]
    calls = []
    real_sort = sort_mod.lax.sort

    def spy(operands, **kw):
        calls.append(len(operands))
        return real_sort(operands, **kw)
    monkeypatch.setattr(sort_mod.lax, "sort", spy)
    passes = np.asarray(sort_mod.sort_permutation(batch, orders))
    assert len(calls) > 1 and max(calls) <= sort_mod.PASS_SORT_KEYS + 1
    calls.clear()
    monkeypatch.setattr(sort_mod, "MAX_SORT_KEYS", 1 << 10)
    single = np.asarray(sort_mod.sort_permutation(batch, orders))
    assert len(calls) == 1 and calls[0] > 5
    assert (passes == single).all()
    # ties keep their input order (``row`` ascends within equal keys) and
    # the host oracle agrees on the real rows
    from spark_rapids_tpu.host.batch import HostBatch
    host = hk.host_sort_permutation(HostBatch.from_arrow(rb), orders)
    assert (passes[:n] == np.asarray(host)).all()
    assert sorted(passes[n:].tolist()) == list(range(n, 1 << 10))


def test_group_by_sum_count_min_max_avg():
    batch = ColumnBatch.from_arrow(_rb(
        k=pa.array([1, 2, 1, None, 2, 1], type=pa.int32()),
        v=pa.array([10, 20, None, 40, 5, 2], type=pa.int64())))
    out = sorted_group_by(batch, [0], [AggSpec("sum", 1), AggSpec("count", 1),
                                       AggSpec("min", 1), AggSpec("max", 1),
                                       AggSpec("avg", 1), AggSpec("count_star", 1)])
    t = out.to_arrow()
    rows = {t.column(0).to_pylist()[i]: tuple(t.column(j).to_pylist()[i] for j in range(1, 7))
            for i in range(out.host_num_rows())}
    assert rows[1] == (12, 2, 2, 10, 6.0, 3)
    assert rows[2] == (25, 2, 5, 20, 12.5, 2)
    assert rows[None] == (40, 1, 40, 40, 40.0, 1)


def test_group_by_all_null_values_sum_is_null():
    batch = ColumnBatch.from_arrow(_rb(
        k=pa.array([7, 7], type=pa.int32()),
        v=pa.array([None, None], type=pa.int64())))
    t = sorted_group_by(batch, [0], [AggSpec("sum", 1)]).to_arrow()
    assert t.column(1).to_pylist() == [None]


def test_grand_aggregate_empty_input():
    batch = ColumnBatch.from_arrow(
        pa.RecordBatch.from_pydict({"v": pa.array([], type=pa.int64())}))
    out = sorted_group_by(batch, [], [AggSpec("count", 0), AggSpec("sum", 0)])
    t = out.to_arrow()
    assert out.host_num_rows() == 1
    assert t.column(0).to_pylist() == [0]
    assert t.column(1).to_pylist() == [None]


def test_group_by_float_minmax_nan():
    batch = ColumnBatch.from_arrow(_rb(
        k=pa.array([1, 1, 1], type=pa.int32()),
        v=pa.array([1.0, float("nan"), -2.0], type=pa.float64())))
    t = sorted_group_by(batch, [0], [AggSpec("min", 1), AggSpec("max", 1)]).to_arrow()
    assert t.column(1).to_pylist() == [-2.0]
    assert np.isnan(t.column(2).to_pylist()[0])  # NaN is max in Spark
