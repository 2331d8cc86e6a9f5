"""Group-by aggregation: a sort-based kernel (cuDF groupBy().aggregate
analog) and, for the per-batch update, a sort-free path for few groups.

Reference: GpuHashAggregateExec computes cuDF hash-group-by per batch then
merges (aggregate.scala:348-560).  XLA has no device hash tables, so the
TPU-idiomatic design (SURVEY §7 "hard parts") is *sort-based*
(:func:`sorted_group_by`): sort rows by the grouping keys, mark segment
boundaries, reduce each now contiguous group by a segmented scan that
leaves the group's value on its first row, and move keys and results
from those rows to the front by one gather a dtype (:class:`_Segments`;
no scatter, whose cost on the chip is a further sort) — fully static
shapes, group count as a traced scalar.

When the update sorts and when it does not (:func:`group_by_update`, the
entry point of ``HashAggregateExec``'s per-batch update): the program
first *discovers* up to ``_DENSE_MAX_GROUPS`` (64) distinct keys by
repeated equality passes over the unsorted key columns.  If every real
row found its group, each group is reduced by a mask over the unsorted,
ungathered input columns and the ≤ 64-row group table alone is sorted —
cost *groups × one read of the columns*, no ``capacity``-row sort, gather
or scatter.  If rows remain after 64 groups, the other branch of the same
``lax.cond`` runs :func:`sorted_group_by` unchanged.  ``percentile`` and
string ``min``/``max`` need the sort and are routed to it at trace time.
Which branch ran is returned beside the batch; ``HashAggregateExec``
counts it as ``agg.update.dense`` / ``agg.update.sorted``.  This is a
bounded linear probe over at most 64 keys, not a hash table: the merge,
the mesh aggregates and every high-cardinality input stay sort-based.

Null keys form their own group (Spark semantics); key equality treats
null == null.  Padding rows are forced into one trailing segment whose
output slot is canonicalized away.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.ops import cents
from spark_rapids_tpu.ops.kernels import (_pad_jit, gather_columns,
                                          gather_stacked)
from spark_rapids_tpu.ops.sort import (SortOrder, normalize_floats,
                                       sort_batch, sort_permutation,
                                       string_key_words)

__all__ = ["AggSpec", "sorted_group_by", "group_by_update"]

#: most distinct keys the update's sort-free path holds; an input with
#: more takes the sort branch of the same program (group_by_update)
_DENSE_MAX_GROUPS = 64

# supported aggregate ops (reference AggregateFunctions.scala:531 CudfAggregate)
_AGG_OPS = ("sum", "count", "count_star", "min", "max", "avg", "first", "last",
            "first_non_null", "last_non_null", "percentile")


@dataclass(frozen=True)
class AggSpec:
    op: str          # one of _AGG_OPS
    child_index: int  # input column (ignored for count_star)
    param: float | None = None  # percentile fraction q in [0, 1]

    def result_type(self, input_type: T.DataType) -> T.DataType:
        if self.op in ("count", "count_star"):
            return T.LongType()
        if self.op == "sum":
            if input_type.integral:
                return T.LongType()
            return T.DoubleType()
        if self.op in ("avg", "percentile"):
            return T.DoubleType()
        return input_type


def _cols_differ(col: DeviceColumn) -> jax.Array:
    """bool[capacity]: row i's key differs from row i-1's (null==null)."""
    v = col.validity
    v_prev = jnp.roll(v, 1)
    if col.is_string:
        d_prev = jnp.roll(col.data, 1, axis=0)
        data_diff = jnp.any(col.data != d_prev, axis=1) | \
            (col.lengths != jnp.roll(col.lengths, 1))
    elif col.dtype.fractional:
        # group keys: NaN == NaN, -0.0 == 0.0 (Spark normalized semantics)
        d = normalize_floats(col.data)
        d_prev = jnp.roll(d, 1)
        data_diff = (d != d_prev) & ~(jnp.isnan(d) & jnp.isnan(d_prev))
    else:
        data_diff = col.data != jnp.roll(col.data, 1)
    return (v != v_prev) | (v & v_prev & data_diff)


def sorted_group_by(batch: ColumnBatch, key_indices: list[int],
                    aggs: list[AggSpec],
                    presorted: bool = False) -> ColumnBatch:
    """Group ``batch`` by key columns, computing ``aggs``.

    Output schema: key columns (original names/types) then one column per
    agg. Output capacity == input capacity; num_rows == number of groups.
    Grand aggregates (no keys) produce exactly one row, even on empty input
    (reference "reduction default-values path", aggregate.scala:514+).

    ``presorted``: the caller guarantees rows equal on the key columns
    are already contiguous (PlanNode.output_ordering) — segment
    detection only needs contiguity, so the O(n log n) sort is skipped
    (the reference's sort-aggregate-over-sorted-input fast path).

    After the sort nothing moves ``capacity`` rows by scatter: each
    aggregate is a segmented scan that leaves a group's value on its
    segment's first row (:class:`_Segments`), the result columns are
    made row-wise there, and ONE gather a dtype at the segment starts
    (a one-operand sort of the flagged row numbers) brings every group's
    key and results to the front.
    """
    cap = batch.capacity
    # percentile is order-holistic: rows must ALSO sort by the value
    # column within each key group (nulls last, so each segment's valid
    # run starts at the segment start) — Spark computes the same via
    # per-group sorted buffers (ObjectHashAggregate Percentile)
    pct_cols = sorted({s.child_index for s in aggs if s.op == "percentile"})
    if len(pct_cols) > 1:
        raise NotImplementedError(
            "percentile aggregates over multiple distinct columns in one "
            "group-by are not supported (one value-sort per group-by)")
    if key_indices or pct_cols:
        if presorted and not pct_cols:
            sb = batch
        else:
            orders = [SortOrder(i, True, True) for i in key_indices]
            orders += [SortOrder(i, True, False) for i in pct_cols]
            # the rows move in one gather a dtype, not one a leaf
            sb = ColumnBatch(
                gather_stacked(batch.columns, sort_permutation(batch, orders),
                               batch.row_mask()),
                batch.num_rows, batch.schema)
    idx = jnp.arange(cap, dtype=jnp.int32)
    if key_indices:
        real = sb.row_mask()
        differ = jnp.zeros(cap, jnp.bool_)
        for ki in key_indices:
            differ = differ | _cols_differ(sb.columns[ki])
        flag = (idx == 0) | (differ & real) | (idx == sb.num_rows)
        # rows past the first padding row never set a new flag
        flag = flag & (idx <= sb.num_rows)
        seg_id = jnp.cumsum(flag.astype(jnp.int32)) - 1
        num_groups = jnp.where(sb.num_rows > 0,
                               seg_id[jnp.maximum(sb.num_rows - 1, 0)] + 1, 0)
    else:
        if not pct_cols:
            sb = batch  # grand aggregate without percentile: no sort
        real = sb.row_mask()
        seg_id = jnp.zeros(cap, jnp.int32)
        num_groups = jnp.asarray(1, jnp.int32)  # grand aggregate: one row
        flag = idx == 0

    # every result is made on its segment's first row, beside the key:
    # one gather a dtype at the starts moves keys and results together
    rows = [sb.columns[ki] for ki in key_indices] + _agg_columns(
        sb, aggs, _Segments(jnp.roll(flag, -1), seg_id), real,
        jnp.ones(cap, jnp.bool_))
    starts = jnp.sort(jnp.where(flag, idx, cap), stable=False)
    return ColumnBatch(
        gather_stacked(rows, jnp.minimum(starts, cap - 1), idx < num_groups),
        num_groups, _output_schema(batch.schema, key_indices, aggs))


def _output_schema(schema: T.Schema, key_indices: list[int],
                   aggs: list[AggSpec]) -> T.Schema:
    """Key fields (original names/types) then one field per agg."""
    fields = [schema.fields[ki] for ki in key_indices]
    for spec in aggs:
        if spec.op == "count_star":
            fields.append(T.StructField("count(1)", T.LongType()))
            continue
        in_f = schema.fields[spec.child_index]
        fields.append(T.StructField(f"{spec.op}({in_f.name})",
                                    spec.result_type(in_f.data_type)))
    return T.Schema(fields)


def _segmented_scan(op, last: jax.Array, x: jax.Array,
                    forward: bool = False) -> jax.Array:
    """Scan ``x`` by ``op`` from each segment's last row (where ``last``
    is set; the final row's is) back to its first, which ends up holding
    the whole segment's value: ``log2(capacity)`` passes, each combining
    a row with the one ``d`` after it unless a segment ends between.
    ``forward`` scans the other way: ``last`` then marks each segment's
    FIRST row (row 0's is set), and every row ends up holding the value
    of its segment's rows up to itself (a running total)."""
    d = 1
    while d < x.shape[0]:
        shift = d if forward else -d
        x = jnp.where(last, x, op(x, jnp.roll(x, shift)))
        last = last | jnp.roll(last, shift)
        d *= 2
    return x


class _Segments:
    """The sorted path's reductions.  The rows of a segment are
    contiguous and ``last`` marks each segment's last row (``seg_id`` is
    the running count of segments), so a reduction is a segmented scan
    and nothing scatters: a result has a slot a ROW, and the slot that
    counts is the segment's first row, which holds the value of the whole
    segment, made of its own rows only (an integer sum exactly, a
    floating one without the rounding of any other group).  What
    ``_compute_agg`` derives from such results holds on those rows too;
    ``sorted_group_by`` reads them there once everything is made."""

    def __init__(self, last: jax.Array, seg_id: jax.Array):
        self.last, self.seg_id = last, seg_id

    def sum(self, x):
        return _segmented_scan(jnp.add, self.last, x)

    def min(self, x):
        return _segmented_scan(jnp.minimum, self.last, x)

    def max(self, x):
        return _segmented_scan(jnp.maximum, self.last, x)


class _OneGroup:
    """The dense path's reductions: the whole column is one group and a
    result has one slot.  ``_compute_agg`` already holds every row that is
    not ``real`` at the reduction's identity, so passing the group's
    member mask as ``real`` is all the masking there is."""

    @staticmethod
    def sum(x):
        return jnp.sum(x, dtype=x.dtype, keepdims=True)

    @staticmethod
    def min(x):
        return jnp.min(x, keepdims=True)

    @staticmethod
    def max(x):
        return jnp.max(x, keepdims=True)


def _sum_doubles(red, x: jax.Array) -> jax.Array:
    """``red.sum(x)`` for float64 ``x`` (rows that do not count held at
    zero): exact where every row of a slot is whole cents, summed as
    integers and rounded once, whatever the order of the rows
    (ops/cents.py); the plain sum elsewhere."""
    limit = min(cents.ROW_LIMIT, (1 << 62) // max(x.shape[0], 1))
    c, whole = cents.as_cents(jnp, x, limit)
    total = red.sum(c)
    exact = (red.sum((~whole).astype(jnp.int32)) == 0) \
        & (jnp.abs(total) < cents.SUM_LIMIT)
    return jnp.where(exact, cents.from_cents(jnp, total), red.sum(x))


def _agg_columns(batch: ColumnBatch, aggs: list[AggSpec], red, real,
                 out_mask) -> list[DeviceColumn]:
    """One result column per spec; ``red`` reduces rows to result slots
    (:class:`_OneGroup`: one slot; :class:`_Segments`: a slot a row, the
    one that counts being its segment's first), ``real`` marks the rows
    that count and ``out_mask`` the result slots that hold a group."""
    # a count fits int32 (capacity does); 64-bit words are emulated on the chip
    real_cnt = red.sum(real.astype(jnp.int32)).astype(jnp.int64)
    return [_compute_agg(
        spec, None if spec.op == "count_star" else
        batch.columns[spec.child_index], red, real, out_mask, real_cnt)
        for spec in aggs]


def _compute_agg(spec: AggSpec, col: DeviceColumn | None, red, real,
                 out_mask, seg_real_cnt) -> DeviceColumn:
    op = spec.op
    cap = real.shape[0]
    if op == "count_star":
        validity = out_mask
        return DeviceColumn(jnp.where(validity, seg_real_cnt, 0), validity,
                            T.LongType())

    contributes = col.validity & real
    cnt_valid = red.sum(contributes.astype(jnp.int32)).astype(jnp.int64)

    if op == "count":
        validity = out_mask
        return DeviceColumn(jnp.where(validity, cnt_valid, 0), validity,
                            T.LongType())

    if op in ("sum", "avg"):
        acc_dt = jnp.int64 if (col.dtype.integral and op == "sum") else jnp.float64
        contrib = jnp.where(contributes, col.data.astype(acc_dt),
                            jnp.zeros((), acc_dt))
        s = red.sum(contrib) if acc_dt == jnp.int64 else \
            _sum_doubles(red, contrib)
        if op == "avg":
            data = cents.mean(jnp, s, jnp.maximum(cnt_valid, 1))
            rtype = T.DoubleType()
        elif col.dtype.integral:
            data, rtype = s, T.LongType()
        else:
            data, rtype = s.astype(jnp.float64), T.DoubleType()
        validity = (cnt_valid > 0) & out_mask
        return DeviceColumn(jnp.where(validity, data, jnp.zeros((), data.dtype)),
                            validity, rtype)

    if op in ("min", "max"):
        if col.dtype.fractional:
            # Spark: NaN is the largest value; no 64-bit bitcasts on TPU, so
            # mask NaNs to +/-inf identities and patch the all/any-NaN cases.
            x = normalize_floats(col.data)
            isnan = jnp.isnan(x)
            nan_cnt = red.sum((contributes & isnan).astype(jnp.int32))
            nonnan_cnt = red.sum((contributes & ~isnan).astype(jnp.int32))
            if op == "min":
                masked = jnp.where(contributes & ~isnan, x,
                                   jnp.full((), jnp.inf, x.dtype))
                r = red.min(masked)
                # min is NaN only when every contributing value is NaN
                data = jnp.where((nonnan_cnt == 0) & (nan_cnt > 0),
                                 jnp.full((), jnp.nan, x.dtype), r)
            else:
                masked = jnp.where(contributes & ~isnan, x,
                                   jnp.full((), -jnp.inf, x.dtype))
                r = red.max(masked)
                # max is NaN when any contributing value is NaN
                data = jnp.where(nan_cnt > 0, jnp.full((), jnp.nan, x.dtype), r)
        elif isinstance(col.dtype, T.StringType):
            # lexicographic min/max by a per-segment sort: order rows by
            # (segment, non-contributing-last, string key words) and take
            # each segment's first row (reference: cudf groupby min/max
            # string aggregations)
            from spark_rapids_tpu.ops.sort import encode_key_operands
            seg_id = red.seg_id     # the sort path only (_dense_covers)
            words = encode_key_operands(col, ascending=(op == "min"))
            flag = (~contributes).astype(jnp.uint8)
            iota = jnp.arange(cap, dtype=jnp.int32)
            # a segment still begins on the same row after this sort
            order = lax.sort([seg_id, flag, *words, iota],
                             num_keys=2 + len(words), is_stable=True)[-1]
            validity = (cnt_valid > 0) & out_mask
            return DeviceColumn(
                jnp.where(validity[:, None], col.data[order], 0), validity,
                col.dtype, jnp.where(validity, col.lengths[order], 0))
        else:
            info = jnp.iinfo(col.data.dtype) if col.data.dtype != jnp.bool_ else None
            if col.data.dtype == jnp.bool_:
                d = col.data.astype(jnp.int32)
                ident = 1 if op == "min" else 0
                masked = jnp.where(contributes, d, ident)
                r = (red.min if op == "min" else red.max)(masked)
                data = r.astype(jnp.bool_)
            else:
                ident = info.max if op == "min" else info.min
                masked = jnp.where(contributes, col.data, ident)
                data = (red.min if op == "min" else red.max)(masked)
        validity = (cnt_valid > 0) & out_mask
        zero = jnp.zeros((), data.dtype)
        return DeviceColumn(jnp.where(validity, data, zero), validity,
                            col.dtype)

    if op == "percentile":
        # rows arrive sorted (keys, value asc, value-nulls last), so each
        # segment's valid values occupy [seg_start, seg_start + cnt_valid);
        # linear interpolation at q*(n-1), Spark Percentile semantics
        q = spec.param
        assert q is not None, "percentile AggSpec needs param=q"
        pos = (cnt_valid - 1).astype(jnp.float64) * q
        lo = jnp.floor(pos).astype(jnp.int32)
        hi = jnp.ceil(pos).astype(jnp.int32)
        frac = pos - lo
        base = jnp.arange(cap, dtype=jnp.int32)  # a result's own row
        x = col.data.astype(jnp.float64)
        vlo = x[jnp.clip(base + lo, 0, cap - 1)]
        vhi = x[jnp.clip(base + hi, 0, cap - 1)]
        data = vlo + (vhi - vlo) * frac
        validity = (cnt_valid > 0) & out_mask
        return DeviceColumn(jnp.where(validity, data, 0.0), validity,
                            T.DoubleType())

    if op in ("first", "last", "first_non_null", "last_non_null"):
        # index of first/last row per segment; *_non_null picks among valid
        # rows only (Spark first/last ignoreNulls=true), plain variants use
        # row position regardless of validity (ignoreNulls=false default)
        ignore_nulls = op.endswith("non_null")
        eligible = contributes if ignore_nulls else real
        idx = jnp.arange(cap, dtype=jnp.int32)
        if op.startswith("first"):
            masked_idx = jnp.where(eligible, idx, cap)
            pick = red.min(masked_idx)
        else:
            masked_idx = jnp.where(eligible, idx, -1)
            pick = red.max(masked_idx)
        pick = jnp.clip(pick, 0, cap - 1)
        has_eligible = cnt_valid > 0 if ignore_nulls else seg_real_cnt > 0
        validity = col.validity[pick] & out_mask & has_eligible
        if col.is_var_width:
            data = jnp.where(validity[:, None], col.data[pick], 0)
            return DeviceColumn(data, validity, col.dtype,
                                jnp.where(validity, col.lengths[pick], 0))
        data = jnp.where(validity, col.data[pick], jnp.zeros((), col.data.dtype))
        return DeviceColumn(data, validity, col.dtype)

    raise NotImplementedError(f"aggregate op {op}")


# ---------------------------------------------------------------------------
# the update's entry point: sort-free for few groups, the sort otherwise
# ---------------------------------------------------------------------------

def _dense_covers(batch: ColumnBatch, aggs: list[AggSpec]) -> bool:
    """False for the specs that need the rows sorted: ``percentile``
    (value order inside a group) and string ``min``/``max`` (a
    lexicographic order no masked reduction gives)."""
    for spec in aggs:
        if spec.op == "percentile":
            return False
        if spec.op in ("min", "max") and batch.columns[spec.child_index].is_string:
            return False
    return True


def _key_eq_operands(col: DeviceColumn) -> list[jax.Array]:
    """1-D operands whose element-wise equality is ``_cols_differ``'s
    key equality: null == null, NaN == NaN, -0.0 == 0.0, strings by
    bytes and length.  A null's data is held at zero so only its
    validity speaks."""
    if col.is_string:
        ops = string_key_words(col) + [col.lengths]
    elif col.dtype.fractional:
        x = normalize_floats(col.data)
        isnan = jnp.isnan(x)
        ops = [isnan, jnp.where(isnan, jnp.zeros((), x.dtype), x)]
    else:
        ops = [col.data]
    v = col.validity
    return [v] + [jnp.where(v, o, jnp.zeros((), o.dtype)) for o in ops]


def _discover_groups(batch: ColumnBatch, key_indices: list[int], table: int):
    """Assign each real row the id of its key among the first ``table``
    distinct keys, in order of first appearance.

    Each pass of the ``while_loop`` takes the first real row without a
    group, reads its key and marks every such row whose key equals it;
    it stops when no real row is left or ``table`` groups are taken.
    Returns ``(gid, reps, n, overflow)``: ``gid`` int32[capacity] (the
    group id; ``table`` on padding rows, -1 on rows left over), ``reps``
    int32[table] (each group's first row), ``n`` groups found, and
    ``overflow`` — real rows remain, so the input has more than
    ``table`` keys."""
    cap = batch.capacity
    operands = [o for ki in key_indices
                for o in _key_eq_operands(batch.columns[ki])]
    idx = jnp.arange(cap, dtype=jnp.int32)

    def first_unassigned(gid):
        r = jnp.min(jnp.where(gid < 0, idx, cap))
        return jnp.minimum(r, cap - 1), r < cap

    def body(state):
        gid, reps, n, r, _more = state
        member = gid < 0
        for o in operands:
            member = member & (o == o[r])
        gid = jnp.where(member, n, gid)
        return (gid, reps.at[n].set(r), n + 1, *first_unassigned(gid))

    gid = jnp.where(batch.row_mask(), -1, table).astype(jnp.int32)
    init = (gid, jnp.zeros(table, jnp.int32), jnp.asarray(0, jnp.int32),
            *first_unassigned(gid))
    gid, reps, n, _r, more = lax.while_loop(
        lambda s: s[4] & (s[2] < table), body, init)
    return gid, reps, n, more


def _dense_group_by(batch: ColumnBatch, key_indices: list[int],
                    aggs: list[AggSpec], gid, reps, n) -> ColumnBatch:
    """The group rows from discovered groups: one masked reduction of the
    unsorted input columns per group, the group table sorted ascending
    by key (nulls first) and padded to the input's capacity — what
    ``sorted_group_by`` emits."""
    cap, table = batch.capacity, reps.shape[0]
    one_slot = jnp.ones(1, jnp.bool_)

    def group_row(member):
        return _agg_columns(batch, aggs, _OneGroup, member, one_slot)

    def body(g, acc):
        return jax.tree.map(
            lambda a, r: lax.dynamic_update_slice_in_dim(a, r, g, 0),
            acc, group_row(gid == g))

    row = jax.eval_shape(group_row, jax.ShapeDtypeStruct((cap,), jnp.bool_))
    acc = jax.tree.map(
        lambda r: jnp.zeros((table,) + r.shape[1:], r.dtype), row)
    agg_cols = lax.fori_loop(0, n, body, acc)
    key_cols = gather_columns([batch.columns[ki] for ki in key_indices],
                              reps, n)
    groups = ColumnBatch(key_cols + agg_cols, n,
                         _output_schema(batch.schema, key_indices, aggs))
    if key_indices:
        groups = sort_batch(groups, [SortOrder(i, True, True)
                                     for i in range(len(key_indices))])
    return _pad_jit(groups, cap)


def group_by_update(batch: ColumnBatch, key_indices: list[int],
                    aggs: list[AggSpec], presorted: bool = False):
    """``sorted_group_by``'s rows, without the sort where the groups are
    few: returns ``(group_rows, dense)``.

    ``group_rows`` is what ``sorted_group_by(batch, key_indices, aggs,
    presorted)`` returns — same schema, capacity, validity
    canonicalisation and row order (ascending by key, nulls first).
    ``dense`` (bool scalar) says which branch of the program produced
    it: True when the input held at most ``_DENSE_MAX_GROUPS`` distinct
    keys, so discovery assigned every real row and the groups were
    reduced by mask; False when rows remained and ``lax.cond`` ran
    ``sorted_group_by`` (discovery's passes over the keys are then the
    price, at most 64), or when a spec needs the sort (``_dense_covers``,
    decided at trace time, no ``cond``).  No keys — a grand aggregate —
    is one group and never sorts."""
    if not _dense_covers(batch, aggs):
        return (sorted_group_by(batch, key_indices, aggs, presorted),
                jnp.asarray(False))
    if not key_indices:
        real = batch.row_mask()
        out = _dense_group_by(
            batch, key_indices, aggs, jnp.where(real, 0, 1),
            jnp.zeros(1, jnp.int32), jnp.asarray(1, jnp.int32))
        return out, jnp.asarray(True)
    table = min(_DENSE_MAX_GROUPS, batch.capacity)
    gid, reps, n, overflow = _discover_groups(batch, key_indices, table)
    out = lax.cond(
        overflow,
        lambda: sorted_group_by(batch, key_indices, aggs, presorted),
        lambda: _dense_group_by(batch, key_indices, aggs, gid, reps, n))
    return out, ~overflow
