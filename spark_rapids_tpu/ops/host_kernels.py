"""Host (CPU oracle) batch kernels: sort, group-by, filter, concat, slice.

The reference uses CPU Spark itself as the differential-test oracle
(tests/SparkQueryCompareTestSuite.scala:153-167,
integration_tests asserts.py:290 ``assert_gpu_and_cpu_are_equal_collect``).
This framework is standalone, so the CPU engine lives here: numpy-vectorized
implementations with exactly Spark's ordering/equality semantics (null
ordering, NaN largest + NaN==NaN for keys, -0.0==0.0).  The benchmark
(benchmark/run.py) checks every cell's rows against this engine.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.host.batch import HostBatch, HostColumn
from spark_rapids_tpu.ops import cents
from spark_rapids_tpu.ops.segmented import AggSpec
from spark_rapids_tpu.ops.sort import SortOrder

__all__ = [
    "host_sort_permutation", "host_sort", "host_filter", "host_concat",
    "host_slice", "host_group_by", "host_take",
    "host_join", "host_join_output",
]


def _f64_sortable_bits(x: np.ndarray) -> np.ndarray:
    """IEEE754 -> uint64 total order (NaN above +inf, -0.0 == +0.0)."""
    x = x.astype(np.float64)
    x = np.where(x == 0.0, 0.0, x)                  # -0.0 -> +0.0
    x = np.where(np.isnan(x), np.float64("nan"), x)  # canonical NaN
    bits = x.view(np.uint64).copy()
    neg = bits >> np.uint64(63) != 0
    bits = np.where(neg, ~bits, bits | np.uint64(1) << np.uint64(63))
    # canonical NaN (0x7ff8...) encodes above +inf already via the flip
    return bits


def _key_codes(col: HostColumn, ascending: bool,
               nulls_first: bool) -> list[np.ndarray]:
    """Encode a column as sortable integer key arrays (most-significant
    first).  Null indicator precedes the value key."""
    v = col.validity
    null_key = np.where(v, np.uint8(1 if nulls_first else 0),
                        np.uint8(0 if nulls_first else 1))
    dt = col.dtype
    if isinstance(dt, T.StringType):
        s = np.array(["" if x is None else x for x in col.data], dtype=str)
        _, codes = np.unique(s, return_inverse=True)
        codes = codes.astype(np.int64)
        val = codes if ascending else -codes
    elif dt.fractional:
        bits = _f64_sortable_bits(col.data)
        val = bits if ascending else ~bits
    else:
        u = col.data.astype(np.int64).view(np.uint64) ^ (np.uint64(1) << np.uint64(63))
        val = u if ascending else ~u
    val = np.where(v, val, np.zeros((), val.dtype))
    return [null_key, val]


def host_sort_permutation(batch: HostBatch,
                          orders: Sequence[SortOrder]) -> np.ndarray:
    """Stable permutation sorting the batch by ``orders``."""
    keys: list[np.ndarray] = []
    for o in orders:
        keys.extend(_key_codes(batch.columns[o.child_index], o.ascending,
                               o.resolved_nulls_first))
    if not keys:
        return np.arange(batch.num_rows)
    # np.lexsort: LAST key is primary -> reverse
    return np.lexsort(list(reversed(keys)))


def host_sort(batch: HostBatch, orders: Sequence[SortOrder]) -> HostBatch:
    perm = host_sort_permutation(batch, orders)
    return HostBatch([c.take(perm) for c in batch.columns], batch.schema)


def host_take(batch: HostBatch, indices: np.ndarray) -> HostBatch:
    return HostBatch([c.take(indices) for c in batch.columns], batch.schema)


def host_filter(batch: HostBatch, mask: np.ndarray) -> HostBatch:
    return HostBatch([c.filter(mask) for c in batch.columns], batch.schema)


def host_slice(batch: HostBatch, start: int, end: int) -> HostBatch:
    idx = np.arange(max(start, 0), min(end, batch.num_rows))
    return host_take(batch, idx)


def host_concat(batches: Sequence[HostBatch]) -> HostBatch:
    assert batches, "empty concat"
    schema = batches[0].schema
    cols = []
    for i, f in enumerate(schema):
        data = np.concatenate([b.columns[i].data for b in batches])
        validity = np.concatenate([b.columns[i].validity for b in batches])
        cols.append(HostColumn(data, validity, f.data_type))
    return HostBatch(cols, schema)


# ---------------------------------------------------------------------------
# group-by (oracle analog of ops.segmented.sorted_group_by)
# ---------------------------------------------------------------------------

def _group_codes(col: HostColumn) -> list[np.ndarray]:
    """Key arrays (null indicator + value code) where equal values (Spark
    key equality: null==null, NaN==NaN, -0.0==0.0) get equal codes, ordered
    ascending with nulls first.  The separate null indicator avoids any
    value/null sentinel collision."""
    v = col.validity
    dt = col.dtype
    if isinstance(dt, T.StringType):
        s = np.array(["" if x is None else x for x in col.data], dtype=str)
        _, codes = np.unique(s, return_inverse=True)
        codes = codes.astype(np.int64)
    elif dt.fractional:
        codes = _f64_sortable_bits(col.data).view(np.int64)
    else:
        codes = col.data.astype(np.int64)
    return [v.astype(np.uint8), np.where(v, codes, np.int64(0))]


def _sum_doubles(vals: np.ndarray) -> np.float64:
    """One group's sum as doubles: whole cents summed as integers and
    rounded once (ops/cents.py), as the device's sum is."""
    x = vals.astype(np.float64)
    c, whole = cents.as_cents(np, x)
    total = int(c.sum(dtype=np.int64))
    if whole.all() and abs(total) < cents.SUM_LIMIT \
            and len(x) * cents.ROW_LIMIT <= 1 << 62:
        return cents.from_cents(np, np.asarray(total, np.int64))
    return np.sum(x)


def _agg_reduce(spec: AggSpec, col: HostColumn | None, seg_starts: np.ndarray,
                seg_lens: np.ndarray, perm: np.ndarray,
                in_type: T.DataType) -> HostColumn:
    """Compute one aggregate per segment of the permuted batch."""
    ngroups = len(seg_starts)
    res_type = spec.result_type(in_type)
    if spec.op == "count_star":
        data = seg_lens.astype(np.int64)
        return HostColumn(data, np.ones(ngroups, np.bool_), T.LongType())
    assert col is not None
    pv = col.validity[perm]
    out_valid = np.zeros(ngroups, np.bool_)
    if isinstance(res_type, T.StringType):
        out = np.empty(ngroups, dtype=object)
    else:
        out = np.zeros(ngroups, dtype=res_type.np_dtype)
    pd = col.data[perm]
    for g in range(ngroups):
        sl = slice(seg_starts[g], seg_starts[g] + seg_lens[g])
        seg_d, seg_v = pd[sl], pv[sl]
        vals = seg_d[seg_v]
        if spec.op == "count":
            out[g] = len(vals)
            out_valid[g] = True
            continue
        if spec.op in ("first", "last"):
            # first/last including nulls (ignoreNulls=False)
            if seg_lens[g] > 0:
                i = 0 if spec.op == "first" else seg_lens[g] - 1
                if seg_v[i]:
                    out[g] = seg_d[i]
                    out_valid[g] = True
            continue
        if len(vals) == 0:
            continue
        if spec.op == "sum":
            if res_type.integral:
                out[g] = np.int64(np.sum(vals.astype(np.int64), dtype=np.int64))
            else:
                out[g] = _sum_doubles(vals)
            out_valid[g] = True
        elif spec.op == "min":
            out[g] = _nan_aware_min(vals, in_type)
            out_valid[g] = True
        elif spec.op == "max":
            out[g] = _nan_aware_max(vals, in_type)
            out_valid[g] = True
        elif spec.op == "avg":
            out[g] = cents.mean(np, np.asarray(_sum_doubles(vals)),
                                np.asarray(len(vals), np.int64))
            out_valid[g] = True
        elif spec.op == "first_non_null":
            out[g] = vals[0]
            out_valid[g] = True
        elif spec.op == "last_non_null":
            out[g] = vals[-1]
            out_valid[g] = True
        elif spec.op == "percentile":
            # same algorithm as the device kernel (sort + linear
            # interpolation at q*(n-1)) so differential tests compare
            # bit-for-bit, not vs np.percentile's internals
            v = np.sort(vals.astype(np.float64))
            pos = (len(v) - 1) * spec.param
            lo, hi = int(np.floor(pos)), int(np.ceil(pos))
            out[g] = v[lo] + (v[hi] - v[lo]) * (pos - lo)
            out_valid[g] = True
        else:
            raise NotImplementedError(spec.op)
    return HostColumn(out, out_valid, res_type)


def _nan_aware_min(vals, dt: T.DataType):
    if isinstance(dt, T.StringType):
        return min(vals)
    if dt.fractional:
        # Spark: NaN is largest -> min ignores NaN unless all NaN
        nn = vals[~np.isnan(vals.astype(np.float64))]
        return np.min(nn) if len(nn) else vals[0]
    return np.min(vals)


def _nan_aware_max(vals, dt: T.DataType):
    if isinstance(dt, T.StringType):
        return max(vals)
    if dt.fractional:
        # Spark: NaN is the LARGEST value, so any NaN wins outright
        # (fuzz-found: argmax over inf-masked values picked a real +inf
        # when both +inf and NaN were present)
        f = vals.astype(np.float64)
        if np.isnan(f).any():
            return np.asarray(np.nan, dtype=vals.dtype)[()]
        return np.max(vals)
    return np.max(vals)


def host_group_by(batch: HostBatch, key_indices: Sequence[int],
                  aggs: Sequence[AggSpec]) -> HostBatch:
    """Group ``batch`` by keys computing ``aggs``; output = keys then aggs,
    groups in ascending key order (matches device sorted_group_by)."""
    n = batch.num_rows
    if key_indices:
        codes: list[np.ndarray] = []
        for k in key_indices:
            codes.extend(_group_codes(batch.columns[k]))
        perm = np.lexsort(list(reversed(codes)))
        pc = [c[perm] for c in codes]
        if n == 0:
            boundaries = np.zeros(0, np.bool_)
        else:
            differ = np.zeros(n, np.bool_)
            differ[0] = True
            for c in pc:
                differ[1:] |= c[1:] != c[:-1]
            boundaries = differ
        seg_starts = np.nonzero(boundaries)[0]
        seg_lens = np.diff(np.append(seg_starts, n))
    else:
        perm = np.arange(n)
        seg_starts = np.zeros(1, np.int64)
        seg_lens = np.array([n], np.int64)

    out_cols: list[HostColumn] = []
    out_fields: list[T.StructField] = []
    for k in key_indices:
        col = batch.columns[k]
        out_cols.append(col.take(perm[seg_starts]))
        out_fields.append(batch.schema.fields[k])
    for spec in aggs:
        col = batch.columns[spec.child_index] if spec.op != "count_star" else None
        in_t = col.dtype if col is not None else T.LongType()
        out_cols.append(_agg_reduce(spec, col, seg_starts, seg_lens, perm, in_t))
        arg = "1" if spec.op == "count_star" else batch.schema.names[spec.child_index]
        name = f"count({arg})" if spec.op == "count_star" else f"{spec.op}({arg})"
        out_fields.append(T.StructField(name, spec.result_type(in_t)))
    return HostBatch(out_cols, T.Schema(out_fields))


# ---------------------------------------------------------------------------
# joins (CPU oracle for ops/join.py; Spark key semantics: null keys never
# match, NaN==NaN, -0.0==0.0)
# ---------------------------------------------------------------------------

def _join_key(cols: list[HostColumn], i: int):
    """Row i's key tuple, or None when any key column is null."""
    out = []
    for c in cols:
        if not c.validity[i]:
            return None
        v = c.data[i]
        if isinstance(c.dtype, (T.FloatType, T.DoubleType)):
            f = float(v)
            if f != f:
                v = "NaN"          # NaN == NaN for join keys
            elif f == 0.0:
                v = 0.0            # -0.0 == 0.0
            else:
                v = f
        elif isinstance(v, np.generic):
            v = v.item()
        out.append(v)
    return tuple(out)


def host_join(lb: HostBatch, rb: HostBatch, lkeys: Sequence[int],
              rkeys: Sequence[int], join_type: str):
    """Returns (li, ri, l_take, r_take) int64/bool arrays (see
    ops/join.py join_indices for the contract)."""
    nl, nr = lb.num_rows, rb.num_rows
    li, ri, lt, rt = [], [], [], []
    if join_type == "cross":
        for i in range(nl):
            for j in range(nr):
                li.append(i); ri.append(j); lt.append(True); rt.append(True)
    else:
        lcols = [lb.columns[k] for k in lkeys]
        rcols = [rb.columns[k] for k in rkeys]
        index: dict = {}
        for j in range(nr):
            k = _join_key(rcols, j)
            if k is not None:
                index.setdefault(k, []).append(j)
        matched_r = np.zeros(nr, np.bool_)
        for i in range(nl):
            k = _join_key(lcols, i)
            matches = index.get(k, []) if k is not None else []
            if join_type == "semi":
                if matches:
                    li.append(i); ri.append(0); lt.append(True); rt.append(False)
            elif join_type == "anti":
                if not matches:
                    li.append(i); ri.append(0); lt.append(True); rt.append(False)
            elif matches:
                for j in matches:
                    matched_r[j] = True
                    li.append(i); ri.append(j); lt.append(True); rt.append(True)
            elif join_type in ("left", "full"):
                li.append(i); ri.append(0); lt.append(True); rt.append(False)
        if join_type == "full":
            for j in range(nr):
                if not matched_r[j]:
                    li.append(0); ri.append(j); lt.append(False); rt.append(True)
    return (np.asarray(li, np.int64), np.asarray(ri, np.int64),
            np.asarray(lt, np.bool_), np.asarray(rt, np.bool_))


def host_join_output(lb: HostBatch, rb: HostBatch, li, ri, lt, rt,
                     schema, include_right: bool) -> HostBatch:
    cols = []
    for c in lb.columns:
        cols.append(_take_masked(c, li, lt))
    if include_right:
        for c in rb.columns:
            cols.append(_take_masked(c, ri, rt))
    return HostBatch(cols, schema)


def _take_masked(c: HostColumn, idx: np.ndarray, take: np.ndarray) -> HostColumn:
    n = len(idx)
    if len(c.data) == 0:
        data = np.zeros(n, dtype=c.data.dtype) if c.data.dtype != object \
            else np.full(n, None, dtype=object)
        return HostColumn(data, np.zeros(n, np.bool_), c.dtype)
    data = c.data[np.clip(idx, 0, len(c.data) - 1)]
    validity = c.validity[np.clip(idx, 0, len(c.data) - 1)] & take
    if c.data.dtype == object:
        data = np.where(validity, data, None)
    else:
        data = np.where(validity, data, np.zeros((), c.data.dtype))
    return HostColumn(data, validity, c.dtype)
