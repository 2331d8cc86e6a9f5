"""Equi-join device kernels: sort-merge composed from XLA primitives.

The reference joins through cuDF hash-join kernels
(shims/spark300/.../GpuHashJoin.scala:300-326 doJoinLeftRight:
innerJoin/leftJoin/leftSemiJoin/leftAntiJoin/fullJoin).  XLA has no
device hash table, but `lax.sort` is excellent on TPU, so this kernel is
sort-based (SURVEY.md §7 "hard parts"):

1. **key ids**: concatenate both sides' key columns, one stable
   multi-operand sort, segment boundaries -> dense int32 rank per row,
   comparable across sides (Spark key semantics: NaN==NaN, -0.0==0.0,
   null keys never match).
2. **probe**: sort right ids; per left row `searchsorted` gives the
   contiguous match range [start, end).
3. **count** (phase 1): per-left-row output counts by join type; total
   is materialized to host ONCE at the batch boundary to pick a static
   pow2 output capacity (XLA static-shape discipline, columnar/batch.py).
4. **gather** (phase 2): output slot j -> (left row, right row) via
   cumsum + searchsorted; full-outer appends unmatched right rows by
   scatter.  Gathers build the output columns.

Right outer join is the exec layer's job (swap sides, reorder columns,
exec/joins.py), matching the reference's build-side flip.

A join on ONE integral key streams: the build side is sorted once
(:func:`build_prepare_fast`) and each stream batch is probed against it
with no sort.  How the probe finds a key's run in the sorted build is
chosen once per build, on the host, from the key range the build holds
(:func:`direct_table_size`):

* **dense keys** (surrogate keys, day numbers: the range is no wider
  than a table the engine can always afford): :func:`build_direct_table`
  makes ``table[k - kmin] = (run start, run length)`` once, and
  :func:`probe_direct` reads it by address: one gather of table rows a
  stream batch, whatever the build's size;
* **anything else** (a hashed id, a natural key): :func:`probe_fast`,
  two ``searchsorted`` over the sorted keys, each ``log2(capacity)``
  dependent gathers over every stream row.

Both return the same ``(start, cnt, perm, out_cnt)`` and ``total``.
"""
from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch, round_capacity
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.ops.segmented import _cols_differ
from spark_rapids_tpu.ops.sort import encode_key_operands

__all__ = ["join_probe", "join_total", "join_indices_from_probe",
           "gather_join_output", "JOIN_TYPES", "DirectBuild",
           "direct_table_size"]

JOIN_TYPES = ("inner", "left", "semi", "anti", "full", "cross")

_I32MAX = jnp.iinfo(jnp.int32).max


def _combined_key_column(lc: DeviceColumn, rc: DeviceColumn) -> DeviceColumn:
    """Concatenate one key column from both sides (string widths padded
    to the max of the two)."""
    assert type(lc.dtype) is type(rc.dtype), (lc.dtype, rc.dtype)
    validity = jnp.concatenate([lc.validity, rc.validity])
    if lc.is_var_width:
        w = max(lc.max_len, rc.max_len)
        ld = jnp.pad(lc.data, ((0, 0), (0, w - lc.max_len)))
        rd = jnp.pad(rc.data, ((0, 0), (0, w - rc.max_len)))
        return DeviceColumn(jnp.concatenate([ld, rd]), validity, lc.dtype,
                            jnp.concatenate([lc.lengths, rc.lengths]))
    return DeviceColumn(jnp.concatenate([lc.data, rc.data]), validity,
                        lc.dtype)


def _key_ids(lbatch: ColumnBatch, rbatch: ColumnBatch,
             lkeys: Sequence[int], rkeys: Sequence[int]):
    """Dense cross-side key ranks.

    Returns (lid[CL], rid[CR]): int32 rank of each row's key tuple;
    rows that are padding or have any null key get _I32MAX on the left
    and _I32MAX-1 on the right so they never match anything.
    """
    cl, cr = lbatch.capacity, rbatch.capacity
    cc = cl + cr
    cols = [_combined_key_column(lbatch.columns[a], rbatch.columns[b])
            for a, b in zip(lkeys, rkeys)]
    valid = jnp.concatenate([lbatch.row_mask(), rbatch.row_mask()])
    for c in cols:
        valid = valid & c.validity

    operands: list[jax.Array] = [(~valid).astype(jnp.uint8)]  # invalid last
    for c in cols:
        operands.extend(encode_key_operands(c, True))
    iota = jnp.arange(cc, dtype=jnp.int32)
    sorted_ops = lax.sort(operands + [iota], num_keys=len(operands),
                          is_stable=True)
    order = sorted_ops[-1]

    differ = jnp.zeros(cc, jnp.bool_)
    for c in cols:
        sc = DeviceColumn(c.data[order], c.validity[order], c.dtype,
                          None if c.lengths is None else c.lengths[order])
        differ = differ | _cols_differ(sc)
    pos = jnp.arange(cc, dtype=jnp.int32)
    seg = jnp.cumsum(((pos > 0) & differ).astype(jnp.int32))
    ids = jnp.zeros(cc, jnp.int32).at[order].set(seg)
    lid = jnp.where(valid[:cl], ids[:cl], _I32MAX)
    rid = jnp.where(valid[cl:], ids[cl:], _I32MAX - 1)
    return lid, rid


def _probe(lbatch: ColumnBatch, rbatch: ColumnBatch,
           lkeys: Sequence[int], rkeys: Sequence[int], join_type: str):
    """Per-left-row match ranges + per-row output counts."""
    cl, cr = lbatch.capacity, rbatch.capacity
    real_l = lbatch.row_mask()
    num_r = rbatch.num_rows
    if join_type == "cross":
        start = jnp.zeros(cl, jnp.int32)
        cnt = jnp.where(real_l, num_r, 0).astype(jnp.int32)
        rsort_perm = jnp.arange(cr, dtype=jnp.int32)
        out_cnt = cnt
        return start, cnt, rsort_perm, out_cnt, None
    lid, rid = _key_ids(lbatch, rbatch, lkeys, rkeys)
    sorted_rid, rsort_perm = lax.sort(
        [rid, jnp.arange(cr, dtype=jnp.int32)], num_keys=1, is_stable=True)
    start = jnp.searchsorted(sorted_rid, lid, side="left").astype(jnp.int32)
    end = jnp.searchsorted(sorted_rid, lid, side="right").astype(jnp.int32)
    cnt = jnp.where(lid == _I32MAX, 0, end - start)
    out_cnt = _out_cnt(cnt, real_l, join_type)
    unmatched_r = None
    if join_type == "full":
        sorted_lid = lax.sort([lid], num_keys=1)[0]
        s = jnp.searchsorted(sorted_lid, rid, side="left")
        e = jnp.searchsorted(sorted_lid, rid, side="right")
        unmatched_r = rbatch.row_mask() & (e == s)
    return start, cnt, rsort_perm, out_cnt, unmatched_r


def build_prepare_fast(rbatch: ColumnBatch, rkey: int):
    """Sort the build side ONCE by its (single, integral) key.

    Returns ``(sorted_key, perm, nv)``: the build keys sorted ascending
    with the ``nv`` valid entries first and every invalid/padding slot
    rewritten to the dtype max so the array stays globally sorted (probe
    ranges are clipped to ``nv``, which keeps genuine max-valued keys —
    they live at positions < nv).  This is the streaming-join analog of
    the reference's build-side hash table (GpuHashJoin build side,
    GpuHashJoin.scala:193-249): built once, probed per stream batch with
    no per-batch sort.
    """
    col = rbatch.columns[rkey]
    valid = col.validity & rbatch.row_mask()
    cr = rbatch.capacity
    iota = jnp.arange(cr, dtype=jnp.int32)
    flag = (~valid).astype(jnp.uint8)
    _, skey, perm = lax.sort([flag, col.data, iota], num_keys=2,
                             is_stable=True)
    nv = jnp.sum(valid, dtype=jnp.int32)
    maxv = jnp.iinfo(col.data.dtype).max
    skey = jnp.where(iota < nv, skey, maxv)
    return skey, perm, nv


def probe_fast(lbatch: ColumnBatch, lkey: int, sorted_key, perm, nv,
               join_type: str):
    """Per-stream-batch probe against a prepared build side: two
    searchsorted passes, zero sorts.  Same contract as the heavy phase of
    :func:`join_probe` (without full-outer bookkeeping — streaming full
    outer tracks matched build rows in the gather phase instead)."""
    col = lbatch.columns[lkey]
    lvalid = col.validity & lbatch.row_mask()
    start = jnp.searchsorted(sorted_key, col.data, side="left").astype(jnp.int32)
    end = jnp.searchsorted(sorted_key, col.data, side="right").astype(jnp.int32)
    end = jnp.minimum(end, nv)
    start = jnp.minimum(start, end)
    cnt = jnp.where(lvalid, end - start, 0)
    out_cnt = _out_cnt(cnt, lbatch.row_mask(), join_type)
    total = jnp.sum(out_cnt, dtype=jnp.int64)
    return (start, cnt, perm, out_cnt, None), total


def _key_range(sorted_key, nv):
    """Smallest and largest valid key of a prepared build side (with
    ``nv == 0`` both are the padding value and mean nothing)."""
    return sorted_key[0], sorted_key[jnp.maximum(nv - 1, 0)]


def build_key_stats(sorted_key, nv):
    """int64[3] ``[nv, kmin, kmax]`` of a prepared build side: what the
    host needs to choose the probe (:func:`direct_table_size`), in one
    array so it is one fetch."""
    kmin, kmax = _key_range(sorted_key, nv)
    return jnp.stack([nv, kmin, kmax]).astype(jnp.int64)


#: a direct-address table may always have this many entries (4 MB of
#: int32 a column), however small the build side is
_TABLE_FLOOR = 1 << 20


def direct_table_size(nv: int, kmin: int, kmax: int,
                      capacity: int) -> int | None:
    """The one rule that picks the probe: entries of the direct-address
    table for a build side holding ``nv`` valid keys in ``[kmin, kmax]``
    (python ints: an int64 range does not overflow) in a batch of
    ``capacity`` slots, or None where the keys are not dense and the
    probe searches.  Dense = the key range, rounded to a capacity
    bucket, is at most ``max(2^20, 8 x capacity)``: the table then costs
    no more than a few of the build's own columns."""
    size = round_capacity(kmax - kmin + 1)
    dense = nv > 0 and size <= max(_TABLE_FLOOR, 8 * capacity)
    return size if dense else None


class DirectBuild(NamedTuple):
    """A prepared build side whose keys are dense (a pytree of arrays:
    ``jax.device_put`` ships it; its type is the choice of probe)."""
    perm: jax.Array    # int32[capacity]: sorted position -> build row
    table: jax.Array   # int32[T, 2]: for key kmin + j, the sorted position
    #                    of its run and the run's length (0 = key absent)
    kmin: jax.Array    # scalars, key dtype
    kmax: jax.Array


def _offset_dtype(key_dtype):
    """Keys are subtracted in at least 32 bits: a dense int8/int16 range
    can be wider than its own dtype holds."""
    return jnp.int64 if jnp.dtype(key_dtype).itemsize > 4 else jnp.int32


def build_direct_table(sorted_key, perm, nv, size: int) -> DirectBuild:
    """Direct-address table over a prepared build side (the output of
    :func:`build_prepare_fast`) whose key range fits ``size`` entries.

    Every run of equal keys in the sorted build writes its first
    position, and its end, at ``key - kmin``: two scatters from the
    build's rows, once per build, so duplicates keep their run.  Rows
    that are not a run's first (or last) are sent past the table's end,
    each to an index of its own: unique indices are what lets XLA scatter
    without first sorting an operand of the build's size (a search of
    the ``size`` candidate keys instead costs 151 ms at 2^19, the two
    scatters 6 ms; PERF.md, PR 28)."""
    wide = _offset_dtype(sorted_key.dtype)
    i = jnp.arange(sorted_key.shape[0], dtype=jnp.int32)
    live = i < nv
    kmin, kmax = _key_range(sorted_key, nv)
    first = live & ((i == 0) | (sorted_key != jnp.roll(sorted_key, 1)))
    last = live & ((i == nv - 1) | (sorted_key != jnp.roll(sorted_key, -1)))
    offset = (sorted_key.astype(wide) - kmin.astype(wide)).astype(jnp.int32)
    nowhere = size + i

    def scatter(at, values):
        return jnp.zeros(size, jnp.int32).at[
            jnp.where(at, offset, nowhere)].set(
                values, unique_indices=True, mode="drop")
    start = scatter(first, i)
    end = scatter(last, i + 1)
    return DirectBuild(perm, jnp.stack([start, end - start], axis=1),
                       kmin, kmax)


def probe_direct(lbatch: ColumnBatch, lkey: int, build: DirectBuild,
                 join_type: str):
    """:func:`probe_fast`'s contract against a :class:`DirectBuild`: the
    stream key's run is read at ``key - kmin``, one gather of table rows
    over the stream rows (4 ms for 2^20 rows whatever the table's size;
    two gathers from two columns cost 18-25 ms; PERF.md, PR 28).  The
    range test comes before the subtraction, so a far key cannot wrap
    into the table."""
    col = lbatch.columns[lkey]
    lvalid = col.validity & lbatch.row_mask()
    wide = _offset_dtype(col.data.dtype)
    in_range = lvalid & (col.data >= build.kmin) & (col.data <= build.kmax)
    idx = jnp.where(in_range,
                    col.data.astype(wide) - build.kmin.astype(wide),
                    0).astype(jnp.int32)
    run = build.table[idx]
    start = run[:, 0]
    cnt = jnp.where(in_range, run[:, 1], 0)
    out_cnt = _out_cnt(cnt, lbatch.row_mask(), join_type)
    total = jnp.sum(out_cnt, dtype=jnp.int64)
    return (start, cnt, build.perm, out_cnt, None), total


def _out_cnt(cnt, real_l, join_type):
    if join_type == "inner":
        return cnt
    if join_type in ("left", "full"):
        return jnp.where(real_l, jnp.maximum(cnt, 1), 0)
    if join_type == "semi":
        return jnp.where(real_l & (cnt > 0), 1, 0).astype(jnp.int32)
    if join_type == "anti":
        return jnp.where(real_l & (cnt == 0), 1, 0).astype(jnp.int32)
    raise ValueError(f"join_type {join_type}")


def matched_build_rows(ri, r_take, cr: int) -> jax.Array:
    """bool[cr]: build rows referenced by matched output slots (streaming
    full-outer bookkeeping, accumulated across stream batches)."""
    slots = jnp.where(r_take, ri, cr)
    return jnp.zeros(cr, jnp.bool_).at[slots].set(True, mode="drop")


def join_probe(lbatch: ColumnBatch, rbatch: ColumnBatch,
               lkeys: Sequence[int], rkeys: Sequence[int],
               join_type: str):
    """Phase 1 (the heavy phase: contains every sort).

    Returns ``(probe_arrays, total)`` where ``probe_arrays`` feeds
    :func:`join_indices_from_probe` and ``total`` is the output row count
    (device scalar).  Splitting probe from gather means the sorts run ONCE
    per join, with only the cheap gather re-specialized per output
    capacity (the reference's two cuDF phases, gather-map + gather,
    GpuHashJoin.scala:300-326, have the same split).
    """
    start, cnt, rsort_perm, out_cnt, unmatched_r = _probe(
        lbatch, rbatch, lkeys, rkeys, join_type)
    total = jnp.sum(out_cnt, dtype=jnp.int64)
    if unmatched_r is not None:
        total = total + jnp.sum(unmatched_r, dtype=jnp.int64)
    return (start, cnt, rsort_perm, out_cnt, unmatched_r), total


def join_total(lbatch: ColumnBatch, rbatch: ColumnBatch,
               lkeys: Sequence[int], rkeys: Sequence[int],
               join_type: str) -> jax.Array:
    """Total output rows (device scalar); prefer :func:`join_probe`."""
    return join_probe(lbatch, rbatch, lkeys, rkeys, join_type)[1]


def join_indices_from_probe(cl: int, probe_arrays, join_type: str,
                            out_cap: int):
    """Phase 2: gather plan into a static ``out_cap`` output from
    precomputed probe arrays (no sorts here).

    Returns (li, ri, l_take, r_take, total):
      li/ri: int32[out_cap] source row per output slot (clamped in range),
      l_take/r_take: bool[out_cap] — False means that side is all-null for
      the slot (outer non-matches) or the slot is padding.
    """
    start, cnt, rsort_perm, out_cnt, unmatched_r = probe_arrays
    offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32),
         jnp.cumsum(out_cnt)[:-1].astype(jnp.int32)])
    total_left = jnp.sum(out_cnt, dtype=jnp.int32)

    j = jnp.arange(out_cap, dtype=jnp.int32)
    in_left = j < total_left
    # left row for slot j: last offset <= j. offsets is non-decreasing.
    li = (jnp.searchsorted(offsets, j, side="right") - 1).astype(jnp.int32)
    li = jnp.clip(li, 0, cl - 1)
    k = j - offsets[li]
    matched = in_left & (k < cnt[li])
    pos = jnp.clip(start[li] + k, 0, rsort_perm.shape[0] - 1)
    ri = rsort_perm[pos]
    l_take = in_left
    r_take = matched
    total = total_left
    if join_type in ("semi", "anti"):
        r_take = jnp.zeros_like(r_take)
    if unmatched_r is not None:  # full outer: append unmatched right rows
        u_off = total_left + jnp.concatenate(
            [jnp.zeros(1, jnp.int32),
             jnp.cumsum(unmatched_r)[:-1].astype(jnp.int32)])
        slots = jnp.where(unmatched_r, u_off, out_cap)
        ridx = jnp.arange(rsort_perm.shape[0], dtype=jnp.int32)
        ri2 = jnp.zeros(out_cap, jnp.int32).at[slots].set(ridx, mode="drop")
        take2 = jnp.zeros(out_cap, jnp.bool_).at[slots].set(
            True, mode="drop")
        ri = jnp.where(take2, ri2, ri)
        r_take = r_take | take2
        total = total + jnp.sum(unmatched_r, dtype=jnp.int32)
    return li, ri, l_take, r_take, total


def gather_join_output(lbatch: ColumnBatch, rbatch: ColumnBatch,
                       li, ri, l_take, r_take, total,
                       schema: T.Schema, include_right: bool) -> ColumnBatch:
    """Build the output batch from a join_indices plan."""
    out_cols: list[DeviceColumn] = []
    for c in lbatch.columns:
        out_cols.append(_take_side(c, li, l_take))
    if include_right:
        for c in rbatch.columns:
            out_cols.append(_take_side(c, ri, r_take))
    return ColumnBatch(out_cols, total.astype(jnp.int32), schema)


def _take_side(c: DeviceColumn, idx, take) -> DeviceColumn:
    validity = c.validity[idx] & take
    if c.is_var_width:
        data = jnp.where(validity[:, None], c.data[idx], 0)
        return DeviceColumn(data, validity, c.dtype,
                            jnp.where(validity, c.lengths[idx], 0))
    data = jnp.where(validity, c.data[idx], jnp.zeros((), c.data.dtype))
    return DeviceColumn(data, validity, c.dtype)
