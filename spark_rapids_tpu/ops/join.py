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
4. **gather** (phase 2): output slot j -> (left row, right row) by a
   cumsum of the rows' output counts, one scatter of their offsets and a
   running maximum (the **expanding** plan, the one-chip executor's and
   a mesh region's alike); full-outer appends unmatched right rows by
   scatter.  Gathers build the output columns, one gather a dtype over
   stacked leaves.  A stream batch whose every live row comes out exactly
   once takes the **aligned** plan instead (:func:`probe_counts` says so
   beside the total): its own columns stay in their slots, only the
   build's move.

Right outer join is the exec layer's job (swap sides, reorder columns,
exec/joins.py), matching the reference's build-side flip.

A join on ONE integral key streams: the build side is sorted once
(:func:`build_prepare_fast`) and each stream batch is probed against it
(never ranked together with it, as the sort path does).  How the probe
finds a key's run in the sorted build is chosen once per build, on the
host, from the key range the build holds (:func:`direct_table_size`):

* **dense keys** (surrogate keys, day numbers: the range is no wider
  than a table the engine can always afford): :func:`build_direct_table`
  makes ``table[k - kmin] = (run start, run length)`` once, and
  :func:`probe_direct` reads it by address: one gather of table rows a
  stream batch, whatever the build's size;
* **anything else** (a hashed id, a natural key): :func:`probe_fast`
  finds each stream key's run in the sorted keys, by a **merge** where
  the shapes say that is cheaper (:func:`probe_merges`: the batch's keys
  are sorted in among the build's, two scans give every run's start and
  end, one sort puts them back in stream order: scans and sorts only,
  no gather), and by **steps** where a small stream batch meets a far
  larger build (one ``searchsorted``, ``log2(capacity)`` dependent
  gathers a stream row, and the run length the build keeps at each
  run's first row).

All return the same ``(start, cnt, perm, out_cnt)`` and ``total``.

A join on SEVERAL integral keys streams the same way: the keys are packed
into ONE mixed-radix ``int64`` key, ``sum((k_i - lo_i) * radix_i)`` with
``radix_i`` the product of the later keys' spans ``hi_j - lo_j + 1``, and
everything after that is the single-key path (a dense product is probed
by address, anything else searched).  The ranges ``[lo_i, hi_i]`` are
the build side's own, found on the device while the build is sorted
(:func:`build_prepare_packed`) and carried to every probe as arrays
(:class:`KeyPacking`: traced, so another build compiles nothing); the
build's one fetch brings them to the host too, where python ints decide
whether the spans' product fits 63 bits (:func:`packed_key_span`).  A
stream row with a NULL in any key, or with any key outside the build's
range for it, matches nothing: the range test comes before the
subtraction, so no such row wraps into a match.  Strings, fractional
and boolean keys, and a product that does not fit, stay on the sort
path (:func:`join_probe`).
"""
from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch, round_capacity
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.ops.kernels import (front_rows, front_stacked,
                                          gather_stacked)
from spark_rapids_tpu.ops.segmented import _cols_differ
from spark_rapids_tpu.ops.sort import encode_key_operands

__all__ = ["join_probe", "join_total", "join_indices_from_probe",
           "gather_join_output", "JOIN_TYPES", "DirectBuild", "KeyPacking",
           "PackedBuild", "direct_table_size", "packed_key_span",
           "probe_merges", "probe_counts"]

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

    Returns ``(sorted_key, perm, nv, run_len)``: the build keys sorted
    ascending with the ``nv`` valid entries first and every
    invalid/padding slot rewritten to the dtype max so the array stays
    globally sorted (a probe's hit must lie below ``nv``, which keeps
    genuine max-valued keys — they live at positions < nv), and at the
    first row of every run of equal keys the run's length, so that ONE
    search finds a key's whole run (:func:`probe_fast`'s stepping
    branch; its merge counts the run itself).  This is the streaming-join
    analog of the reference's build-side hash table (GpuHashJoin build
    side, GpuHashJoin.scala:193-249): built once, probed per stream
    batch.
    """
    col = rbatch.columns[rkey]
    return _sort_build(col.data, col.validity & rbatch.row_mask())


def _sort_build(key, valid):
    cap = key.shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)
    flag = (~valid).astype(jnp.uint8)
    _, skey, perm = lax.sort([flag, key, iota], num_keys=2, is_stable=True)
    nv = jnp.sum(valid, dtype=jnp.int32)
    skey = jnp.where(iota < nv, skey, jnp.iinfo(key.dtype).max)
    # the length of each run of equal keys, at the run's first row (0 at
    # the others): the next run's first row, found by a running minimum
    # from the end, less this one
    first = (iota < nv) & ((iota == 0) | (skey != jnp.roll(skey, 1)))
    nxt = lax.cummin(jnp.where(first, iota, cap), reverse=True)
    after = jnp.concatenate([nxt[1:], jnp.full(1, cap, jnp.int32)])
    run_len = jnp.where(first, jnp.minimum(after, nv) - iota, 0)
    return skey, perm, nv, run_len


class KeyPacking(NamedTuple):
    """How ``k`` integral keys become one: the build side's range of each
    key and its place value, ``int64[k]`` device arrays (arguments of the
    probe programs, never static)."""
    lo: jax.Array
    hi: jax.Array
    radix: jax.Array


class PackedBuild(NamedTuple):
    """A build side prepared from several keys: what a single-key probe
    takes (a :class:`DirectBuild` or ``(sorted_key, perm, nv, run_len)``,
    over the packed key) and the packing the stream side has to repeat."""
    build: tuple
    packing: KeyPacking


def _pack_keys(batch: ColumnBatch, keys: Sequence[int], packing: KeyPacking):
    """``(packed int64[capacity], valid)``: rows that are padding, hold a
    NULL key or a key outside ``[lo_i, hi_i]`` are not valid (their
    packed value means nothing).  The range test comes first: only an
    in-range key is subtracted, so nothing wraps."""
    valid = batch.row_mask()
    packed = jnp.zeros(batch.capacity, jnp.int64)
    for i, k in enumerate(keys):
        col = batch.columns[k]
        data = col.data.astype(jnp.int64)
        ok = col.validity & (data >= packing.lo[i]) & (data <= packing.hi[i])
        valid = valid & ok
        packed = packed + jnp.where(ok, data - packing.lo[i], 0) \
            * packing.radix[i]
    return packed, valid


def build_prepare_packed(rbatch: ColumnBatch, rkeys: Sequence[int]):
    """:func:`build_prepare_fast` for several integral keys: the range of
    each key over the rows whose keys are all valid, the mixed-radix
    packing they give (last key fastest), and the build sorted by the
    packed key.  Returns ``((sorted_key, perm, nv, run_len), packing)``.
    Where the spans' product does not fit 63 bits the radices have
    wrapped and the result is to be dropped: the host sees that from the
    ranges (:func:`packed_key_span`)."""
    valid = rbatch.row_mask()
    for k in rkeys:
        valid = valid & rbatch.columns[k].validity
    wide = [rbatch.columns[k].data.astype(jnp.int64) for k in rkeys]
    i64 = jnp.iinfo(jnp.int64)
    lo = jnp.stack([jnp.min(jnp.where(valid, d, i64.max)) for d in wide])
    hi = jnp.stack([jnp.max(jnp.where(valid, d, i64.min)) for d in wide])
    span = hi - lo + 1
    radix = jnp.concatenate(
        [jnp.cumprod(span[:0:-1])[::-1], jnp.ones(1, jnp.int64)])
    packing = KeyPacking(lo, hi, radix)
    packed, _ = _pack_keys(rbatch, rkeys, packing)
    return _sort_build(packed, valid), packing


def packed_key_span(nv: int, ranges: Sequence[int]) -> int | None:
    """Values a packed key can take, from the ``[lo_0, hi_0, lo_1, ...]``
    of a build's fetch (python ints: nothing overflows), or None where
    that does not fit an ``int64`` and the keys cannot be packed.  An
    empty build packs: nothing can match it whatever its ranges say."""
    if nv == 0:
        return 1
    product = 1
    for lo, hi in zip(ranges[0::2], ranges[1::2]):
        product *= hi - lo + 1
    return product if product < (1 << 63) else None


def _stream_key(lbatch: ColumnBatch, lkey, packing: KeyPacking | None):
    """The stream side's key and which rows may match: column ``lkey``,
    or the columns ``lkey`` packed as the build was."""
    if packing is not None:
        return _pack_keys(lbatch, lkey, packing)
    col = lbatch.columns[lkey]
    return col.data, col.validity & lbatch.row_mask()


#: :func:`probe_fast` merges while the build has at most this many slots a
#: stream-batch slot.  The merge costs 6-7 ns a SORTED row (``cl + cr`` of
#: them), the steps 0.34-0.55 us a STREAM row against a 2^22-entry build:
#: they break even near 57-78 build slots a stream slot, and at 32 the
#: merge still wins twice over for ``int32`` and ``int64`` keys alike
#: (PERF.md, PR 35: both branches on the chip at five shapes).
MERGE_MAX_BUILD_RATIO = 32


def probe_merges(cl: int, cr: int) -> bool:
    """The one rule that picks :func:`probe_fast`'s branch, from the
    capacities of the stream batch and of the build alone (static, so the
    choice is made while tracing): merge, unless the build is so much
    larger than the batch that sorting it again costs more than stepping
    through it.  The executor counts by the same rule
    (``join.probe.search.merged``)."""
    return cr <= MERGE_MAX_BUILD_RATIO * cl


def _runs_by_merge(sorted_key, nv, data):
    """``(start, cnt)`` of each stream key's run in the sorted build, by a
    merge: sorts and scans, no gather.

    Build keys then stream keys are sorted together by key, stably, so
    within a run of equal keys the build's rows come first, in their
    order (a tie broken by a second sort key instead gave the same
    arrays 10 % slower, PERF.md PR 35).  The running count of build rows is then, at a stream row, the
    number of build keys <= its key (its run's end); that count *before*
    the first element of a run of equal keys is the number of build keys
    below them (the run's start), carried along the run by a running
    maximum (it never falls).  Both are clamped to ``nv``: the build's
    padding slots (rewritten to the dtype's maximum, after every valid
    row) never count, and a genuine maximum-valued key below ``nv`` still
    matches.  One sort by position puts the answers back in stream order
    (not a scatter: into this many slots the TPU compiler sorts before
    it anyway)."""
    cr = sorted_key.shape[0]
    keys = jnp.concatenate([sorted_key, data])
    iota = jnp.arange(keys.shape[0], dtype=jnp.int32)
    skey, src = lax.sort([keys, iota], num_keys=1, is_stable=True)
    from_build = src < cr
    seen = jnp.cumsum(from_build, dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones(1, jnp.bool_), skey[1:] != skey[:-1]])
    below = lax.cummax(jnp.where(first, seen - from_build, 0))
    _, start, end = lax.sort(
        [src, jnp.minimum(below, nv), jnp.minimum(seen, nv)], num_keys=1)
    return start[cr:], end[cr:] - start[cr:]


def _runs_by_steps(sorted_key, nv, run_len, data):
    """:func:`_runs_by_merge`'s answer by ONE ``searchsorted``
    (``log2(capacity)`` dependent gathers a stream row, 64-bit words
    emulated: 586 ms for 2^20 rows in a 2^22-entry ``int64`` build,
    whatever the build's size nearly; a second search for the run's end
    cost as much again, PERF.md PR 33), then the key and the run length
    read where it landed."""
    start = jnp.searchsorted(sorted_key, data, side="left").astype(jnp.int32)
    start = jnp.minimum(start, sorted_key.shape[0] - 1)
    hit = (start < nv) & (sorted_key[start] == data)
    return start, jnp.where(hit, run_len[start], 0)


def probe_fast(lbatch: ColumnBatch, lkey, sorted_key, perm, nv, run_len,
               join_type: str, packing: KeyPacking | None = None):
    """Per-stream-batch probe against a prepared build side: each stream
    key's run in the sorted build, found by a merge or by steps as the
    two capacities say (:func:`probe_merges`; the same ``start`` and
    ``cnt`` either way).  Same contract as the heavy phase of
    :func:`join_probe` (without full-outer bookkeeping — streaming full
    outer tracks matched build rows in the gather phase instead);
    ``start`` means nothing where ``cnt`` is 0.  With a ``packing``,
    ``lkey`` names the key columns to pack first."""
    data, lvalid = _stream_key(lbatch, lkey, packing)
    if probe_merges(data.shape[0], sorted_key.shape[0]):
        start, cnt = _runs_by_merge(sorted_key, nv, data)
    else:
        start, cnt = _runs_by_steps(sorted_key, nv, run_len, data)
    cnt = jnp.where(lvalid, cnt, 0)
    out_cnt = _out_cnt(cnt, lbatch.row_mask(), join_type)
    total = jnp.sum(out_cnt, dtype=jnp.int64)
    return (start, cnt, perm, out_cnt, None), total


def _key_range(sorted_key, nv):
    """Smallest and largest valid key of a prepared build side (with
    ``nv == 0`` both are the padding value and mean nothing)."""
    return sorted_key[0], sorted_key[jnp.maximum(nv - 1, 0)]


def build_key_stats(sorted_key, nv, packing: KeyPacking | None = None):
    """int64[1 + 2k] ``[nv, lo_0, hi_0, ...]`` of a prepared build side:
    the valid rows and each key's smallest and largest value, what the
    host needs to choose the probe (:func:`packed_key_span`,
    :func:`direct_table_size`), in one array so it is one fetch."""
    if packing is None:
        kmin, kmax = _key_range(sorted_key, nv)
        return jnp.stack([nv, kmin, kmax]).astype(jnp.int64)
    ranges = jnp.stack([packing.lo, packing.hi], axis=1).reshape(-1)
    return jnp.concatenate([nv.astype(jnp.int64)[None], ranges])


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
    """Direct-address table over a prepared build side (the first three
    outputs of :func:`build_prepare_fast`) whose key range fits ``size``
    entries.

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


def probe_direct(lbatch: ColumnBatch, lkey, build: DirectBuild,
                 join_type: str, packing: KeyPacking | None = None):
    """:func:`probe_fast`'s contract against a :class:`DirectBuild`: the
    stream key's run is read at ``key - kmin``, one gather of table rows
    over the stream rows (4 ms for 2^20 rows whatever the table's size;
    two gathers from two columns cost 18-25 ms; PERF.md, PR 28).  The
    range test comes before the subtraction, so a far key cannot wrap
    into the table.  ``packing`` as in :func:`probe_fast`."""
    data, lvalid = _stream_key(lbatch, lkey, packing)
    wide = _offset_dtype(data.dtype)
    in_range = lvalid & (data >= build.kmin) & (data <= build.kmax)
    idx = jnp.where(in_range,
                    data.astype(wide) - build.kmin.astype(wide),
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


def probe_counts(out_cnt, real_l, total) -> jax.Array:
    """int64[2] ``[total, aligned]``: what the host fetches of a stream
    batch's probe, in one array so it is one transfer.  ``aligned`` is 1
    where every live stream row (``real_l``) comes out exactly once and
    no other row does (for a left join: no key matched twice; for an inner
    join also none went unmatched; a cross join: the build holds one row):
    the batch's output is then its own rows in their own slots, and
    :func:`join_indices_from_probe` need not place them."""
    aligned = jnp.all(out_cnt == real_l.astype(out_cnt.dtype))
    return jnp.stack([total.astype(jnp.int64), aligned.astype(jnp.int64)])


def join_indices_from_probe(cl: int, probe_arrays, join_type: str,
                            out_cap: int, aligned: bool = False):
    """Phase 2: gather plan into a static ``out_cap`` output from
    precomputed probe arrays (no sorts here).

    Returns (li, ri, l_take, r_take, total):
      li/ri: int32[out_cap] source row per output slot (clamped in range),
      l_take/r_take: bool[out_cap] — False means that side is all-null for
      the slot (outer non-matches) or the slot is padding.

    Two plans, the same arrays out of each:

    * **expanding** (the one-chip executor's since PR 33, a mesh region's
      join body's since PR 44): the left row of each slot by one scatter
      and a running maximum, per-row numbers and column leaves moved in
      stacked gathers: 99 ms at 2^20 slots x 9 columns on the chip
      (PERF.md PR 43; the plan it replaced searched the offsets for each
      slot and gathered a leaf at a time: 501.7 ms, PR 33).  Any batch
      may take it: a stream row matched twice, an inner join that drops
      rows.
    * **aligned** (``aligned=True``, since PR 43): only for a batch whose
      probe said every live stream row comes out exactly once
      (:func:`probe_counts`; the executor picks it batch by batch from
      that fetched flag).  **Precondition, not checked here:** the caller
      has fetched :func:`probe_counts`' flag for these very arrays and it
      read 1, and the stream's live rows are front-packed
      (``row_mask`` = ``arange < num_rows``, as every ColumnBatch's are);
      on any other batch the result is silently wrong rows.  Slot ``j``
      is then stream row ``j``: ``li`` is
      None (:func:`gather_join_output` takes the stream's first
      ``out_cap`` slots as they lie), no scatter, no running maximum, no
      gather of per-row numbers; ``ri = perm[start]`` is the one index
      pass left here (9.9 ms at 2^20 slots on the chip; the whole aligned
      body 34 ms where the expanding one takes 99: the build's stacks are
      25.7 of them, its one 64-bit leaf 17.3; PERF.md section 6 PR 43).
    """
    start, cnt, rsort_perm, out_cnt, unmatched_r = probe_arrays
    if aligned:
        assert unmatched_r is None, "the full-outer tail expands"
        total = jnp.sum(out_cnt, dtype=jnp.int32)
        l_take = jnp.arange(out_cap, dtype=jnp.int32) < total
        first, n = front_rows(start, out_cap), front_rows(cnt, out_cap)
        ri = rsort_perm[jnp.clip(first, 0, rsort_perm.shape[0] - 1)]
        r_take = l_take & (n > 0)
        if join_type in ("semi", "anti"):
            r_take = jnp.zeros_like(r_take)
        return None, ri, l_take, r_take, total
    offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32),
         jnp.cumsum(out_cnt)[:-1].astype(jnp.int32)])
    total_left = jnp.sum(out_cnt, dtype=jnp.int32)

    j = jnp.arange(out_cap, dtype=jnp.int32)
    in_left = j < total_left
    # left row for slot j: the last row with output whose offset is
    # <= j.  Those offsets rise strictly, so each such row writes its
    # index at its offset (unique indices: no sort; the others go
    # past the end, each to an index of its own) and a running
    # maximum fills the slots between.  A search of the offsets is
    # log2(cl) dependent gathers a slot.
    row = jnp.arange(cl, dtype=jnp.int32)
    at = jnp.where((out_cnt > 0) & (offsets < out_cap), offsets,
                   out_cap + row)
    li = lax.cummax(jnp.zeros(out_cap, jnp.int32).at[at].set(
        row, unique_indices=True, mode="drop"))
    # one gather of rows for the three per-row numbers a slot needs
    off, n, first = jnp.stack([offsets, cnt, start], axis=1)[li].T
    k = j - off
    matched = in_left & (k < n)
    pos = jnp.clip(first + k, 0, rsort_perm.shape[0] - 1)
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
    """Build the output batch from a join_indices plan: each side's
    leaves move in one gather of rows a dtype
    (``ops/kernels.gather_stacked``).  With ``li`` None (the aligned plan
    of :func:`join_indices_from_probe`) the stream's columns do not move
    at all: their first ``out_cap`` slots, sliced and masked
    (``ops/kernels.front_stacked``), and only the build's columns are
    gathered."""
    out_cols = front_stacked(lbatch.columns, l_take) if li is None \
        else gather_stacked(lbatch.columns, li, l_take)
    if include_right:
        out_cols += gather_stacked(rbatch.columns, ri, r_take)
    return ColumnBatch(out_cols, total.astype(jnp.int32), schema)
