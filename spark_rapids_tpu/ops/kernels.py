"""Core batch kernels: compaction (filter), gather, concat, slice.

Reference seams: ``Table.filter`` (GpuFilterExec,
basicPhysicalOperators.scala), ``Table.concatenate`` (ConcatAndConsumeAll,
GpuCoalesceBatches.scala:40), batch slicing (limit.scala).

TPU-first: filter does NOT change the array shape.  ``compact`` front-packs
kept rows — a scan of the keep flags, one int32 scatter that turns the
destinations into a source index, then one row gather a dtype over the
leaves stacked side by side, through a bucket an eighth of the capacity
when the kept count fits it — and updates the traced ``num_rows`` scalar:
everything stays inside one compiled program, no host sync on the
data-dependent row count.  Every batch is therefore front-packed with
zeroed padding, and ``concat_batches`` leans on it: it places each input at
the sum of the row counts before it, with no sort and no gather.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch, round_capacity
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.obs.registry import get_registry

__all__ = ["compact", "count_compaction", "take", "concat_batches",
           "slice_batch", "slice_rows", "gather_columns", "gather_stacked",
           "front_rows", "front_stacked", "shrink_capacity",
           "pad_capacity", "device_scalar"]


@__import__("functools").lru_cache(maxsize=65536)
def device_scalar(value, dtype_str: str = "int32") -> jax.Array:
    """Device-resident scalar cached by value.

    A tiny host->device transfer is pure per-call latency, and the same
    small values (partition ids, limits, zero offsets) recur on every
    batch.  The analog
    of the reference pinning small Scalars on the GPU across kernel
    launches (GpuScalar caching, GpuExpressionsUtils.scala)."""
    dtype = jnp.dtype(dtype_str)
    # a miss is one tiny H2D; hits never reach this body
    get_registry().inc_many((("h2d_calls", 1),
                             ("h2d_bytes", dtype.itemsize)))
    return jnp.asarray(value, dtype)


def _gather_column(col: DeviceColumn, perm: jax.Array,
                   out_mask: jax.Array) -> DeviceColumn:
    """Gather rows of ``col`` by ``perm`` then canonicalize padding by
    ``out_mask`` (bool[capacity], True = real row)."""
    validity = col.validity[perm] & out_mask
    if col.is_var_width:
        data = jnp.where(validity[:, None], col.data[perm], 0)
        lengths = jnp.where(validity, col.lengths[perm], 0)
        return DeviceColumn(data, validity, col.dtype, lengths)
    data = jnp.where(validity, col.data[perm], jnp.zeros((), col.data.dtype))
    return DeviceColumn(data, validity, col.dtype)


def gather_columns(cols: Sequence[DeviceColumn], perm: jax.Array,
                   new_count: jax.Array) -> list[DeviceColumn]:
    cap = perm.shape[0]
    out_mask = jnp.arange(cap, dtype=jnp.int32) < new_count
    return [_gather_column(c, perm, out_mask) for c in cols]


def gather_stacked(columns: Sequence[DeviceColumn], idx: jax.Array,
                   take: jax.Array) -> list[DeviceColumn]:
    """:func:`gather_columns` with ONE gather of rows a dtype: the rows
    at ``idx`` where ``take`` (bool, ``idx``'s length), zeros and nulls
    elsewhere.  Leaves of one dtype -- validity flags; each width of
    number; string lengths with the int32 data; byte matrices side by
    side -- are stacked ``[capacity, k]`` first: a gather costs the chip
    10-17 ns an index whatever it reads, so fewer, wider gathers win
    (PERF.md, PRs 28, 32, 33 and 37)."""
    leaves, tree = jax.tree.flatten(list(columns))
    stacks: dict = {}
    for x in leaves:
        stacks.setdefault(x.dtype, []).append(x.reshape(x.shape[0], -1))
    moved = {}
    for dtype, xs in stacks.items():
        rows = jnp.concatenate(xs, axis=1)[idx]
        bounds = np.cumsum([x.shape[1] for x in xs])[:-1]
        moved[dtype] = iter(jnp.split(rows, bounds, axis=1))
    return [_keep_rows(c, take) for c in jax.tree.unflatten(tree, [
        next(moved[x.dtype]).reshape(idx.shape + x.shape[1:])
        for x in leaves])]


def _keep_rows(c: DeviceColumn, take: jax.Array) -> DeviceColumn:
    """``c`` where ``take`` and valid, zeros and nulls elsewhere."""
    validity = c.validity & take
    data = jnp.where(validity[(...,) + (None,) * (c.data.ndim - 1)],
                     c.data, jnp.zeros((), c.data.dtype))
    return DeviceColumn(
        data, validity, c.dtype,
        None if c.lengths is None else jnp.where(validity, c.lengths, 0))


def front_rows(x: jax.Array, slots: int) -> jax.Array:
    """The first ``slots`` rows of ``x``: a static slice, or zeros after
    its last row where it has fewer."""
    n = x.shape[0]
    if n >= slots:
        return x if n == slots else x[:slots]
    return jnp.pad(x, ((0, slots - n),) + ((0, 0),) * (x.ndim - 1))


def front_stacked(columns: Sequence[DeviceColumn],
                  take: jax.Array) -> list[DeviceColumn]:
    """What :func:`gather_stacked` gives for ``idx = arange(len(take))``,
    with no gather: the columns' first ``len(take)`` slots where ``take``,
    zeros and nulls elsewhere (static slices and selects: a launch's
    floor where a gather costs 10-17 ns an index)."""
    slots = take.shape[0]
    return [_keep_rows(DeviceColumn(
        front_rows(c.data, slots), front_rows(c.validity, slots), c.dtype,
        None if c.lengths is None else front_rows(c.lengths, slots)), take)
        for c in columns]


# A compaction moves ``capacity // SMALL_BUCKET_DIVISOR`` slots instead
# of ``capacity`` when the kept count fits them.  Below
# ``COND_MIN_CAPACITY`` slots either move costs what the launch costs
# (1.4-1.6 ms at 2^13 x 4 columns on the chip, PERF.md PR 32) and only
# the full one is traced: half the program to compile.
SMALL_BUCKET_DIVISOR = 8
COND_MIN_CAPACITY = 1 << 14


def compact(batch: ColumnBatch, keep: jax.Array) -> ColumnBatch:
    """Filter: keep rows where ``keep`` (bool[capacity]) is True.

    Order-preserving front-pack, rows moved once: an inclusive scan of
    the keep flags gives every kept row its destination, one int32
    scatter inverts that into ``src`` (destination -> source row), and
    one row gather a dtype moves all leaves of that dtype together
    (``_move_rows``).  The size of the move follows the count the
    program can see: under ``lax.cond`` a batch that keeps at most
    ``capacity // SMALL_BUCKET_DIVISOR`` rows builds and gathers that
    many slots and pads the rest with zeros, any other batch moves
    ``capacity`` slots.  Output shapes are the input's either way.
    Padding and rows beyond ``num_rows`` are always dropped; everything
    at and beyond the new count is zeroed, validity canonical.
    """
    keep = keep & batch.row_mask()
    cap = batch.capacity
    dest = jnp.cumsum(keep.astype(jnp.int32)) - 1
    new_count = jnp.sum(keep, dtype=jnp.int32)
    if cap < COND_MIN_CAPACITY:
        cols = _move_rows(batch.columns, keep, dest, new_count, cap)
    else:
        small = cap // SMALL_BUCKET_DIVISOR
        cols = jax.lax.cond(
            new_count <= small,
            partial(_move_rows, slots=small),
            partial(_move_rows, slots=cap),
            batch.columns, keep, dest, new_count)
    return ColumnBatch(cols, new_count, batch.schema)


def count_compaction(capacity: int) -> None:
    """Account one dispatch of a program whose body compacts a
    ``capacity``-slot batch (counters ``compact.launches`` /
    ``compact.slots``).  Which branch of the ``cond`` ran is decided on
    the device and not worth a fetch: the seconds a launch show it."""
    get_registry().inc_many((("compact.launches", 1),
                             ("compact.slots", capacity)))


def _move_rows(columns: Sequence[DeviceColumn], keep: jax.Array,
               dest: jax.Array, new_count: jax.Array,
               slots: int) -> list[DeviceColumn]:
    """``compact``'s move through a ``slots``-row bucket (``new_count <=
    slots <= capacity``): columns of the input's capacity holding the
    kept rows at ``dest``, zeros from ``new_count`` on.

    Every dropped row scatters to an index of its own past the bucket's
    end: unique indices are what lets XLA scatter without sorting
    (``ops/join.build_direct_table``; a bucket of 2^22 slots or more no
    longer fits the chip's fast memory and is scattered behind one
    two-operand sort all the same); the rows then move by
    :func:`gather_stacked`."""
    cap = keep.shape[0]
    row = jnp.arange(cap, dtype=jnp.int32)
    src = jnp.zeros(slots, jnp.int32).at[
        jnp.where(keep, dest, slots + row)].set(
            row, unique_indices=True, mode="drop")
    live = jnp.arange(slots, dtype=jnp.int32) < new_count

    def pad(x):
        return x if x is None or slots == cap else jnp.pad(
            x, ((0, cap - slots),) + ((0, 0),) * (x.ndim - 1))

    return [DeviceColumn(pad(c.data), pad(c.validity), c.dtype,
                         pad(c.lengths))
            for c in gather_stacked(columns, src, live)]


def take(batch: ColumnBatch, indices: jax.Array,
         out_count: jax.Array) -> ColumnBatch:
    """Gather rows at ``indices`` (int32[out_capacity]); entries at position
    >= out_count are padding."""
    cols = gather_columns(batch.columns, indices, out_count)
    return ColumnBatch(cols, out_count, batch.schema)


def slice_batch(batch: ColumnBatch, limit: jax.Array) -> ColumnBatch:
    """Keep the first ``limit`` rows (GpuLocalLimit, limit.scala)."""
    if isinstance(limit, int):
        limit = device_scalar(limit)  # cached: no per-call H2D round trip
    new_count = jnp.minimum(batch.num_rows, jnp.asarray(limit, jnp.int32))
    identity = jnp.arange(batch.capacity, dtype=jnp.int32)
    cols = gather_columns(batch.columns, identity, new_count)
    return ColumnBatch(cols, new_count, batch.schema)


def slice_rows(batch: ColumnBatch, lo: int, hi: int) -> ColumnBatch:
    """Row range ``[lo, hi)`` of a front-packed batch as its own batch.

    The caller must know (host-side) that ``hi <= num_rows``, so every
    row in the range is real.  Slices run eagerly: each (lo, hi, shape)
    triple is unique to its split point, so a jit here would compile a
    fresh executable per slice (the opposite of the canonical-bucket
    discipline the jitted shrink/pad kernels exist for)."""
    cols = []
    for c in batch.columns:
        if c.is_var_width:
            cols.append(DeviceColumn(c.data[lo:hi], c.validity[lo:hi],
                                     c.dtype, c.lengths[lo:hi]))
        else:
            cols.append(DeviceColumn(c.data[lo:hi], c.validity[lo:hi],
                                     c.dtype))
    n = hi - lo
    return ColumnBatch(cols, jnp.asarray(n, jnp.int32), batch.schema,
                       known_rows=n)


def shrink_capacity(batch: ColumnBatch, cap: int) -> ColumnBatch:
    """Static-slice a front-packed batch down to ``cap`` rows of storage.

    The caller must know (host-side) that ``num_rows <= cap``; rows are
    already front-packed so a plain prefix slice keeps them all.  Used to
    hold a running aggregation buffer at a fixed canonical capacity
    instead of walking compilation buckets upward.  Jitted per (cap,
    batch-shape) so the eager path costs one dispatch, not one per column.
    """
    if batch.capacity <= cap:
        return batch
    return _shared("batch_shrink", _shrink_jit)(batch, cap)


_SHARED_JITS: dict = {}


def _shared(name: str, fn):
    """Compile-accounted wrapper for a capacity-changing kernel.

    These kernels compile NEW executables mid-query (every distinct
    capacity is a fresh signature, and spill/retry storms churn
    capacities across drain threads), so they go through the shared-jit
    wrapper, which serializes CPU compiles process-wide.  kernels sits
    below exec/, hence the wrapper is bound lazily on first dispatch
    instead of imported at module load."""
    w = _SHARED_JITS.get(name)
    if w is None:
        from spark_rapids_tpu.exec.compile_cache import instrument
        w = _SHARED_JITS.setdefault(name, instrument(fn, name))
    return w


@partial(jax.jit, static_argnames=("cap",))
def _shrink_jit(batch: ColumnBatch, cap: int) -> ColumnBatch:
    cols = []
    for c in batch.columns:
        if c.is_var_width:
            cols.append(DeviceColumn(c.data[:cap], c.validity[:cap],
                                     c.dtype, c.lengths[:cap]))
        else:
            cols.append(DeviceColumn(c.data[:cap], c.validity[:cap], c.dtype))
    return ColumnBatch(cols, batch.num_rows, batch.schema)


def halve_capacity(batch: ColumnBatch) -> tuple[ColumnBatch, ColumnBatch]:
    """The lower and the upper half of a front-packed batch's slots,
    each a batch at half the capacity: static slices, the rows counted
    on the device (the upper half holds what lies past the middle, maybe
    nothing).  ``memory.retry.split_half`` cuts at the middle ROW, which
    it has to fetch and then moves rows from by a dynamic start — 2 s
    for a 2^24-slot batch of two columns on the chip, a gather's price,
    where this is a copy (PERF.md Findings PR 42)."""
    run = _SHARED_JITS.get("batch_halves") \
        or _shared("batch_halves", jax.jit(_halves))
    lo, hi = run(batch)
    if batch.known_rows is not None:
        half = batch.capacity // 2
        lo.known_rows = min(batch.known_rows, half)
        hi.known_rows = max(batch.known_rows - half, 0)
    return lo, hi


def _halves(batch: ColumnBatch):
    half = batch.capacity // 2

    def part(at: int, rows) -> ColumnBatch:
        cols = [DeviceColumn(c.data[at:at + half], c.validity[at:at + half],
                             c.dtype,
                             c.lengths[at:at + half] if c.is_var_width
                             else None)
                for c in batch.columns]
        return ColumnBatch(cols, rows, batch.schema)
    n = batch.num_rows
    return (part(0, jnp.minimum(n, half)),
            part(half, jnp.maximum(n - half, 0)))


def pad_capacity(batch: ColumnBatch, cap: int) -> ColumnBatch:
    """Grow a batch's storage to ``cap`` rows with trailing padding
    (cheap realloc; keeps compilation buckets canonical)."""
    if cap <= batch.capacity:
        return batch
    return _shared("batch_pad", _pad_jit)(batch, cap)


@partial(jax.jit, static_argnames=("cap",))
def _pad_jit(batch: ColumnBatch, cap: int) -> ColumnBatch:
    pad = cap - batch.capacity
    cols = []
    for c in batch.columns:
        validity = jnp.concatenate([c.validity, jnp.zeros(pad, jnp.bool_)])
        if c.is_var_width:
            data = jnp.concatenate(
                [c.data, jnp.zeros((pad, c.max_len), c.data.dtype)])
            lengths = jnp.concatenate([c.lengths, jnp.zeros(pad, jnp.int32)])
            cols.append(DeviceColumn(data, validity, c.dtype, lengths))
        else:
            data = jnp.concatenate([c.data, jnp.zeros(pad, c.data.dtype)])
            cols.append(DeviceColumn(data, validity, c.dtype))
    return ColumnBatch(cols, batch.num_rows, batch.schema)


def concat_batches(batches: Sequence[ColumnBatch],
                   out_capacity: int | None = None) -> ColumnBatch:
    """Concatenate batches (GpuCoalesceBatches / Table.concatenate).

    Shapes are static: the output capacity is the pow2 bucket of the summed
    input capacities unless given.  Inputs are front-packed, so batch i
    is placed at the sum of the row counts before it (``_place_batches``);
    rows keep list order, then batch order.  Called outside a trace it is
    one launch of the ``concat_batches`` program (signature: schema +
    the tuple of input capacities); inside one it is the plain body.
    """
    assert batches, "concat of zero batches"
    total = sum(b.capacity for b in batches)
    cap = out_capacity or round_capacity(total)
    if cap < total:
        # dynamic_update_slice clamps a start that would overrun: with
        # cap >= total, offset i + capacity i <= total and nothing clamps
        raise ValueError("out_capacity smaller than concatenated capacities")
    batches = tuple(batches)
    if any(isinstance(b.num_rows, jax.core.Tracer) for b in batches):
        return _place_batches(batches, cap)
    # align devices: inputs committed to different mesh devices (e.g. a
    # mesh join's per-device probe outputs consumed by a non-mesh
    # operator) cannot feed one jitted concat; move strays to the first
    # batch's device (no-op when aligned)
    if batches[0].columns:
        devs = {repr(d) for b in batches if b.columns
                for d in [next(iter(b.columns[0].data.devices()))]
                if getattr(b.columns[0].data, "committed", False)}
        if len(devs) > 1:
            target = next(iter(batches[0].columns[0].data.devices()))
            batches = tuple(jax.device_put(b, target) for b in batches)
    get_registry().inc_many((("concat.launches", 1),
                             ("concat.batches_in", len(batches))))
    out = _shared("concat_batches", _concat_jit)(batches, cap)
    if all(b.known_rows is not None for b in batches):
        # the jit boundary strips the host-side count; the sum is free
        out.known_rows = sum(b.known_rows for b in batches)
    return out


def _place_batches(batches: tuple, cap: int) -> ColumnBatch:
    """Write each input at its row offset into zeroed ``cap``-row
    outputs; a later input overwrites the padding of the one before it.
    One elementwise pass then canonicalizes (validity under the output's
    row mask, data and lengths zeroed where invalid), so an input whose
    padding was not zeroed still gives a canonical batch."""
    zero = jnp.zeros((), jnp.int32)  # x64 is on: a python 0 is int64
    offsets = [zero]
    for b in batches:
        offsets.append(offsets[-1] + jnp.asarray(b.num_rows, jnp.int32))
    new_count = offsets.pop()
    out_mask = jnp.arange(cap, dtype=jnp.int32) < new_count

    def place(leaves):
        # strings and arrays: trailing width of the widest input
        tail = tuple(max(d) for d in zip(*(x.shape[1:] for x in leaves)))
        out = jnp.zeros((cap,) + tail, leaves[0].dtype)
        for x, off in zip(leaves, offsets):
            if x.shape[1:] != tail:
                x = jnp.pad(x, ((0, 0),) + tuple(
                    (0, t - w) for w, t in zip(x.shape[1:], tail)))
            out = jax.lax.dynamic_update_slice(
                out, x, (off,) + (zero,) * len(tail))
        return out

    cols = []
    for parts in zip(*(b.columns for b in batches)):
        validity = place([p.validity for p in parts]) & out_mask
        data = place([p.data for p in parts])
        data = jnp.where(validity[(...,) + (None,) * (data.ndim - 1)],
                         data, jnp.zeros((), data.dtype))
        lengths = None
        if parts[0].is_var_width:
            lengths = jnp.where(
                validity, place([p.lengths for p in parts]), 0)
        cols.append(DeviceColumn(data, validity, parts[0].dtype, lengths))
    return ColumnBatch(cols, new_count, batches[0].schema)


@partial(jax.jit, static_argnames=("cap",))
def _concat_jit(batches: tuple, cap: int) -> ColumnBatch:
    return _place_batches(batches, cap)
