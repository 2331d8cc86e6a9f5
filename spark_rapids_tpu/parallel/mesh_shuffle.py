"""All-to-all hash exchange over a device mesh.

TPU-native shuffle data plane (SURVEY.md §5.8).  The reference moves map
output peer-to-peer over UCX tag matching (shuffle-plugin/.../UCX.scala,
RapidsShuffleClient.scala, RapidsShuffleServer.scala); here the exchange
is one XLA `all_to_all` collective inside `shard_map`, so it rides ICI
within a slice and DCN across slices with zero host involvement, and it
fuses with the surrounding kernels in one compiled program.

Design: every device holds a fixed-capacity shard.  A shuffle step is
  1. partition ids per row: Spark-bit-exact murmur3 pmod P
     (reference GpuHashPartitioning.scala),
  2. bucketize: one stable sort by partition id, then scatter into a
     [P, C] send buffer per column (reference Table.contiguousSplit,
     GpuPartitioning.scala:45-52),
  3. `lax.all_to_all` on the [P, C] buffers (+ per-target row counts),
  4. repack the received [P, C] buffers into one [P*C]-capacity batch
     (front-pack permutation — reference concatenates received shuffle
     buffers, RapidsShuffleClient BufferReceiveState).

All shapes are static; row validity travels as counts, so the whole
exchange jits and the compiler overlaps the collective with compute.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.expr.core import EvalCtx, Val
from spark_rapids_tpu.expr.hashing import murmur3_val, DEFAULT_SEED
from spark_rapids_tpu.ops import kernels as dk

__all__ = [
    "partition_ids_for_keys",
    "exchange_local", "exchange_local_checked", "canonicalize",
]


def partition_ids_for_keys(batch: ColumnBatch, key_indices: Sequence[int],
                           num_parts: int) -> jax.Array:
    """int32[capacity]: pmod(murmur3(keys), P) per real row; P for padding.

    Bit-exact with Spark's HashPartitioning(Murmur3Hash) so host- and
    device-partitioned data interleave (reference GpuHashPartitioning).
    """
    cap = batch.capacity
    mask = batch.row_mask()
    ctx = EvalCtx(jnp, True, cap, mask)
    seed = jnp.full(cap, DEFAULT_SEED, dtype=jnp.uint32)
    for ki in key_indices:
        c = batch.columns[ki]
        seed = murmur3_val(Val(c.data, c.validity, c.lengths, c.dtype),
                           seed, ctx)
    h = seed.astype(jnp.int32)
    pid = ((h % num_parts) + num_parts) % num_parts  # Spark pmod
    return jnp.where(mask, pid, num_parts)


def _bucketize(batch: ColumnBatch, part: jax.Array, num_parts: int,
               send_capacity: int | None = None):
    """Split into [P, C] per-column send buffers + int32[P] counts.

    ``send_capacity`` bounds C below the full shard capacity (the
    static worst case where every row targets one destination).  Rows
    beyond a destination's C would scatter out of bounds — the caller
    MUST check the returned counts against C (``exchange_local_checked``
    surfaces an overflow flag) instead of letting ``mode="drop"``
    silently truncate them."""
    cap = batch.capacity
    counts = jnp.sum(part[None, :] == jnp.arange(num_parts, dtype=jnp.int32)[:, None],
                     axis=1, dtype=jnp.int32)
    C = cap if send_capacity is None else min(send_capacity, cap)
    overflow = jnp.any(counts > C)
    order = jnp.argsort(part, stable=True)       # padding (P) sinks to end
    sorted_part = part[order]
    starts = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    rank = jnp.arange(cap, dtype=jnp.int32) - \
        starts[jnp.clip(sorted_part, 0, num_parts - 1)]
    dest = (sorted_part, rank)  # index (P, C); sorted_part==P or rank>=C drops

    send_cols = []
    for c in batch.columns:
        data_s = c.data[order]
        val_s = c.validity[order]
        if c.is_string:
            d = jnp.zeros((num_parts, C, c.max_len), c.data.dtype
                          ).at[dest].set(data_s, mode="drop")
            ln = jnp.zeros((num_parts, C), jnp.int32
                           ).at[dest].set(c.lengths[order], mode="drop")
        else:
            d = jnp.zeros((num_parts, C), c.data.dtype
                          ).at[dest].set(data_s, mode="drop")
            ln = None
        v = jnp.zeros((num_parts, C), jnp.bool_
                      ).at[dest].set(val_s, mode="drop")
        send_cols.append((d, v, ln))
    # clamp so _repack's receive mask never counts rows the bounded
    # buffer could not carry; the overflow flag is the loud signal
    return send_cols, jnp.minimum(counts, C), overflow


def _repack(schema: T.Schema, recv_cols, recv_counts: jax.Array,
            num_parts: int, cap: int) -> ColumnBatch:
    """[P, C] received buffers -> one front-packed [P*C] batch."""
    out_cap = num_parts * cap
    real = (jnp.arange(cap, dtype=jnp.int32)[None, :]
            < recv_counts[:, None]).reshape(out_cap)
    perm = jnp.argsort(~real, stable=True)
    total = jnp.sum(recv_counts, dtype=jnp.int32)
    cols = []
    for f, (d, v, ln) in zip(schema, recv_cols):
        if ln is not None:
            col = DeviceColumn(d.reshape(out_cap, d.shape[-1]),
                               v.reshape(out_cap), f.data_type,
                               ln.reshape(out_cap))
        else:
            col = DeviceColumn(d.reshape(out_cap), v.reshape(out_cap),
                               f.data_type)
        cols.append(col)
    cols = dk.gather_columns(cols, perm, total)
    return ColumnBatch(cols, total, schema)


def exchange_local(batch: ColumnBatch, part: jax.Array, num_parts: int,
                   axis_name: str) -> ColumnBatch:
    """Inside shard_map: all-to-all rows of ``batch`` by ``part`` id.

    Output capacity is P*C (static worst case: every row lands on one
    device).  The reference's analogs of these three phases are
    contiguousSplit -> UCX tag send/recv -> BufferReceiveState reassembly.
    """
    out, _ = exchange_local_checked(batch, part, num_parts, axis_name)
    return out


def exchange_local_checked(batch: ColumnBatch, part: jax.Array,
                           num_parts: int, axis_name: str,
                           send_capacity: int | None = None):
    """``exchange_local`` with a bounded [P, C] send buffer and a loud
    overflow signal.

    Returns ``(batch, overflow)``: ``overflow`` is a device bool that is
    True on any shard where one destination received more than C rows —
    those rows did NOT travel, and the caller must retry at worst-case
    capacity (mesh_exec.py degrades exactly like the OOM split-and-retry
    ladder: detect, never truncate, re-run with room).  With
    ``send_capacity=None`` C is the shard capacity and overflow is
    statically impossible."""
    send_cols, counts, overflow = _bucketize(batch, part, num_parts,
                                             send_capacity)
    a2a = partial(jax.lax.all_to_all, axis_name=axis_name,
                  split_axis=0, concat_axis=0, tiled=True)
    recv_counts = a2a(counts)
    recv_cols = [(a2a(d), a2a(v), a2a(ln) if ln is not None else None)
                 for (d, v, ln) in send_cols]
    C = batch.capacity if send_capacity is None \
        else min(send_capacity, batch.capacity)
    return _repack(batch.schema, recv_cols, recv_counts, num_parts,
                   C), overflow


def canonicalize(batch: ColumnBatch) -> ColumnBatch:
    """Re-zero padding rows after an external num_rows adjustment."""
    mask = batch.row_mask()
    cols = []
    for c in batch.columns:
        v = c.validity & mask
        if c.is_string:
            cols.append(DeviceColumn(jnp.where(v[:, None], c.data, 0), v,
                                     c.dtype, jnp.where(v, c.lengths, 0)))
        else:
            cols.append(DeviceColumn(
                jnp.where(v, c.data, jnp.zeros((), c.data.dtype)), v, c.dtype))
    return ColumnBatch(cols, batch.num_rows, batch.schema)
