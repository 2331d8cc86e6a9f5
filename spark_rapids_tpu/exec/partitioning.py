"""Partitioning strategies: hash / range / round-robin / single.

Reference (SURVEY.md §2.4 Partitioning): GpuHashPartitioning.scala (cudf
murmur3 % n), GpuRangePartitioning.scala + GpuRangePartitioner.scala
(sampled bounds, then upper-bound search), GpuRoundRobinPartitioning,
GpuSinglePartitioning; device slicing via Table.contiguousSplit
(GpuPartitioning.scala:45-52).

TPU design: partition ids are computed on device (bit-exact Spark
murmur3 pmod for hash; rank-vs-bounds comparison for range) and each
output partition is front-pack compacted — no host round trip, so the
split fuses into the surrounding program.  Range bounds are quantile
rows of an on-device sort of the full input (the exchange is already a
stage barrier holding all batches), deterministic across backends where
the reference's reservoir sample is not.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.exec.compile_cache import guarded_jit
from spark_rapids_tpu.exec.core import fetch_to_host
from spark_rapids_tpu.expr.core import (Expression, bind, eval_device,
                                        eval_host)
from spark_rapids_tpu.host.batch import HostBatch
from spark_rapids_tpu.ops import host_kernels as hk
from spark_rapids_tpu.ops import kernels as dk
from spark_rapids_tpu.ops.segmented import _cols_differ
from spark_rapids_tpu.ops.sort import SortOrder, encode_key_operands
from spark_rapids_tpu.parallel.mesh_shuffle import partition_ids_for_keys

__all__ = ["Partitioning", "HashPartitioning", "RangePartitioning",
           "RoundRobinPartitioning", "SinglePartitioning"]


class Partitioning:
    """Computes int32 partition ids per row on either backend."""

    def __init__(self, num_partitions: int):
        self.num_partitions = num_partitions

    def bind(self, schema: T.Schema) -> None:
        """Resolve key expressions against the child schema."""

    def prepare(self, batches, is_device: bool) -> None:
        """One-time setup over ALL materialized input batches (range
        bounds); called by the exchange before partitioning."""

    def device_ids(self, batch: ColumnBatch, batch_index: int) -> jax.Array:
        raise NotImplementedError

    def host_ids(self, batch: HostBatch, batch_index: int) -> np.ndarray:
        raise NotImplementedError


def _augment_device(batch: ColumnBatch, bound_keys) -> tuple:
    cols = list(batch.columns)
    fields = list(batch.schema.fields)
    idx = []
    for i, k in enumerate(bound_keys):
        v = eval_device(k, batch)
        cols.append(v)
        fields.append(T.StructField(f"_pk{i}", k.dtype, True))
        idx.append(len(cols) - 1)
    return ColumnBatch(cols, batch.num_rows, T.Schema(fields)), idx


class HashPartitioning(Partitioning):
    """Spark-bit-exact murmur3 pmod (reference GpuHashPartitioning)."""

    def __init__(self, keys: Sequence[Expression], num_partitions: int):
        super().__init__(num_partitions)
        self._keys = list(keys)
        self._bound = None

    def bind(self, schema: T.Schema) -> None:
        self._bound = [bind(k, schema) for k in self._keys]

    def device_ids(self, batch: ColumnBatch, batch_index: int) -> jax.Array:
        b2, idx = _augment_device(batch, self._bound)
        ids = partition_ids_for_keys(b2, idx, self.num_partitions)
        # padding rows got id == num_partitions; compact drops them anyway
        return ids

    def host_ids(self, batch: HostBatch, batch_index: int) -> np.ndarray:
        from spark_rapids_tpu.expr.core import EvalCtx, Val
        from spark_rapids_tpu.expr.hashing import murmur3_val, DEFAULT_SEED
        n = batch.num_rows
        ctx = EvalCtx(np, False, n, np.ones(n, np.bool_))
        seed = np.full(n, DEFAULT_SEED, dtype=np.uint32)
        for k in self._bound:
            c = eval_host(k, batch)
            seed = murmur3_val(Val(c.data, c.validity, None, c.dtype),
                               seed, ctx)
        h = seed.astype(np.int32)
        n_p = self.num_partitions
        return ((h % n_p) + n_p) % n_p


class RoundRobinPartitioning(Partitioning):
    """Even distribution by running row index (reference
    GpuRoundRobinPartitioning; deterministic instead of random-start)."""

    def __init__(self, num_partitions: int):
        super().__init__(num_partitions)
        self._offsets: list[int] = []

    def prepare(self, batches, is_device: bool) -> None:
        # precompute each batch's global row offset so both backends and
        # any batch order produce identical assignment (counts fetched in
        # ONE device round trip, not one per batch)
        if is_device:
            counts = [int(c) for c in
                      # enginelint: disable=RL003 (ONE stacked round trip for all batch counts; this IS the batched sync)
                      fetch_to_host([b.num_rows for b in batches],
                                    "fetch@ShuffleExchangeExec")]
        else:
            counts = [b.num_rows for b in batches]
        off = 0
        self._offsets = []
        for c in counts:
            self._offsets.append(off)
            off += c

    def device_ids(self, batch: ColumnBatch, batch_index: int) -> jax.Array:
        off = self._offsets[batch_index]
        return (jnp.arange(batch.capacity, dtype=jnp.int32) + off) \
            % self.num_partitions

    def host_ids(self, batch: HostBatch, batch_index: int) -> np.ndarray:
        off = self._offsets[batch_index]
        return (np.arange(batch.num_rows, dtype=np.int32) + off) \
            % self.num_partitions


class SinglePartitioning(Partitioning):
    def __init__(self):
        super().__init__(1)

    def device_ids(self, batch: ColumnBatch, batch_index: int) -> jax.Array:
        return jnp.zeros(batch.capacity, jnp.int32)

    def host_ids(self, batch: HostBatch, batch_index: int) -> np.ndarray:
        return np.zeros(batch.num_rows, np.int32)


# ---------------------------------------------------------------------------
# Range partitioning
# ---------------------------------------------------------------------------

def _rank_operands(cols, orders: Sequence[SortOrder], valid_rows):
    """Sort operand list for ranking rows under ``orders`` (nulls
    participate per nulls_first)."""
    operands = [(~valid_rows).astype(jnp.uint8)]
    for o, c in zip(orders, cols):
        null_ind = jnp.where(c.validity,
                             jnp.uint8(1 if o.resolved_nulls_first else 0),
                             jnp.uint8(0 if o.resolved_nulls_first else 1))
        operands.append(null_ind)
        operands.extend(encode_key_operands(c, o.ascending))
    return operands


def _combined_rank_ids(a_cols, b_cols, orders, real_a, real_b):
    """Dense ranks comparable across two row sets (a=data, b=bounds)."""
    from jax import lax
    na = real_a.shape[0]
    cc = na + real_b.shape[0]
    comb = []
    for ca, cb in zip(a_cols, b_cols):
        validity = jnp.concatenate([ca.validity, cb.validity])
        if ca.is_string:
            w = max(ca.max_len, cb.max_len)
            da = jnp.pad(ca.data, ((0, 0), (0, w - ca.max_len)))
            db = jnp.pad(cb.data, ((0, 0), (0, w - cb.max_len)))
            comb.append(DeviceColumn(jnp.concatenate([da, db]), validity,
                                     ca.dtype,
                                     jnp.concatenate([ca.lengths, cb.lengths])))
        else:
            comb.append(DeviceColumn(jnp.concatenate([ca.data, cb.data]),
                                     validity, ca.dtype))
    valid = jnp.concatenate([real_a, real_b])
    operands = _rank_operands(comb, orders, valid)
    iota = jnp.arange(cc, dtype=jnp.int32)
    sorted_ops = lax.sort(operands + [iota], num_keys=len(operands),
                          is_stable=True)
    order = sorted_ops[-1]
    differ = jnp.zeros(cc, jnp.bool_)
    for c in comb:
        sc = DeviceColumn(c.data[order], c.validity[order], c.dtype,
                          None if c.lengths is None else c.lengths[order])
        differ = differ | _cols_differ(sc)
    pos = jnp.arange(cc, dtype=jnp.int32)
    seg = jnp.cumsum(((pos > 0) & differ).astype(jnp.int32))
    ids = jnp.zeros(cc, jnp.int32).at[order].set(seg)
    return ids[:na], ids[na:]


class RangePartitioning(Partitioning):
    """Ordered partitioning by quantile bounds (reference
    GpuRangePartitioning + GpuRangePartitioner).

    ``prepare`` concatenates the input, sorts it by ``orders`` on the
    executing backend and takes n-1 equally spaced rows as bounds; a
    row's partition = count of bounds strictly below it (Spark
    RangePartitioner.getPartition semantics).
    """

    def __init__(self, orders: Sequence, num_partitions: int):
        super().__init__(num_partitions)
        self._orders_raw = list(orders)
        self._orders: list[SortOrder] = []
        self._key_exprs: list[Expression] = []
        self._bounds_d: list[DeviceColumn] | None = None
        self._bounds_h: HostBatch | None = None

    def bind(self, schema: T.Schema) -> None:
        from spark_rapids_tpu.exec.sortexec import resolve_orders
        self._schema = schema
        self._orders = resolve_orders(self._orders_raw, schema)

    def prepare(self, batches, is_device: bool) -> None:
        nb = self.num_partitions - 1
        if nb <= 0 or not batches:
            self._bounds_d = []
            self._bounds_h = None
            return
        if is_device:
            big = dk.concat_batches(batches) if len(batches) > 1 else batches[0]
            sb = _jit_sorted(big, tuple(self._orders))
            n = big.num_rows
            pos = ((jnp.arange(1, self.num_partitions, dtype=jnp.int64)
                    * n.astype(jnp.int64)) // self.num_partitions)
            pos = jnp.clip(pos, 0, jnp.maximum(n - 1, 0)).astype(jnp.int32)
            key_cols = [sb.columns[o.child_index] for o in self._orders]
            self._bounds_d = [
                DeviceColumn(c.data[pos], c.validity[pos], c.dtype,
                             None if c.lengths is None else c.lengths[pos])
                for c in key_cols]
            self._bounds_real = n > 0  # no bounds when input empty
        else:
            big = hk.host_concat(list(batches))
            sb = hk.host_sort(big, self._orders)
            n = big.num_rows
            if n == 0:
                self._bounds_h = None
                return
            pos = np.clip((np.arange(1, self.num_partitions, dtype=np.int64)
                           * n) // self.num_partitions, 0, n - 1)
            self._bounds_h = sb.take(pos)

    def device_ids(self, batch: ColumnBatch, batch_index: int) -> jax.Array:
        if not self._bounds_d:
            return jnp.zeros(batch.capacity, jnp.int32)
        key_cols = [batch.columns[o.child_index] for o in self._orders]
        nb = self.num_partitions - 1
        real_b = jnp.broadcast_to(jnp.asarray(self._bounds_real), (nb,))
        row_rank, bound_rank = _combined_rank_ids(
            key_cols, self._bounds_d, self._orders, batch.row_mask(), real_b)
        sorted_b = jnp.sort(bound_rank)
        return jnp.searchsorted(sorted_b, row_rank,
                                side="left").astype(jnp.int32)

    def host_ids(self, batch: HostBatch, batch_index: int) -> np.ndarray:
        n = batch.num_rows
        if self._bounds_h is None:
            return np.zeros(n, np.int32)
        # rank rows against bounds with the host sort's key codes
        nb = self._bounds_h.num_rows
        key_idx = [o.child_index for o in self._orders]
        comb_cols = []
        for ki in key_idx:
            a, b = batch.columns[ki], self._bounds_h.columns[ki]
            data = np.concatenate([a.data, b.data])
            validity = np.concatenate([a.validity, b.validity])
            from spark_rapids_tpu.host.batch import HostColumn
            comb_cols.append(HostColumn(data, validity, a.dtype))
        from spark_rapids_tpu.host.batch import HostBatch as HB
        schema = T.Schema([batch.schema.fields[ki] for ki in key_idx])
        comb = HB(comb_cols, schema)
        orders2 = [SortOrder(i, o.ascending, o.nulls_first)
                   for i, o in enumerate(self._orders)]
        perm = hk.host_sort_permutation(comb, orders2)
        # dense ranks with key-equality grouping
        ranks = np.zeros(n + nb, np.int64)
        r = 0
        for j in range(1, n + nb):
            prev, cur = perm[j - 1], perm[j]
            if any(not _host_keys_equal(c, prev, cur) for c in comb_cols):
                r += 1
            ranks[cur] = r
        ranks[perm[0]] = 0
        row_rank = ranks[:n]
        bound_rank = np.sort(ranks[n:])
        return np.searchsorted(bound_rank, row_rank,
                               side="left").astype(np.int32)


def _host_keys_equal(c, i: int, j: int) -> bool:
    vi, vj = c.validity[i], c.validity[j]
    if not vi or not vj:
        return vi == vj
    a, b = c.data[i], c.data[j]
    if isinstance(c.dtype, (T.FloatType, T.DoubleType)):
        fa, fb = float(a), float(b)
        if fa != fa and fb != fb:
            return True
        return fa == fb
    return a == b


@guarded_jit("range_partition_sort", static_argnames=("orders",))
def _jit_sorted(batch: ColumnBatch, orders):
    from spark_rapids_tpu.ops.sort import sort_batch
    return sort_batch(batch, list(orders))
