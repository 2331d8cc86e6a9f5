"""Lexicographic sort with Spark null ordering (Table.orderBy analog).

Reference: GpuSortExec.scala:51 / SortUtils.scala build cuDF orderBy args
(ascending/descending, null ordering).  TPU-first design: one stable
multi-operand ``lax.sort`` handles any mix of key types, directions and null
orders (a key of more than ``MAX_SORT_KEYS`` operands: stable passes of
narrower sorts, ``_lexsort_permutation``).  Per key column the operands are:

* a leading null-indicator byte (0/1 by nulls-first/last),
* for floats: a NaN-indicator byte (Spark: NaN is the largest value; for
  descending keys NaN must come first) followed by the value itself with
  -0.0 normalized to +0.0 and NaN zeroed (ref NormalizeFloatingNumbers);
  descending negates the value,
* for integers/date/timestamp/bool: the value; descending uses bitwise NOT
  (monotonic inversion with no overflow),
* for strings: the padded byte matrix chunked into big-endian uint32 words
  (zero padding makes prefixes sort first); descending inverts each word.

A most-significant pad flag forces batch padding rows to sort last.

Note: no 64-bit bitcasts anywhere — TPU v5e XLA does not implement
bitcast-convert on 64-bit element types (verified empirically); s64/f64
arithmetic and comparisons are supported (emulated).

OOM retry contract (memory/retry.py): ``sort_batch`` is a TOTAL order
over its input and no pairwise sorted-merge kernel exists here, so
exec/sortexec.py runs it under ``with_retry_no_split`` (reference
GpuSortExec's withRetryNoSplit, GpuSortExec.scala) — on HBM exhaustion
the scope spills and re-attempts the whole batch but never splits it:
independently sorted halves would interleave and break the order.
Operators whose outputs compose row-wise (project/filter) or through an
associative merge (aggregate update, window state) use the splitting
scope instead.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.ops.kernels import gather_columns

__all__ = ["SortOrder", "sort_batch", "sort_permutation", "encode_key_operands",
           "normalize_floats"]


@dataclass(frozen=True)
class SortOrder:
    """One sort key: column index + direction + null ordering."""
    child_index: int
    ascending: bool = True
    nulls_first: bool | None = None  # None = Spark default (first iff asc)

    @property
    def resolved_nulls_first(self) -> bool:
        if self.nulls_first is None:
            return self.ascending  # Spark: asc->nulls first, desc->nulls last
        return self.nulls_first


def normalize_floats(x: jax.Array) -> jax.Array:
    """-0.0 -> +0.0 and NaN -> canonical NaN (ref NormalizeFloatingNumbers)."""
    zero = jnp.zeros((), x.dtype)
    x = jnp.where(x == zero, zero, x)
    return jnp.where(jnp.isnan(x), jnp.full((), jnp.nan, x.dtype), x)


def string_key_words(col: DeviceColumn) -> list[jax.Array]:
    """Padded byte matrix -> list of big-endian uint32 word operands."""
    w = col.max_len
    nwords = (w + 3) // 4
    padded = col.data if w % 4 == 0 else \
        jnp.pad(col.data, ((0, 0), (0, 4 * nwords - w)))
    b = padded.reshape(col.capacity, nwords, 4).astype(jnp.uint32)
    words = (b[..., 0] << 24) | (b[..., 1] << 16) | (b[..., 2] << 8) | b[..., 3]
    return [words[:, i] for i in range(nwords)]


def encode_key_operands(col: DeviceColumn, ascending: bool = True) -> list[jax.Array]:
    """Encode a column's values into sort operands (see module docstring)."""
    dt = col.dtype
    if isinstance(dt, T.StringType):
        # lengths break ties between strings differing only by trailing NULs
        words = string_key_words(col) + [col.lengths]
        return words if ascending else [~wd for wd in words]
    if isinstance(dt, T.BooleanType):
        v = col.data.astype(jnp.int32)
        return [v] if ascending else [~v]
    if dt.fractional:
        x = normalize_floats(col.data)
        isnan = jnp.isnan(x)
        # NaN largest: asc -> NaN flag sorts last; desc -> first
        nan_key = jnp.where(isnan, jnp.uint8(1 if ascending else 0),
                            jnp.uint8(0 if ascending else 1))
        v = jnp.where(isnan, jnp.zeros((), x.dtype), x)
        return [nan_key, v if ascending else -v]
    # integral / date / timestamp
    return [col.data] if ascending else [~col.data]


def sort_permutation(batch: ColumnBatch, orders: list[SortOrder],
                     real: jax.Array | None = None) -> jax.Array:
    """Return the permutation (int32[capacity]) that sorts the batch.

    ``real`` overrides the front-packed ``row_mask()`` real-row
    indicator — a mesh broadcast sort (exec/mesh_region.py) all-gathers
    P shard segments whose rows are packed per SEGMENT, not globally,
    so the caller supplies the segment-aware mask and the sort's
    padding-last flag simultaneously front-packs and orders."""
    cap = batch.capacity
    if real is None:
        real = batch.row_mask()
    operands: list[jax.Array] = [(~real).astype(jnp.uint8)]  # padding last
    for o in orders:
        col = batch.columns[o.child_index]
        null_ind = jnp.where(col.validity,
                             jnp.uint8(1 if o.resolved_nulls_first else 0),
                             jnp.uint8(0 if o.resolved_nulls_first else 1))
        operands.append(null_ind)
        operands.extend(encode_key_operands(col, o.ascending))
    return _lexsort_permutation(operands, cap)


#: key operands one ``lax.sort`` may take, and how many a pass takes
#: where a key has more.  The chip's compiler takes seconds that grow
#: with the SQUARE of a sort's operand count (2^16 rows, compiled for a
#: described v5e: 17 s for one key and the row index, 38 s for two keys,
#: 143 s for four).  The 21 of TPC-H Q18's five-column group key with an
#: 18-byte string in it did not compile on the chip in 1200 s as one
#: sort and take 73 s in passes of two (PERF.md Findings PR 31).  Every
#: single integer, date, boolean or float key, alone, stays the one
#: sort it was.
MAX_SORT_KEYS = 4
PASS_SORT_KEYS = 2


def _lexsort_permutation(operands: list[jax.Array], cap: int) -> jax.Array:
    """The stable permutation that orders rows by ``operands``, most
    significant first.  Up to ``MAX_SORT_KEYS`` of them: one multi-operand
    ``lax.sort`` carrying the row index.  More: the same order from
    stable passes over groups of ``PASS_SORT_KEYS`` operands, the least
    significant group first, each pass gathering its operands into the
    order the passes before it left (a stable sort by the more
    significant group keeps ties in that order: a radix sort whose digits
    are operand groups)."""
    perm = jnp.arange(cap, dtype=jnp.int32)
    step = len(operands) if len(operands) <= MAX_SORT_KEYS \
        else PASS_SORT_KEYS
    groups = [operands[i:i + step] for i in range(0, len(operands), step)]
    for n, group in enumerate(reversed(groups)):
        keys = group if n == 0 else [op[perm] for op in group]
        perm = lax.sort(keys + [perm], num_keys=len(keys),
                        is_stable=True)[-1]
    return perm


def sort_batch(batch: ColumnBatch, orders: list[SortOrder]) -> ColumnBatch:
    perm = sort_permutation(batch, orders)
    cols = gather_columns(batch.columns, perm, batch.num_rows)
    return ColumnBatch(cols, batch.num_rows, batch.schema)
