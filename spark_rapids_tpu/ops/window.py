"""Window function device kernels.

Reference: GpuWindowExec + GpuWindowExpression (GpuWindowExec.scala:92,
GpuWindowExpression.scala:169-830) lower to cuDF rolling-window
aggregations.  TPU design: after one sort by (partition keys, order
keys), every window shape becomes static-shape index arithmetic:

* partition extents ``seg_start/seg_end`` via boundary-flag cummax,
* running (UNBOUNDED PRECEDING..CURRENT ROW) and whole-partition frames
  via one **segmented scan** of the sorted column (log2(cap) shifted
  passes; no gather, and no prefix that carries other partitions' rows),
* frames bounded by row offsets via **sparse tables** (log2(cap) levels
  of power-of-two-span min/max, XLA-friendly static depth) for min/max
  and clamped prefix-sum differences for sum/count/avg,
* sums and averages of doubles that are whole cents (money) over
  ``int64`` cents, rounded once (``ops/cents.py``),
* RANGE frames differ from ROWS only in using peer-group edges
  (first/last row with equal order keys) as the effective row,
* row_number/rank/dense_rank/lead/lag from the same segment arrays.

All results are computed in sorted order; the exec emits the sorted
batch (Spark does not define window output order).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.ops.segmented import (AggSpec, _cols_differ,
                                            _compute_agg, _segmented_scan)

__all__ = ["WindowFrame", "UNBOUNDED", "CURRENT_ROW", "SegmentInfo",
           "sorted_segments", "frame_scans", "running_or_bounded_agg",
           "row_number", "rank",
           "dense_rank", "lead_lag"]

UNBOUNDED = None
CURRENT_ROW = 0


@dataclass(frozen=True)
class WindowFrame:
    """ROWS/RANGE frame: bounds are None (unbounded) or int row offsets
    (negative = preceding).  RANGE only supports UNBOUNDED/CURRENT_ROW
    bounds (Spark's value-RANGE with literal offsets is a planner
    rejection, as in the reference tagging)."""
    mode: str = "range"            # "rows" | "range"
    lower: int | None = UNBOUNDED  # None=unbounded preceding, k<=0 offset
    upper: int | None = CURRENT_ROW  # None=unbounded following, k>=0

    def __post_init__(self):
        if self.mode == "range":
            assert self.lower in (UNBOUNDED, CURRENT_ROW)
            assert self.upper in (UNBOUNDED, CURRENT_ROW)


@dataclass
class SegmentInfo:
    """Per-row partition/peer extents over the sorted batch."""
    seg_start: jax.Array    # int32[cap] first row index of row's partition
    seg_end: jax.Array      # int32[cap] last row index (inclusive)
    peer_start: jax.Array   # first row of the order-key peer group
    peer_end: jax.Array     # last row of the peer group
    seg_id: jax.Array       # int32[cap]
    order_change: jax.Array  # bool[cap] order key differs from prev in seg
    real: jax.Array         # bool[cap]


def sorted_segments(sb: ColumnBatch, part_idx: Sequence[int],
                    order_idx: Sequence[int]) -> SegmentInfo:
    cap = sb.capacity
    idx = jnp.arange(cap, dtype=jnp.int32)
    real = sb.row_mask()
    part_flag = jnp.zeros(cap, jnp.bool_)
    for ki in part_idx:
        part_flag = part_flag | _cols_differ(sb.columns[ki])
    part_flag = (idx == 0) | (part_flag & real) | (idx == sb.num_rows)
    part_flag = part_flag & (idx <= sb.num_rows)
    seg_id = jnp.cumsum(part_flag.astype(jnp.int32)) - 1
    seg_start = lax.cummax(jnp.where(part_flag, idx, 0))
    # seg_end: reverse cummax of next-boundary - 1
    nxt = jnp.where(part_flag, idx, cap)
    rev_next = jnp.flip(lax.cummin(jnp.flip(
        jnp.concatenate([nxt[1:], jnp.asarray([cap], jnp.int32)]))))
    seg_end = jnp.minimum(rev_next - 1, jnp.maximum(sb.num_rows - 1, 0))

    order_change = jnp.zeros(cap, jnp.bool_)
    for ki in order_idx:
        order_change = order_change | _cols_differ(sb.columns[ki])
    peer_flag = part_flag | (order_change & real)
    peer_start = lax.cummax(jnp.where(peer_flag, idx, 0))
    pnxt = jnp.where(peer_flag, idx, cap)
    rev_pnext = jnp.flip(lax.cummin(jnp.flip(
        jnp.concatenate([pnxt[1:], jnp.asarray([cap], jnp.int32)]))))
    peer_end = jnp.minimum(rev_pnext - 1, jnp.maximum(sb.num_rows - 1, 0))
    return SegmentInfo(seg_start, seg_end, peer_start, peer_end, seg_id,
                       order_change & real, real)


# ---------------------------------------------------------------------------
# frame edges
# ---------------------------------------------------------------------------

def _frame_edges(seg: SegmentInfo, frame: WindowFrame):
    """(lo, hi) inclusive row-index bounds per row."""
    cap = seg.seg_start.shape[0]
    idx = jnp.arange(cap, dtype=jnp.int32)
    if frame.mode == "rows":
        lo = seg.seg_start if frame.lower is UNBOUNDED else \
            jnp.maximum(idx + frame.lower, seg.seg_start)
        hi = seg.seg_end if frame.upper is UNBOUNDED else \
            jnp.minimum(idx + frame.upper, seg.seg_end)
    else:  # range: CURRENT_ROW means the whole peer group
        lo = seg.seg_start if frame.lower is UNBOUNDED else seg.peer_start
        hi = seg.seg_end if frame.upper is UNBOUNDED else seg.peer_end
    return lo, hi


# ---------------------------------------------------------------------------
# reductions over every row's frame
# ---------------------------------------------------------------------------

def frame_scans(frame: WindowFrame) -> bool:
    """True for a frame that starts at its partition's first row and
    ends at the current row, its last peer (range mode) or the
    partition's last row: running totals and whole-partition frames."""
    return frame.lower is UNBOUNDED and frame.upper in (UNBOUNDED,
                                                        CURRENT_ROW)


class _ScanFrames:
    """Reductions over frames that start at the partition's first row
    (``frame_scans``): one forward segmented scan of the sorted column
    (``ops/segmented._segmented_scan``: log2(capacity) shifted passes,
    no gather, no prefix over other partitions' rows), and where the
    frame ends past the current row one more scan that carries the value
    on the frame's last row back over its rows.  A row's result is made
    of its own partition's rows only: an integer sum exactly."""

    def __init__(self, seg: SegmentInfo, frame: WindowFrame):
        cap = seg.seg_start.shape[0]
        idx = jnp.arange(cap, dtype=jnp.int32)
        self.first = idx == seg.seg_start
        end = seg.seg_end if frame.upper is UNBOUNDED else \
            seg.peer_end if frame.mode == "range" else None
        self.last = None if end is None else \
            (idx == end) | (idx == cap - 1)

    def _reduce(self, op, x):
        x = _segmented_scan(op, self.first, x, forward=True)
        if self.last is not None:
            x = _segmented_scan(lambda row, later: later, self.last, x)
        return x

    def sum(self, x):
        return self._reduce(jnp.add, x)

    def min(self, x):
        return self._reduce(jnp.minimum, x)

    def max(self, x):
        return self._reduce(jnp.maximum, x)


class _TableFrames:
    """Reductions over frames bounded by row offsets: a sum is the
    difference of two prefix sums over the whole sorted batch (exact for
    integers; a floating sum carries the rounding of the rows before its
    partition), ``min`` / ``max`` two overlapping power-of-two spans of a
    sparse table (log2(capacity) levels of the whole column)."""

    def __init__(self, seg: SegmentInfo, frame: WindowFrame):
        cap = seg.seg_start.shape[0]
        self.lo, self.hi = _frame_edges(seg, frame)
        # empty frames (lo > hi, e.g. ROWS 2 FOLLOWING..5 FOLLOWING at the
        # partition tail) must yield 0, not a negative cross-partition diff
        self._to = jnp.clip(jnp.maximum(self.hi + 1, self.lo), 0, cap)
        self._from = jnp.clip(self.lo, 0, cap)

    def sum(self, x):
        ps = jnp.concatenate([jnp.zeros(1, x.dtype), jnp.cumsum(x)])
        return ps[self._to] - ps[self._from]

    def _extreme(self, op, x):
        ident = jnp.inf if x.dtype.kind == "f" else jnp.iinfo(x.dtype).max
        if op is jnp.maximum:
            ident = -jnp.inf if x.dtype.kind == "f" else \
                jnp.iinfo(x.dtype).min
        return _range_query(_sparse_table(x, op), self.lo, self.hi, op,
                            ident)

    def min(self, x):
        return self._extreme(jnp.minimum, x)

    def max(self, x):
        return self._extreme(jnp.maximum, x)


def _sparse_table(x: jax.Array, op) -> list[jax.Array]:
    """st[k][i] = op over x[i : i+2^k), clamped at the end."""
    cap = x.shape[0]
    levels = [x]
    k = 1
    while (1 << k) <= cap:
        prev = levels[-1]
        half = 1 << (k - 1)
        shifted = jnp.concatenate([prev[half:], prev[-1:].repeat(half)])
        levels.append(op(prev, shifted))
        k += 1
    return levels


def _range_query(levels: list[jax.Array], lo, hi, op, identity):
    """Per-row op over x[lo..hi] via two overlapping power-of-two spans."""
    length = hi - lo + 1
    valid = length > 0
    length = jnp.maximum(length, 1)
    # floor(log2(length)) via pure integer comparisons (no f64 log on TPU)
    k = jnp.zeros(length.shape, jnp.int32)
    for kk in range(1, len(levels)):
        k = k + (length >= (1 << kk)).astype(jnp.int32)
    cap = levels[0].shape[0]
    res = jnp.full(levels[0].shape, identity, levels[0].dtype)
    for kk in range(len(levels)):
        span = 1 << kk
        a = levels[kk][jnp.clip(lo, 0, cap - 1)]
        b = levels[kk][jnp.clip(hi - span + 1, 0, cap - 1)]
        cand = op(a, b)
        res = jnp.where(k == kk, cand, res)
    return jnp.where(valid, res, identity)


# ---------------------------------------------------------------------------
# aggregates over frames
# ---------------------------------------------------------------------------

def running_or_bounded_agg(op: str, col: DeviceColumn | None,
                           seg: SegmentInfo, frame: WindowFrame):
    """count_star|count|sum|avg|min|max over every row's frame.  Returns
    (data, validity, result_type).

    The aggregate is the group-by's (``ops/segmented._compute_agg``:
    NULLs skipped, NaN the largest double, a ``sum`` / ``avg`` of doubles
    that are whole cents taken over ``int64`` cents and rounded once,
    ``ops/cents.py``, so equal totals compare equal whatever batch,
    partition or sort order produced them); what differs is how rows
    reduce to a result: over each row's frame, by a scan where the frame
    starts at its partition's first row (``frame_scans``) and by prefix
    differences and a sparse table where row offsets bound it."""
    if col is not None and col.is_var_width and op in ("min", "max"):
        raise NotImplementedError("windowed min/max over strings/arrays")
    red = (_ScanFrames if frame_scans(frame) else _TableFrames)(seg, frame)
    rows = red.sum(seg.real.astype(jnp.int32)).astype(jnp.int64) \
        if op == "count_star" else None
    out = _compute_agg(AggSpec(op, 0), col, red, seg.real, seg.real, rows)
    return out.data, out.validity, out.dtype


# ---------------------------------------------------------------------------
# ranking / offset functions
# ---------------------------------------------------------------------------

def row_number(seg: SegmentInfo) -> jax.Array:
    idx = jnp.arange(seg.seg_start.shape[0], dtype=jnp.int32)
    return idx - seg.seg_start + 1


def rank(seg: SegmentInfo) -> jax.Array:
    return seg.peer_start - seg.seg_start + 1


def dense_rank(seg: SegmentInfo) -> jax.Array:
    cap = seg.seg_start.shape[0]
    changes = jnp.cumsum(seg.order_change.astype(jnp.int32))
    return changes - changes[seg.seg_start] + 1


def lead_lag(col: DeviceColumn, seg: SegmentInfo, offset: int,
             default_data=None, default_valid=None, default_len=None):
    """lead(offset>0) / lag(offset<0) within the partition.

    ``default_*``: optional out-of-frame fill — scalar-broadcast array
    for fixed-width columns; for strings a [cap, w] byte matrix plus
    ``default_len`` (round-1 advisor finding: strings previously raised
    inside the jitted program)."""
    cap = col.capacity
    idx = jnp.arange(cap, dtype=jnp.int32)
    src = idx + offset
    in_seg = (src >= seg.seg_start) & (src <= seg.seg_end) & seg.real
    srcc = jnp.clip(src, 0, cap - 1)
    validity = jnp.where(in_seg, col.validity[srcc], False)
    if col.is_var_width:
        cdata = col.data
        if default_data is not None and \
                default_data.shape[1] > cdata.shape[1]:
            cdata = jnp.pad(
                cdata, ((0, 0), (0, default_data.shape[1] - cdata.shape[1])))
        data = jnp.where(validity[:, None], cdata[srcc], 0)
        lengths = jnp.where(validity, col.lengths[srcc], 0)
        if default_data is not None:
            if data.shape[1] < default_data.shape[1]:
                data = jnp.pad(
                    data, ((0, 0), (0, default_data.shape[1] - data.shape[1])))
            use_def = ~in_seg & seg.real & default_valid
            data = jnp.where(use_def[:, None], default_data, data)
            lengths = jnp.where(use_def, default_len, lengths)
            validity = validity | use_def
        return data, validity, lengths
    data = jnp.where(validity, col.data[srcc], jnp.zeros((), col.data.dtype))
    if default_data is not None:
        use_def = ~in_seg & seg.real & default_valid
        data = jnp.where(use_def, default_data, data)
        validity = validity | use_def
    return data, validity, None
