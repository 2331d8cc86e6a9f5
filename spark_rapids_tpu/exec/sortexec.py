"""Sort and coalesce execs.

Reference: GpuSortExec.scala:51 (cuDF ``Table.orderBy`` per batch;
``RequireSingleBatch`` goal for a total sort), GpuCoalesceBatches.scala
(AbstractGpuCoalesceIterator :132 — concatenates small batches up to a
``CoalesceGoal``).
"""
from __future__ import annotations

from typing import Iterator, Sequence

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exec.core import (CoalesceGoal, ExecCtx, PlanNode,
                                        RequireSingleBatch, RequireSingleBatchT,
                                        TargetSize)
from spark_rapids_tpu.expr.core import Expression, bind
from spark_rapids_tpu.host.batch import HostBatch
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.ops import host_kernels as hk
from spark_rapids_tpu.ops import kernels as dk
from spark_rapids_tpu.ops.sort import SortOrder, sort_batch

__all__ = ["SortExec", "CoalesceBatchesExec", "resolve_orders"]


def resolve_orders(orders: Sequence, schema: T.Schema) -> list[SortOrder]:
    """Accept SortOrder or (expr|name, ascending[, nulls_first]) tuples and
    resolve to column-index SortOrders. Sort keys must be plain columns
    (pre-project computed keys, as Spark's planner does)."""
    out: list[SortOrder] = []
    for o in orders:
        if isinstance(o, SortOrder):
            out.append(o)
            continue
        name, *rest = o if isinstance(o, tuple) else (o,)
        if isinstance(name, Expression):
            b = bind(name, schema)
            from spark_rapids_tpu.expr.core import BoundReference
            assert isinstance(b, BoundReference), \
                "sort keys must be column references; project first"
            idx = b.index
        else:
            idx = schema.index_of(name)
        asc = rest[0] if rest else True
        nf = rest[1] if len(rest) > 1 else None
        if isinstance(schema.fields[idx].data_type, T.ArrayType):
            raise ValueError("cannot sort by an array column")
        out.append(SortOrder(idx, asc, nf))
    return out


class SortExec(PlanNode):
    """Sort each partition. With ``global_sort`` the input is first
    coalesced to a single batch per partition (reference: GpuSortExec's
    RequireSingleBatch child goal for total ordering; cross-partition
    ordering is the exchange's job via range partitioning)."""

    def __init__(self, orders: Sequence, child: PlanNode,
                 global_sort: bool = False):
        super().__init__([child])
        self._orders = resolve_orders(orders, child.output_schema)
        self._global = global_sort

    @property
    def output_schema(self) -> T.Schema:
        return self.children[0].output_schema

    @property
    def children_coalesce_goal(self) -> list[CoalesceGoal | None]:
        return [RequireSingleBatch if self._global else None]

    @property
    def output_ordering(self):
        """Each emitted batch is lexicographically sorted by the sort
        keys — equal keys are contiguous regardless of direction."""
        return [self.output_schema.names[o.child_index]
                for o in self._orders]

    def num_partitions(self, ctx: ExecCtx) -> int:
        # a global sort is a TOTAL order: the output is one partition.
        # Sorting each input partition independently and letting a limit
        # read them in partition order silently breaks the order across
        # partitions (caught by q65/q68/q73/q79 at SF1: a sort below a
        # join kept the join's partitioning).  The reference establishes
        # total order via a range exchange + per-partition sort; here the
        # final sort collapses partitions (range-partitioned distributed
        # sort remains available explicitly via RangePartitioning).
        if self._global:
            return 1
        return self.children[0].num_partitions(ctx)

    def _jit_fn(self):
        if not hasattr(self, "_sort_jit"):
            from spark_rapids_tpu.exec import compile_cache as cc
            self._sort_jit = cc.shared_jit(
                cc.fragment_key("sort", tuple(self._orders),
                                self.children[0].output_schema),
                lambda b: sort_batch(b, self._orders), name="sort_batch")
        return self._sort_jit

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        from spark_rapids_tpu.exec.core import drain_partitions
        child = self.children[0]
        if self._global:
            # concurrent drain + spillable parking, not a serial loop
            # (review finding: completed partitions must be able to
            # spill while later ones are still producing)
            batches = list(drain_partitions(ctx, child))
        else:
            batches = list(child.partition_iter(ctx, pid))
        if not batches:
            return
        if ctx.is_device:
            b = batches[0] if len(batches) == 1 \
                else ctx.dispatch(dk.concat_batches, batches)
            if self._global:
                b = _right_sized(ctx, b)
            # withRetryNoSplit (reference GpuSortExec): a sort's output
            # is a TOTAL order over its input — emitting independently
            # sorted halves would break it, so on OOM this scope only
            # spills and retries whole (no merge kernel exists to
            # recombine split outputs; see ops/sort.py)
            get_registry().inc("sort.launches")
            yield ctx.dispatch_retry(self._jit_fn(), b, split=False,
                                     op="sort")[0]
        else:
            b = batches[0] if len(batches) == 1 else hk.host_concat(batches)
            yield hk.host_sort(b, self._orders)

    def node_desc(self) -> str:
        return f"SortExec[{self._orders}]"


#: a total sort right-sizes an input of at least this many slots whose
#: row count the host does not hold: below it a sort of the empty slots
#: costs less than the fetch that would tell (PERF.md Findings PR 38)
_RIGHT_SIZE_MIN_CAPACITY = 1 << 16


def _shrunk_to_rows(ctx: ExecCtx, b, op: str):
    """``b`` in its rows' own capacity bucket where that is at most half
    of its capacity (one fetch of the count where the host does not hold
    it, a slice program), else ``b`` as it is."""
    from spark_rapids_tpu.columnar.batch import round_capacity
    rows = b.host_num_rows(op)
    cap = round_capacity(max(rows, 1))
    if cap > b.capacity // 2:
        return b
    b = ctx.dispatch(dk.shrink_capacity, b, cap)
    b.known_rows = rows
    return b


def _right_sized(ctx: ExecCtx, b):
    """A total sort, and the fetch of its result, run at batch CAPACITY,
    and the sort's one input may be what a filter left of a far larger
    batch (TPC-DS q51: 92k rows in 2^23 slots): sort it in its rows' own
    bucket.  The count is the host's where the batch carries it
    (``known_rows``); else one fetch, for large batches only."""
    if b.known_rows is None and b.capacity < _RIGHT_SIZE_MIN_CAPACITY:
        return b
    return _shrunk_to_rows(ctx, b, "fetch@SortExec")


class CoalesceBatchesExec(PlanNode):
    """Concatenate small batches up to the goal (GpuCoalesceBatches)."""

    def __init__(self, goal: CoalesceGoal, child: PlanNode):
        super().__init__([child])
        self._goal = goal

    @property
    def output_schema(self) -> T.Schema:
        return self.children[0].output_schema

    @property
    def output_batching(self) -> CoalesceGoal:
        return self._goal

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        child_it = self.children[0].partition_iter(ctx, pid)
        if isinstance(self._goal, RequireSingleBatchT):
            batches = list(child_it)
            if not batches:
                return
            if len(batches) == 1:
                yield self._maybe_shrink(ctx, batches[0])
            elif ctx.is_device:
                yield self._maybe_shrink(ctx, dk.concat_batches(batches))
            else:
                yield hk.host_concat(batches)
            return
        assert isinstance(self._goal, TargetSize)
        target = self._goal.size
        pending: list = []
        pending_bytes = 0
        for b in child_it:
            sz = b.device_size_bytes() if ctx.is_device else _host_bytes(b)
            if pending and pending_bytes + sz > target:
                yield self._flush(ctx, pending)
                pending, pending_bytes = [], 0
            pending.append(b)
            pending_bytes += sz
        if pending:
            yield self._flush(ctx, pending)

    def _upstream_can_shrink(self) -> bool:
        """True when an operator below (this side of any exchange) can
        leave batches much emptier than their capacity — filters,
        limits, residual-condition joins.  Dense pipelines skip the
        per-batch row-count sync entirely: a blocking host round trip
        per coalesced batch would serialize the async dispatch pipeline
        for zero benefit (review finding)."""
        if not hasattr(self, "_shrink_possible"):
            from spark_rapids_tpu.exec.basic import (FilterExec,
                                                     GlobalLimitExec,
                                                     LocalLimitExec)
            from spark_rapids_tpu.exec.exchange import (
                AdaptiveShuffleReaderExec, ShuffleExchangeExec)
            from spark_rapids_tpu.exec.joins import JoinExec
            found = False

            def walk(n):
                nonlocal found
                if found or isinstance(n, (ShuffleExchangeExec,
                                           AdaptiveShuffleReaderExec)):
                    # exchange slices are already right-sized
                    return
                if isinstance(n, (FilterExec, LocalLimitExec,
                                  GlobalLimitExec)) or \
                        (isinstance(n, JoinExec)
                         and n._condition is not None):
                    found = True
                    return
                for c in n.children:
                    walk(c)

            walk(self.children[0])
            self._shrink_possible = found
        return self._shrink_possible

    def _maybe_shrink(self, ctx: ExecCtx, b):
        """Repack a sparse batch (selective upstream filter) to its
        pow2 row bucket: every downstream sort/segment program runs at
        batch CAPACITY, so a 4M-capacity batch holding 400k filtered
        rows would pay 8x its useful sort work (TPC-DS q28's
        count-distinct branches).  Costs one row-count sync + a slice
        program; only probed when the upstream subtree can actually
        leave batches sparse."""
        if not ctx.is_device or not self._upstream_can_shrink():
            return b
        return _shrunk_to_rows(ctx, b, "fetch@CoalesceBatchesExec")

    def _flush(self, ctx: ExecCtx, batches: list):
        if len(batches) == 1:
            return self._maybe_shrink(ctx, batches[0])
        out = dk.concat_batches(batches) if ctx.is_device \
            else hk.host_concat(batches)
        return self._maybe_shrink(ctx, out)


def _host_bytes(b: HostBatch) -> int:
    total = 0
    for c in b.columns:
        if c.data.dtype == object:
            total += sum(len(x) for x in c.data if x is not None) + len(c.data)
        else:
            total += c.data.nbytes
        total += c.validity.nbytes
    return total
