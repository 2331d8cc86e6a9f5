"""Basic physical operators: scan(local), project, filter, range, union,
limits.

Reference: basicPhysicalOperators.scala (GpuProjectExec ~:40, GpuFilterExec
~:150, GpuRangeExec ~:200, GpuUnionExec), limit.scala (GpuLocalLimitExec,
GpuGlobalLimitExec, GpuCollectLimitExec).
"""
from __future__ import annotations

from functools import partial as _partial
from typing import Iterator, Sequence

import jax as _jax
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch, round_capacity
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.exec.core import (ExecCtx, PlanNode, host_to_device)
from spark_rapids_tpu.exec.compile_cache import guarded_jit as _guarded_jit
from spark_rapids_tpu.expr.core import (Alias, Expression, bind, eval_device,
                                        eval_host, output_name)
from spark_rapids_tpu.host.batch import HostBatch, HostColumn
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.ops import host_kernels as hk
from spark_rapids_tpu.ops import kernels as dk

__all__ = ["LocalScanExec", "ProjectExec", "FilterExec", "RangeExec",
           "UnionExec", "LocalLimitExec", "GlobalLimitExec"]


@_guarded_jit("monotonic_id", static_argnames=("cap",))
def _jit_miid(mask, cap: int, base):
    import jax.numpy as jnp
    data = jnp.where(mask, base + jnp.arange(cap, dtype=jnp.int64), 0)
    return DeviceColumn(data, mask, T.LongType())


@_guarded_jit("spark_partition_id", static_argnames=("cap",))
def _jit_spid(mask, cap: int, pid):
    import jax.numpy as jnp
    data = jnp.where(mask, pid.astype(jnp.int32), 0)
    return DeviceColumn(data, mask, T.IntegerType())


class LocalScanExec(PlanNode):
    """Scan over in-memory host batches, split into partitions.

    The leaf for tests and local pipelines (file scans live in
    spark_rapids_tpu.io).  On the device backend each host batch is
    transferred H2D (reference HostColumnarToGpu.scala).
    """

    def __init__(self, batches: Sequence[HostBatch], schema: T.Schema,
                 partitions: int = 1):
        super().__init__([])
        self._batches = list(batches)
        self._schema = schema
        self._parts = max(partitions, 1)

    @staticmethod
    def from_pydict(data: dict[str, list], schema: T.Schema,
                    partitions: int = 1, rows_per_batch: int | None = None
                    ) -> "LocalScanExec":
        cols = [HostColumn.from_values(data[f.name], f.data_type)
                for f in schema]
        hb = HostBatch(cols, schema)
        n = hb.num_rows
        rpb = rows_per_batch or max(n, 1)
        batches = [hk.host_slice(hb, i, i + rpb) for i in range(0, n, rpb)] \
            if n else [hb]
        return LocalScanExec(batches, schema, partitions)

    @property
    def output_schema(self) -> T.Schema:
        return self._schema

    def num_partitions(self, ctx: ExecCtx) -> int:
        return self._parts

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        mine = [b for i, b in enumerate(self._batches)
                if i % self._parts == pid]
        for hb in mine:
            if ctx.is_device:
                yield host_to_device(hb)
            else:
                yield hb

    def node_desc(self) -> str:
        return f"LocalScanExec[{self._schema.names}]"


class ProjectExec(PlanNode):
    """Evaluate bound expressions per batch (GpuProjectExec.project).

    Partition-aware expressions (spark_partition_id /
    monotonically_increasing_id) are rewritten to references of extra
    input columns computed per batch from (pid, row offset) — reference
    GpuSparkPartitionID/GpuMonotonicallyIncreasingID."""

    combines_batches = False

    def __init__(self, exprs: Sequence[Expression], child: PlanNode):
        super().__init__([child])
        self._raw = list(exprs)
        self._bound = [bind(e, child.output_schema) for e in self._raw]
        self._schema = T.Schema([
            T.StructField(output_name(r), b.dtype)
            for r, b in zip(self._raw, self._bound)])
        # hoist partition-aware expressions into extra input columns
        from spark_rapids_tpu.expr.core import BoundReference
        from spark_rapids_tpu.expr.misc import PartitionAwareExpression
        self._paware: list = []
        ncols = len(child.output_schema.fields)
        seen: dict[str, int] = {}

        def hoist(node):
            if isinstance(node, PartitionAwareExpression):
                key = type(node).__name__
                if key not in seen:
                    seen[key] = ncols + len(self._paware)
                    self._paware.append(node)
                return BoundReference(seen[key], node.dtype, False,
                                      f"_{key}")
            return node

        if any(any(isinstance(s, PartitionAwareExpression)
                   for s in e.walk()) for e in self._bound):
            self._bound = [e.transform_up(hoist) for e in self._bound]

    @property
    def output_schema(self) -> T.Schema:
        return self._schema

    @property
    def bound_exprs(self):
        return list(self._bound)

    def _jit_fn(self):
        # one program per batch shape: whole-projection jit (the eager
        # per-op path costs a dispatch round trip per op on a remote TPU),
        # shared process-wide so identical projections across plans and
        # queries reuse one compiled program (exec/compile_cache.py)
        if not hasattr(self, "_project_jit"):
            from spark_rapids_tpu.exec import compile_cache as cc

            def project(b):
                cols = [eval_device(e, b) for e in self._bound]
                return ColumnBatch(cols, b.num_rows, self._schema)

            self._project_jit = cc.shared_jit(
                cc.fragment_key("project", tuple(self._bound), self._schema,
                                self.children[0].output_schema),
                project, name="project_batch")
        return self._project_jit

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        child_it = self.children[0].partition_iter(ctx, pid)
        if ctx.is_device:
            import jax.numpy as jnp
            fn = self._jit_fn()
            # running row offset stays a DEVICE scalar: no per-batch sync
            offset = jnp.asarray(0, jnp.int64)
            for b in child_it:
                if self._paware:
                    # augment BEFORE the retry scope: the partition-aware
                    # columns ride along as data, so a split slices them
                    # with their rows and the global offsets stay exact
                    b = self._with_paware_device(b, pid, offset)
                    offset = offset + b.num_rows
                # elementwise: splitting on OOM yields identical rows
                # in order (reference GpuProjectExec withRetry)
                outs = list(ctx.dispatch_retry(fn, b, op="project"))
                if len(outs) == 1:
                    # a projection keeps its rows: the host-side count
                    # the jit boundary strips stays free downstream
                    outs[0].known_rows = b.known_rows
                yield from outs
        else:
            offset = 0
            for b in child_it:
                if self._paware:
                    b = self._with_paware_host(b, pid, offset)
                    offset += b.num_rows
                cols = [eval_host(e, b) for e in self._bound]
                yield HostBatch(cols, self._schema)

    def _with_paware_device(self, b, pid: int, offset):
        import jax.numpy as jnp
        from spark_rapids_tpu.expr.misc import MonotonicallyIncreasingID
        cols = list(b.columns)
        fields = list(b.schema.fields)
        for node in self._paware:
            if isinstance(node, MonotonicallyIncreasingID):
                col_ = _jit_miid(b.row_mask(), b.capacity,
                                 jnp.asarray(pid << 33, jnp.int64) + offset)
            else:
                col_ = _jit_spid(b.row_mask(), b.capacity,
                                 jnp.asarray(pid, jnp.int32))
            cols.append(col_)
            fields.append(T.StructField(f"_{type(node).__name__}",
                                        node.dtype, False))
        return ColumnBatch(cols, b.num_rows, T.Schema(fields))

    def _with_paware_host(self, b, pid: int, offset: int):
        from spark_rapids_tpu.expr.misc import MonotonicallyIncreasingID
        cols = list(b.columns)
        fields = list(b.schema.fields)
        n = b.num_rows
        for node in self._paware:
            if isinstance(node, MonotonicallyIncreasingID):
                data = (np.arange(n, dtype=np.int64) + (pid << 33) + offset)
            else:
                data = np.full(n, pid, dtype=np.int32)
            cols.append(HostColumn(data, np.ones(n, np.bool_), node.dtype))
            fields.append(T.StructField(f"_{type(node).__name__}",
                                        node.dtype, False))
        return HostBatch(cols, T.Schema(fields))

    @property
    def output_batching(self):
        # 1:1 batch mapping: whatever batching contract the child
        # satisfies, the projection's output satisfies too (keeps the
        # planner from inserting a coalesce that would destroy the
        # child's ordering between an aggregate pair)
        return self.children[0].output_batching

    @property
    def output_ordering(self):
        """Elementwise projection preserves row order; the child's
        clustering survives through columns projected as plain
        references (possibly renamed)."""
        from spark_rapids_tpu.expr.core import BoundReference
        child_ord = self.children[0].output_ordering
        if not child_ord:
            return None
        child_names = self.children[0].output_schema.names
        renames: dict[str, str] = {}
        for b, out in zip(self._bound, self._schema.names):
            inner = b.children[0] if isinstance(b, Alias) else b
            if isinstance(inner, BoundReference) \
                    and inner.index < len(child_names):
                renames.setdefault(child_names[inner.index], out)
        names = []
        for n in child_ord:
            if n not in renames:
                break
            names.append(renames[n])
        return names or None

    def node_desc(self) -> str:
        return f"ProjectExec[{self._schema.names}]"


def matched_columns(ops) -> tuple:
    """``(matches, ordinals)`` of a filter / project chain
    (innermost-first): how many device string matches its expressions
    hold (expr/strings.py ``string_matches``) and, for those whose
    matched child is a column of the chain's input, that column's
    ordinal."""
    from spark_rapids_tpu.expr.core import BoundReference
    from spark_rapids_tpu.expr.strings import string_matches
    matches, ordinals, projected = 0, [], False
    for op in ops:
        for e in op.bound_exprs:
            for child in string_matches(e):
                matches += 1
                if not projected and isinstance(child, BoundReference):
                    ordinals.append(child.index)
        projected = projected or type(op) is not FilterExec
    return matches, tuple(ordinals)


def count_string_matches(b, matches: int, ordinals: tuple) -> None:
    """One dispatch of a program that matches strings on ``b``:
    ``like.device.rows`` (the batch's rows where it carries its count,
    else its slots, a match) and ``like.device.bytes`` (rows x the
    matched column's staged width: what the match reads at least once)."""
    if matches:
        rows = b.capacity if b.known_rows is None else b.known_rows
        get_registry().inc_many((
            ("like.device.rows", rows * matches),
            ("like.device.bytes", rows * sum(
                b.columns[i].data.shape[1] for i in ordinals))))


class FilterExec(PlanNode):
    """Boolean condition -> compact kept rows (GpuFilterExec:
    Table.filter via front-packing permutation on device)."""

    combines_batches = False

    def __init__(self, condition: Expression, child: PlanNode):
        super().__init__([child])
        from spark_rapids_tpu.expr.misc import reject_partition_aware
        reject_partition_aware([condition], "a filter condition")
        self._cond = bind(condition, child.output_schema)
        assert isinstance(self._cond.dtype, T.BooleanType), \
            f"filter condition must be boolean, got {self._cond.dtype}"

    @property
    def bound_exprs(self):
        return [self._cond]

    @property
    def output_schema(self) -> T.Schema:
        return self.children[0].output_schema

    @property
    def output_ordering(self):
        # front-pack compaction is a stable permutation: surviving rows
        # keep their relative order, so the child's clustering holds
        return self.children[0].output_ordering

    @property
    def output_batching(self):
        # 1:1 batch mapping (fewer rows per batch never violates a goal
        # the child's batching already satisfied)
        return self.children[0].output_batching

    def _jit_fn(self):
        if not hasattr(self, "_filter_jit"):
            from spark_rapids_tpu.exec import compile_cache as cc

            def filt(b):
                c = eval_device(self._cond, b)
                keep = c.data & c.validity  # null -> drop (SQL WHERE)
                return dk.compact(b, keep)

            # a condition that matches strings runs under a name of its
            # own, so a trace tells its seconds from other filters'
            self._matched = matched_columns([self])
            self._filter_jit = cc.shared_jit(
                cc.fragment_key("filter", self._cond,
                                self.children[0].output_schema),
                filt, name="string_match_filter" if self._matched[0]
                else "filter_batch")
        return self._filter_jit

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        child_it = self.children[0].partition_iter(ctx, pid)
        if ctx.is_device:
            fn = self._jit_fn()
            for b in child_it:
                # row-wise predicate: split pieces filter to the same
                # surviving rows in order (GpuFilterExec withRetry)
                dk.count_compaction(b.capacity)
                count_string_matches(b, *self._matched)
                yield from ctx.dispatch_retry(fn, b, op="filter")
        else:
            for b in child_it:
                c = eval_host(self._cond, b)
                keep = c.data.astype(np.bool_) & c.validity
                yield hk.host_filter(b, keep)


class RangeExec(PlanNode):
    """Generate [start, end) step sequences on device (GpuRangeExec)."""

    def __init__(self, start: int, end: int, step: int = 1,
                 partitions: int = 1, name: str = "id",
                 rows_per_batch: int = 1 << 20):
        super().__init__([])
        self._start, self._end, self._step = start, end, step
        self._parts = partitions
        self._rpb = rows_per_batch
        self._schema = T.Schema([T.StructField(name, T.LongType())])

    @property
    def output_schema(self) -> T.Schema:
        return self._schema

    def num_partitions(self, ctx: ExecCtx) -> int:
        return self._parts

    def _partition_bounds(self, pid: int) -> tuple[int, int]:
        total = max(0, -(-(self._end - self._start) // self._step))
        per = -(-total // self._parts)
        lo, hi = pid * per, min((pid + 1) * per, total)
        return lo, max(hi, lo)

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        import jax.numpy as jnp
        lo, hi = self._partition_bounds(pid)
        for off in range(lo, hi, self._rpb) if hi > lo else []:
            cnt = min(self._rpb, hi - off)
            vals = (np.arange(off, off + cnt, dtype=np.int64) * self._step
                    + self._start)
            validity = np.ones(cnt, np.bool_)
            if ctx.is_device:
                cap = round_capacity(cnt)
                col = DeviceColumn.from_numpy(vals, validity, T.LongType(), cap)
                yield ColumnBatch([col], jnp.asarray(cnt, jnp.int32),
                                  self._schema)
            else:
                yield HostBatch([HostColumn(vals, validity, T.LongType())],
                                self._schema)


class UnionExec(PlanNode):
    """Concatenate children's partitions (GpuUnionExec): output partitions
    are the children's partitions back to back."""

    def __init__(self, children: Sequence[PlanNode]):
        super().__init__(children)
        s0 = children[0].output_schema
        for c in children[1:]:
            assert [f.data_type for f in c.output_schema] == \
                [f.data_type for f in s0], "union schema mismatch"

    @property
    def output_schema(self) -> T.Schema:
        return self.children[0].output_schema

    def num_partitions(self, ctx: ExecCtx) -> int:
        return sum(c.num_partitions(ctx) for c in self.children)

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        for c in self.children:
            np_ = c.num_partitions(ctx)
            if pid < np_:
                for b in c.partition_iter(ctx, pid):
                    yield _relabel(b, self.output_schema)
                return
            pid -= np_
        raise IndexError("partition out of range")


def _relabel(b, schema: T.Schema):
    if isinstance(b, HostBatch):
        cols = [HostColumn(c.data, c.validity, f.data_type)
                for c, f in zip(b.columns, schema)]
        return HostBatch(cols, schema)
    return ColumnBatch(b.columns, b.num_rows, schema)


def _limited(ctx: ExecCtx, it: Iterator, remaining: int) -> Iterator:
    """Yield batches sliced to at most ``remaining`` total rows."""
    for b in it:
        if remaining <= 0:
            return
        if ctx.is_device:
            b = dk.slice_batch(b, remaining)
            remaining -= b.host_num_rows()
        else:
            b = hk.host_slice(b, 0, remaining)
            remaining -= b.num_rows
        yield b


class LocalLimitExec(PlanNode):
    """Per-partition limit (GpuLocalLimitExec, limit.scala)."""

    def __init__(self, limit: int, child: PlanNode):
        super().__init__([child])
        self._limit = limit

    @property
    def output_schema(self) -> T.Schema:
        return self.children[0].output_schema

    @property
    def output_ordering(self):
        return self.children[0].output_ordering  # prefix slice

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        yield from _limited(ctx, self.children[0].partition_iter(ctx, pid),
                            self._limit)


class GlobalLimitExec(PlanNode):
    """Whole-query limit: single output partition (GpuGlobalLimitExec)."""

    combines_batches = False

    def __init__(self, limit: int, child: PlanNode):
        super().__init__([child])
        self._limit = limit

    @property
    def output_schema(self) -> T.Schema:
        return self.children[0].output_schema

    def num_partitions(self, ctx: ExecCtx) -> int:
        return 1

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        child = self.children[0]
        all_parts = (b for cpid in range(child.num_partitions(ctx))
                     for b in child.partition_iter(ctx, cpid))
        for b in _limited(ctx, all_parts, self._limit):
            # _limited has the count on the host already
            get_registry().inc("limit.rows_out", b.known_rows
                               if ctx.is_device else b.num_rows)
            yield b
