"""Hash-aggregate exec with partial/final/complete modes.

Reference: aggregate.scala (GpuHashAggregateExec, ``doExecuteColumnar``
:348-560): per input batch compute a groupby aggregate, then iteratively
concatenate with the running result and merge-aggregate; final projection
over the aggregation buffer.  The device kernel here is sort-based
(:mod:`spark_rapids_tpu.ops.segmented`, the TPU-idiomatic substitute for
cuDF's hash groupby — see SURVEY.md §7 hard parts): one sort by the keys,
each aggregate a segmented scan that leaves a group's value on its first
sorted row, then keys and results gathered together from those rows; no
row is scattered.

When the update sorts and when it does not: the per-batch update is ONE
program (``agg_update``) around ``segmented.group_by_update``.  It
discovers up to 64 distinct keys in the batch; a batch with that few is
reduced by mask, group by group, with no sort, gather or scatter over the
batch, and a batch with more takes the ``sorted_group_by`` branch of the
same ``lax.cond`` (as do ``percentile`` and string ``min``/``max``,
always).  The program returns which branch ran beside the group count;
both ride in the chunk's one stacked fetch and are counted as
``agg.update.dense`` / ``agg.update.sorted`` (and the group counts
summed as ``agg.update.groups``) in the metrics registry, hence in the
per-query record.  The cross-batch merge and the final mode
always sort: they see a handful of small buffers.

Modes mirror Spark's aggregate modes:
* ``complete`` — one exec does update + cross-batch merge + result;
* ``partial``  — update only, emits the aggregation buffer (keys +
  intermediates) for an exchange;
* ``final``    — consumes buffer batches, merges across them, projects
  results.
"""
from __future__ import annotations

from typing import Iterator, Sequence

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.exec.core import (ExecCtx, PlanNode,
                                        RequireSingleBatch, TargetSize,
                                        fetch_to_host)
from spark_rapids_tpu.expr.aggregates import AggregateFunction
from spark_rapids_tpu.expr.core import (Alias, BoundReference, Expression,
                                        bind, eval_device, eval_host,
                                        output_name)
from spark_rapids_tpu.host.batch import HostBatch
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.ops import host_kernels as hk
from spark_rapids_tpu.ops import kernels as dk
from spark_rapids_tpu.ops.segmented import (AggSpec, group_by_update,
                                            sorted_group_by)

__all__ = ["HashAggregateExec"]

#: span of this operator's blocking count fetches (exec/core.fetch_to_host)
_FETCH = "fetch@HashAggregateExec"


def _strip_alias(e: Expression) -> Expression:
    return e.children[0] if isinstance(e, Alias) else e


class HashAggregateExec(PlanNode):
    """Group-by aggregation.

    ``group_exprs``: grouping expressions (resolved against child schema).
    ``result_exprs``: output expressions over group keys and aggregate
    functions (e.g. ``(Sum(col("x")) / CountStar()).alias("r")``).
    """

    def __init__(self, group_exprs: Sequence[Expression],
                 result_exprs: Sequence[Expression], child: PlanNode,
                 mode: str = "complete"):
        if mode == "final":
            raise ValueError("use HashAggregateExec.final_from_partial()")
        assert mode in ("complete", "partial")
        super().__init__([child])
        from spark_rapids_tpu.expr.misc import reject_partition_aware
        reject_partition_aware(list(group_exprs) + list(result_exprs),
                               "aggregations")
        self.mode = mode
        child_schema = child.output_schema

        self._group_bound = [bind(_strip_alias(g), child_schema)
                             for g in group_exprs]
        for g in self._group_bound:
            if isinstance(g.dtype, T.ArrayType):
                raise ValueError("cannot group by an array column")
        self._group_names = [output_name(g) for g in group_exprs]
        self._result_raw = list(result_exprs)
        self._result_bound = [bind(r, child_schema) for r in self._result_raw]

        # collect distinct aggregate functions (structural identity)
        self._aggs: list[AggregateFunction] = []
        seen: dict[str, int] = {}
        for r in self._result_bound:
            for a in _collect_aggs(r):
                key = repr(a)
                if key not in seen:
                    seen[key] = len(self._aggs)
                    self._aggs.append(a)
        self._agg_index = seen
        # holistic aggregates (percentile) have NO mergeable
        # intermediate: the whole input must reduce in one pass, so a
        # partial/final split can never be planned over them
        self._holistic = any(getattr(a, "requires_complete", False)
                             for a in self._aggs)
        if self._holistic and mode == "partial":
            raise ValueError(
                "holistic aggregates (percentile) cannot run in partial "
                "mode; plan a complete aggregation")
        if self._holistic and any(
                op.startswith(("first", "last"))
                for a in self._aggs for op in a.update_ops):
            raise NotImplementedError(
                "percentile cannot be combined with first/last in one "
                "aggregation: the percentile value-sort would change "
                "which row first/last observe")

        # pre-projection layout: [group keys..., one col per DISTINCT
        # agg input] — p50(v) and p90(v) share one projected column
        # (also what lets multiple percentiles ride one value-sort)
        self._pre_exprs: list[Expression] = list(self._group_bound)
        self._agg_input_col: list[int | None] = []
        in_seen: dict[str, int] = {}
        for a in self._aggs:
            if a.input is None:
                self._agg_input_col.append(None)
                continue
            key = repr(a.input)
            if key not in in_seen:
                in_seen[key] = len(self._pre_exprs)
                self._pre_exprs.append(a.input)
            self._agg_input_col.append(in_seen[key])
        if not self._pre_exprs:
            # rows-only aggregation (e.g. bare COUNT(*)): a zero-column
            # batch would lose its row count, so project a dummy literal
            # (reference: JustRowsColumnarBatch, SpillableColumnarBatch.scala)
            from spark_rapids_tpu.expr.core import Literal
            self._pre_exprs.append(Literal(1, T.ByteType()))
        k = len(self._group_bound)
        self._pre_schema = T.Schema(
            [T.StructField(n, g.dtype, True)
             for n, g in zip(self._group_names, self._group_bound)]
            + [T.StructField(f"_agg_in_{i}", e.dtype, True)
               for i, e in enumerate(self._pre_exprs[k:])])

        # update specs + buffer layout
        self._update_specs: list[AggSpec] = []
        self._agg_offsets: list[list[int]] = []
        buf_fields = list(self._pre_schema.fields[:k])
        for a, ci in zip(self._aggs, self._agg_input_col):
            offs = []
            for op, it in zip(a.update_ops, a.intermediate_types()):
                offs.append(k + len(self._update_specs))
                self._update_specs.append(AggSpec(
                    op, ci if ci is not None else 0,
                    param=getattr(a, "q", None)))
                buf_fields.append(T.StructField(
                    f"_buf_{len(buf_fields) - k}", it, True))
            self._agg_offsets.append(offs)
        self._buffer_schema = T.Schema(buf_fields)

        # merge specs operate over buffer columns
        self._merge_specs: list[AggSpec] = []
        for a, offs in zip(self._aggs, self._agg_offsets):
            for op, off in zip(a.merge_ops, offs):
                self._merge_specs.append(AggSpec(op, off))

        # result projection over the buffer batch
        self._final_exprs = [self._to_buffer_space(r, b)
                             for r, b in zip(self._result_raw,
                                             self._result_bound)]
        self._output_schema = (
            self._buffer_schema if mode == "partial" else T.Schema(
                [T.StructField(output_name(r), b.dtype, True)
                 for r, b in zip(self._result_raw, self._final_exprs)]))

    # ------------------------------------------------------------------
    @classmethod
    def final_from_partial(cls, partial: "HashAggregateExec",
                           child: PlanNode) -> "HashAggregateExec":
        """Build the final-mode exec consuming ``partial``'s buffer output
        (typically through an exchange)."""
        self = object.__new__(cls)
        PlanNode.__init__(self, [child])
        self.mode = "final"
        for attr in ("_group_bound", "_group_names", "_result_raw",
                     "_result_bound", "_aggs", "_agg_index", "_holistic",
                     "_pre_exprs",
                     "_agg_input_col", "_pre_schema", "_update_specs",
                     "_agg_offsets", "_buffer_schema", "_merge_specs",
                     "_final_exprs"):
            setattr(self, attr, getattr(partial, attr))
        self._output_schema = T.Schema(
            [T.StructField(output_name(r), b.dtype, True)
             for r, b in zip(self._result_raw, self._final_exprs)])
        return self

    def _to_buffer_space(self, raw: Expression, bound: Expression) -> Expression:
        """Rewrite a bound result expression to evaluate over the buffer
        batch: aggs -> final_expr(offsets), group exprs -> key refs."""
        group_reprs = {repr(g): i for i, g in enumerate(self._group_bound)}

        def rewrite(node: Expression) -> Expression:
            if isinstance(node, AggregateFunction):
                i = self._agg_index[repr(node)]
                return self._aggs[i].final_expr(self._agg_offsets[i])
            r = repr(node)
            if r in group_reprs:
                i = group_reprs[r]
                f = self._buffer_schema.fields[i]
                return BoundReference(i, f.data_type, True, f.name)
            return node

        return _rewrite_topdown(bound, rewrite)

    # ------------------------------------------------------------------
    @property
    def output_schema(self) -> T.Schema:
        return self._output_schema

    @property
    def bound_exprs(self):
        return list(self._pre_exprs) + list(self._final_exprs)

    @property
    def output_batching(self):
        return RequireSingleBatch

    @property
    def children_coalesce_goal(self):
        # batch small scan output up to batchSizeBytes before aggregating
        # (fewer, larger sorted group-by dispatches; reference: the
        # aggregate's TargetSize child goal, GpuExec.scala:71-86).
        # TargetSize(0) resolves to spark.rapids.sql.batchSizeBytes at
        # planning.  Final mode reads shuffle output that the adaptive
        # reader already coalesced.
        if self.mode == "final":
            return [None]
        return [TargetSize(0)]

    def num_partitions(self, ctx: ExecCtx) -> int:
        # complete mode is a whole-input aggregation: collapse partitions
        # (partial/final run per partition; the exchange between them owns
        # cross-partition movement, as in Spark's planner).
        if self.mode == "complete":
            return 1
        return self.children[0].num_partitions(ctx)

    @property
    def output_ordering(self):
        """Group rows leave the segment machinery clustered by the key
        columns (sorted when the update sorted; in child arrangement
        when the presorted fast path kept it) — either way, equal keys
        are contiguous per batch."""
        k = len(self._group_bound)
        if not k:
            return None
        if self.mode == "partial":
            return list(self._pre_schema.names[:k])
        key_out: dict[int, str] = {}
        for raw, fe in zip(self._result_raw, self._final_exprs):
            fe = _strip_alias(fe)
            if isinstance(fe, BoundReference) and fe.index < k:
                key_out.setdefault(fe.index, output_name(raw))
        names = []
        for i in range(k):
            if i not in key_out:
                break
            names.append(key_out[i])
        return names or None

    def _child_presorted(self) -> bool:
        """True when every group key is a plain reference to a child
        column and the child's output_ordering already clusters those
        columns (as a prefix set) — the update's re-sort is then skipped
        (VERDICT r3 item 4: agg-over-agg re-sorted the inner
        aggregation's already-clustered output at every level)."""
        k = len(self._group_bound)
        if not k or self.mode == "final":
            return False
        ordering = self.children[0].output_ordering
        if not ordering or len(ordering) < k:
            return False
        child_names = self.children[0].output_schema.names
        # keys must match the child ordering prefix IN BOUND ORDER: a
        # set-match would keep the child's (permuted) arrangement while
        # output_ordering claims bound-key order, and a downstream
        # prefix consumer would then skip a sort it still needs
        for g, have in zip(self._group_bound, ordering):
            if not isinstance(g, BoundReference) \
                    or child_names[g.index] != have:
                return False
        return len({g.index for g in self._group_bound}) == k

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        child = self.children[0]
        if self.mode == "complete":
            from spark_rapids_tpu.exec.core import drain_partitions
            child_it = drain_partitions(ctx, child)
        else:
            child_it = child.partition_iter(ctx, pid)
        key_idx = list(range(len(self._group_bound)))
        if ctx.is_device:
            yield from self._run_device(ctx, child_it, key_idx)
        else:
            yield from self._run_host(child_it, key_idx)

    # -- device path (reference aggregate.scala:427-485 concat+merge loop) --
    #
    # Compilation discipline (XLA analog of the reference's zero-per-batch-
    # compilation hot loop, SURVEY §3.3): the per-batch update and the
    # n-way merge are each ONE jitted program; buffers are shrunk to
    # pow2 group-count buckets, so programs compile once per capacity
    # bucket, and the merge runs O(total/bound) times, not per batch.
    def _jit_fns(self):
        if not hasattr(self, "_jits"):
            key_idx = list(range(len(self._group_bound)))
            presorted = self._child_presorted() and not self._holistic

            def update(b):
                # -> (buffer batch, int32[2] = group count, took the
                # sort-free branch): the pair flush_chunk fetches
                cols = [eval_device(e, b) for e in self._pre_exprs]
                pre = ColumnBatch(cols, b.num_rows, self._pre_schema)
                out, dense = group_by_update(pre, key_idx,
                                             self._update_specs,
                                             presorted=presorted)
                head = jnp.stack([out.num_rows, dense.astype(jnp.int32)])
                return _relabel_d(out, self._buffer_schema), head

            def merge(cat):
                return _relabel_d(
                    sorted_group_by(cat, key_idx, self._merge_specs),
                    self._buffer_schema)

            def final(run):
                cols = [eval_device(e, run) for e in self._final_exprs]
                return ColumnBatch(cols, run.num_rows, self._output_schema)

            import jax
            import jax.numpy as jnp
            from spark_rapids_tpu.exec import compile_cache as cc
            key = cc.fragment_key(
                "agg", presorted, len(key_idx), tuple(self._pre_exprs),
                self._pre_schema, self._update_specs, self._merge_specs,
                self._buffer_schema, tuple(self._final_exprs),
                self._output_schema)
            # single atomic publication: concurrent partition workers must
            # never observe a partially-initialized triple (the cached
            # value is the complete immutable triple)
            self._jits = cc.get_or_build(key, lambda: (
                cc.instrument(jax.jit(update), "agg_update"),
                cc.instrument(jax.jit(merge), "agg_merge"),
                cc.instrument(jax.jit(final), "agg_final")))
        return self._jits

    # pending partial buffers merge once their summed capacity crosses
    # this bound — peak concat storage stays ~2x the bound while the
    # n-way merge keeps the sort count at O(total/bound), not O(batches)
    _MERGE_PENDING_CAP = 1 << 23
    #: batches whose group counts sync to host in one stacked device_get
    _SYNC_CHUNK = 8
    #: an input batch of more slots than this is updated half by half
    #: (``ops/kernels.halve_capacity``: static slices), the halves'
    #: buffers merged like any two batches': on the chip ONE sort-branch
    #: update at 2^24 slots took 4.55 s or 5.71 s, collect by collect,
    #: where two at 2^23 and their merge take 1.27 s and repeat to 0.1 s
    #: (PERF.md Findings PR 42)
    _UPDATE_MAX_CAP = 1 << 23

    def _run_device(self, ctx: ExecCtx, child_it, key_idx) \
            -> Iterator[ColumnBatch]:
        from spark_rapids_tpu.columnar.batch import round_capacity
        update_jit, merge_jit, final_jit = self._jit_fns()

        # Each incoming batch is reduced to its own group buffer and
        # SHRUNK to its group count; buffers then merge in one n-way
        # concat + sorted group-by.  The previous pairwise loop re-sorted
        # the whole running buffer per batch — k full sorts for k
        # batches — which dominated agg-heavy plans (q65's final
        # aggregates were ~5s each on SF1).  The reference's
        # concatenate-then-merge loop amortizes the same way
        # (aggregate.scala:427-485).
        if self._holistic:
            # no merge exists for holistic aggregates: concatenate the
            # raw input ONCE and reduce it in a single group-by pass
            # (Spark's ObjectHashAggregate similarly buffers per-group
            # raw values for Percentile)
            raw = list(child_it)
            if len(raw) > 1:
                child_it = [ctx.dispatch(dk.concat_batches, raw)]
            else:
                child_it = raw
        parts: list[ColumnBatch] = []
        total_cap = 0

        def merge_pending() -> None:
            nonlocal parts, total_cap
            if len(parts) <= 1:
                return
            # the concat is the path's peak allocation: run it under
            # dispatch so the DeviceSemaphore bounds occupancy and the
            # OOM-spill-retry hook covers it (review finding)
            cat = _relabel_d(ctx.dispatch(dk.concat_batches, parts),
                             self._buffer_schema)
            merged = ctx.dispatch(merge_jit, cat)
            ng = merged.host_num_rows(_FETCH)
            cap = round_capacity(max(int(ng), 1))
            merged = ctx.dispatch(dk.shrink_capacity, merged, cap)
            merged.known_rows = int(ng)
            parts = [merged]
            total_cap = cap

        # Group-count syncs are CHUNKED: each host round trip is pure
        # latency with the device idle behind it, so up to
        # _SYNC_CHUNK updated buffers are dispatched asynchronously and
        # their counts fetched in ONE device_get of a stacked vector
        # (one barrier per chunk, not per batch).  HBM backpressure:
        # a chunk holds at most _SYNC_CHUNK un-shrunk buffers.  Each
        # chunk entry retains its SOURCE batch (parked spillable, so it
        # pins no HBM): an OOM surfacing at the stacked sync — where
        # async backends report it — is recovered by re-running the
        # updates from the sources through the splitting retry scope,
        # and the cross-batch merge makes the extra partial buffers
        # semantically free.
        import jax.numpy as _jnp
        from spark_rapids_tpu.memory.catalog import (SpillableColumnarBatch,
                                                     SpillPriority)

        # a chunk entry is (source, buffer, head): ``head`` is the device
        # value the flush fetches — the update program's [group count,
        # dense flag], or in final mode the incoming buffer's num_rows
        def update_entries(src) -> list:
            return [(piece, part, head) for piece, (part, head) in
                    ctx.dispatch_retry(update_jit, src, op="agg_update",
                                       pairs=True)]

        def flush_chunk(chunk: list) -> None:
            nonlocal total_cap
            if not chunk:
                return

            def redo() -> None:
                new = []
                for entry in chunk:
                    if entry[0] is None:  # final mode: no dispatch to redo
                        new.append(entry)
                    else:
                        new.extend(update_entries(entry[0]))
                chunk[:] = new

            def sync_heads():
                heads = [head for _s, _p, head in chunk]
                one = len(heads) == 1
                dev = heads[0] if one else ctx.dispatch(_jnp.stack, heads)
                # enginelint: disable=RL003 (one stacked transfer for the whole chunk; this IS the batched sync)
                host = fetch_to_host(dev, _FETCH)
                return [host] if one else list(host)

            heads = ctx.retry_sync(sync_heads, redo=redo, op="agg_flush")
            for (src, part, _h), head in zip(chunk, heads):
                if src is None:
                    ng = int(head)
                else:
                    ng = int(head[0])
                    get_registry().inc_many((
                        ("agg.update.dense" if head[1]
                         else "agg.update.sorted", 1),
                        ("agg.update.groups", ng)))
                part.known_rows = ng
                if isinstance(src, SpillableColumnarBatch):
                    src.close()
                if ng == 0 and key_idx:
                    continue
                cap = round_capacity(max(ng, 1))
                part = ctx.dispatch(dk.shrink_capacity, part, cap)
                part.known_rows = ng
                parts.append(part)
                total_cap += cap
                if total_cap >= self._MERGE_PENDING_CAP:
                    merge_pending()

        chunk: list = []
        for b in child_it:
            if self.mode == "final":
                chunk.append((None, _relabel_d(b, self._buffer_schema),
                              b.num_rows))
            else:
                if b.known_rows is not None:
                    get_registry().inc("agg.update.rows", b.known_rows)
                for piece in self._update_pieces(b):
                    src = SpillableColumnarBatch(piece, ctx.catalog,
                                                 SpillPriority.READ_SHUFFLE)
                    chunk.extend(update_entries(src))
            if len(chunk) >= self._SYNC_CHUNK:
                flush_chunk(chunk)
                chunk = []
        flush_chunk(chunk)
        merge_pending()
        running = parts[0] if parts else None
        if running is None:
            if key_idx or self.mode == "partial":
                return  # no groups / nothing to emit
            # grand aggregate on empty input: default-values row
            # (reference aggregate.scala reduction default path :514+)
            from spark_rapids_tpu.exec.core import host_to_device
            empty = _empty_host(self._pre_schema)
            pre = host_to_device(empty)
            running = _relabel_d(
                sorted_group_by(pre, key_idx, self._update_specs),
                self._buffer_schema)
        if self.mode == "partial":
            yield running
        else:
            # the final projection keeps the groups the flushes counted
            out = ctx.dispatch(final_jit, running)
            out.known_rows = running.known_rows
            yield out

    def _update_pieces(self, b: ColumnBatch) -> list[ColumnBatch]:
        """``b``, or its halves by slots until none has more than
        ``_UPDATE_MAX_CAP``; a half known to be empty is left out.  A
        holistic aggregate has no merge and takes its one batch whole."""
        if self._holistic or b.capacity <= self._UPDATE_MAX_CAP:
            return [b]
        return [p for half in dk.halve_capacity(b) if half.known_rows != 0
                for p in self._update_pieces(half)]

    # -- host oracle path --------------------------------------------------
    def _run_host(self, child_it, key_idx) -> Iterator[HostBatch]:
        if self._holistic:
            # single-pass reduction over the concatenated raw input
            # (no mergeable intermediate exists)
            raw = list(child_it)
            hb = hk.host_concat(raw) if len(raw) > 1 else (
                raw[0] if raw else None)
            if hb is None:
                if key_idx:
                    return
                hb = _empty_host(self.children[0].output_schema)
            cols = [eval_host(e, hb) for e in self._pre_exprs]
            pre = HostBatch(cols, self._pre_schema)
            running = _relabel_h(
                hk.host_group_by(pre, key_idx, self._update_specs),
                self._buffer_schema)
            cols = [eval_host(e, running) for e in self._final_exprs]
            yield HostBatch(cols, self._output_schema)
            return
        parts: list[HostBatch] = []
        for b in child_it:
            if self.mode == "final":
                parts.append(_relabel_h(b, self._buffer_schema))
            else:
                cols = [eval_host(e, b) for e in self._pre_exprs]
                pre = HostBatch(cols, self._pre_schema)
                parts.append(_relabel_h(
                    hk.host_group_by(pre, key_idx, self._update_specs),
                    self._buffer_schema))
        if not parts:
            if key_idx or self.mode == "partial":
                return
            parts = [_relabel_h(
                hk.host_group_by(_empty_host(self._pre_schema), key_idx,
                                 self._update_specs), self._buffer_schema)]
        running = parts[0] if len(parts) == 1 else _relabel_h(
            hk.host_group_by(hk.host_concat(parts), key_idx,
                             self._merge_specs), self._buffer_schema)
        if self.mode == "partial":
            yield running
        else:
            cols = [eval_host(e, running) for e in self._final_exprs]
            yield HostBatch(cols, self._output_schema)

    def node_desc(self) -> str:
        return (f"HashAggregateExec[{self.mode}, keys={self._group_names}, "
                f"out={self._output_schema.names}]")


# ---------------------------------------------------------------------------

def _collect_aggs(e: Expression) -> list[AggregateFunction]:
    if isinstance(e, AggregateFunction):
        return [e]
    out: list[AggregateFunction] = []
    for c in e.children:
        out.extend(_collect_aggs(c))
    return out


def _rewrite_topdown(e: Expression, fn) -> Expression:
    new = fn(e)
    if new is not e:
        return new
    children = [_rewrite_topdown(c, fn) for c in e.children]
    if all(a is b for a, b in zip(children, e.children)):
        return e
    return e.with_new_children(children)


def _relabel_d(b: ColumnBatch, schema: T.Schema) -> ColumnBatch:
    from spark_rapids_tpu.columnar.column import DeviceColumn
    cols = [DeviceColumn(c.data, c.validity, f.data_type, c.lengths)
            for c, f in zip(b.columns, schema)]
    return ColumnBatch(cols, b.num_rows, schema)


def _relabel_h(b: HostBatch, schema: T.Schema) -> HostBatch:
    from spark_rapids_tpu.host.batch import HostColumn
    cols = [HostColumn(c.data, c.validity, f.data_type)
            for c, f in zip(b.columns, schema)]
    return HostBatch(cols, schema)


def _empty_host(schema: T.Schema) -> HostBatch:
    import numpy as np
    from spark_rapids_tpu.host.batch import HostColumn
    cols = []
    for f in schema:
        if isinstance(f.data_type, T.StringType):
            data = np.empty(0, dtype=object)
        else:
            data = np.zeros(0, dtype=f.data_type.np_dtype)
        cols.append(HostColumn(data, np.zeros(0, np.bool_), f.data_type))
    return HostBatch(cols, schema)
