"""Exchange execs: shuffle (repartition) and broadcast.

Reference: GpuShuffleExchangeExecBase + ShuffledBatchRDD
(GpuShuffleExchangeExec.scala:70, SURVEY.md §2.4) and
GpuBroadcastExchangeExec (host-serialized torrent broadcast :47-368).

Execution model: an exchange is a stage barrier.  On first pull it
materializes every child partition, computes partition ids per batch on
the executing backend, splits, and caches the per-output-partition batch
lists in the ExecCtx (the analog of map-output in the
ShuffleBufferCatalog; reference RapidsCachingWriter stores partition
tables in the spillable device store).  Subsequent partition pulls serve
from the cache.  On the device backend the id+split computation is one
jitted program per batch — the local, single-process analog of the mesh
all-to-all path (exec/mesh_exec.py, which the planner selects instead of
this exec when ``spark.rapids.tpu.mesh.deviceCount`` > 1 and the shape
matches; see plan/overrides.py lower()).
"""
from __future__ import annotations

from functools import partial
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.exec.core import ExecCtx, PlanNode, fetch_to_host
from spark_rapids_tpu.exec.compile_cache import guarded_jit
from spark_rapids_tpu.exec.partitioning import Partitioning
from spark_rapids_tpu.host.batch import HostBatch
from spark_rapids_tpu.ops import host_kernels as hk
from spark_rapids_tpu.ops import kernels as dk

__all__ = ["ShuffleExchangeExec", "BroadcastExchangeExec",
           "AdaptiveShuffleReaderExec"]

from spark_rapids_tpu.conf import ConfEntry, register, _bool

ADAPTIVE_ENABLED = register(ConfEntry(
    "spark.sql.adaptive.enabled", True,
    "Adaptive execution: coalesce small shuffle output partitions using "
    "the map-output sizes (reference GpuCustomShuffleReaderExec + "
    "GpuTransitionOverrides.optimizeAdaptiveTransitions :51-94).",
    conv=_bool))
ADVISORY_PARTITION_BYTES = register(ConfEntry(
    "spark.sql.adaptive.advisoryPartitionSizeInBytes", 64 << 20,
    "Target post-shuffle partition size for adaptive coalescing.",
    conv=int))
SKEWED_PARTITION_THRESHOLD = register(ConfEntry(
    "spark.sql.adaptive.skewedPartitionThresholdInBytes", 256 << 20,
    "A shuffle output partition larger than this is skewed: the adaptive "
    "reader splits it into multiple reader groups at map-batch "
    "granularity targeting advisoryPartitionSizeInBytes each (the skew "
    "half of Spark 3.0 AQE; small partitions are coalesced, large ones "
    "split).", conv=int))


@guarded_jit("shuffle_group_by_part", static_argnames=("num_parts",))
def _jit_group_by_part(batch: ColumnBatch, ids: jax.Array, num_parts: int):
    """Sort rows by partition id; return (sorted_batch, counts[num_parts]).

    The analog of Table.contiguousSplit (GpuPartitioning.scala:45-52):
    one stable sort groups each partition's rows contiguously; the small
    counts vector is the only thing synced to host, and each partition is
    then sliced into a right-sized capacity (no num_parts x capacity
    buffer blowup).
    """
    cap = batch.capacity
    ids = jnp.where(batch.row_mask(), ids, num_parts)  # padding last
    order = jnp.argsort(ids, stable=True)
    counts = jnp.sum(ids[None, :] == jnp.arange(num_parts,
                                                dtype=jnp.int32)[:, None],
                     axis=1, dtype=jnp.int32)
    starts = jnp.cumsum(counts) - counts  # exclusive prefix, on device
    cols = dk.gather_columns(batch.columns, order, batch.num_rows)
    return ColumnBatch(cols, batch.num_rows, batch.schema), counts, starts


@guarded_jit("shuffle_slice_part", static_argnames=("out_cap",))
def _jit_slice_part(sorted_batch: ColumnBatch, starts, counts, p,
                    out_cap: int):
    """Copy partition ``p``'s rows [starts[p], starts[p]+counts[p]) into
    a fresh out_cap batch.  ``starts``/``counts`` stay device-resident
    and ``p`` is a cached device scalar: the per-partition offsets never
    round-trip to host (only the counts vector does, once per batch,
    for the static capacity choice)."""
    start = starts[p]
    idx = jnp.clip(start + jnp.arange(out_cap, dtype=jnp.int32), 0,
                   sorted_batch.capacity - 1)
    return dk.take(sorted_batch, idx, counts[p])


def _fp_extra(n: PlanNode) -> str | None:
    """Per-class fingerprint payload for operator parameters that
    node_desc/bound_exprs do not surface.  Returning None marks the
    class UNKNOWN: the node then contributes its object identity, so
    structurally-identical-looking subtrees through it never dedup —
    a missed optimization, never a wrong reuse."""
    from spark_rapids_tpu.exec.aggregate import HashAggregateExec
    from spark_rapids_tpu.exec.basic import (FilterExec, GlobalLimitExec,
                                             LocalLimitExec, ProjectExec,
                                             UnionExec)
    from spark_rapids_tpu.exec.expand import ExpandExec
    from spark_rapids_tpu.exec.generate import GenerateExec
    from spark_rapids_tpu.exec.joins import CrossJoinExec, JoinExec
    from spark_rapids_tpu.exec.sortexec import (CoalesceBatchesExec,
                                                SortExec)
    from spark_rapids_tpu.exec.transitions import BackendSwitchExec

    if isinstance(n, ShuffleExchangeExec):
        p = n.partitioning
        keys = getattr(p, "_keys", None) or getattr(p, "_orders_raw", ())
        return f"{type(p).__name__}:{p.num_partitions}:{keys!r}"
    if isinstance(n, AdaptiveShuffleReaderExec):
        return f"{n.allow_coalesce}:{n.allow_skew_split}"
    if isinstance(n, (LocalLimitExec, GlobalLimitExec)):
        return str(n._limit)
    if isinstance(n, CoalesceBatchesExec):
        return repr(n._goal)
    if isinstance(n, HashAggregateExec):
        # desc/bound_exprs/schema do NOT identify the aggregate: min(v) and
        # max(v) finals are both plain BoundReferences and partial buffer
        # schemas can coincide ('_buf_0'), so two different aggregations
        # over one shared scan would otherwise fingerprint identically and
        # ReuseExchange would serve one consumer the other's data.
        return (f"{n.mode}:{n._update_specs!r}:{n._merge_specs!r}:"
                f"{getattr(n, '_agg_offsets', None)!r}")
    if isinstance(n, BroadcastExchangeExec):
        return ""
    from spark_rapids_tpu.exec.stage_boundary import StageBoundaryExec
    if isinstance(n, (ProjectExec, FilterExec, UnionExec, JoinExec,
                      CrossJoinExec, SortExec,
                      ExpandExec, GenerateExec, BackendSwitchExec,
                      StageBoundaryExec)):
        # desc + bound_exprs + schema already carry their parameters
        return ""
    return None


def plan_fingerprint(node: PlanNode) -> str:
    """Structural identity of a physical subtree: node descriptions,
    bound expressions, output schemas, per-class parameter payloads
    (_fp_extra), and LEAF OBJECT identity (two subtrees match only when
    they read the very same source execs).  Operators outside the known
    set contribute object identity too, so unknown semantics can never
    collide.  Identical fingerprints mean identical map output — the
    basis for exchange reuse (Spark's ReuseExchange rule, which the
    reference inherits: a DataFrame referenced twice otherwise executes
    its whole shuffle pipeline twice — q65's agg-over-agg self-join ran
    the store_sales scan+join+partial-agg twice)."""
    import hashlib
    h = hashlib.sha1()

    def feed(n: PlanNode):
        h.update(type(n).__name__.encode())
        h.update(n.node_desc().encode())
        h.update(repr(n.output_schema).encode())
        for e in getattr(n, "bound_exprs", []):
            h.update(repr(e).encode())
        extra = _fp_extra(n)
        if extra is None or not n.children:
            h.update(str(id(n)).encode())
        else:
            h.update(extra.encode())
        for c in n.children:
            feed(c)

    feed(node)
    return h.hexdigest()


class ShuffleExchangeExec(PlanNode):
    """Repartition child output by a Partitioning strategy."""

    def __init__(self, partitioning: Partitioning, child: PlanNode,
                 shuffle_id: "int | str | None" = None):
        super().__init__([child])
        self.partitioning = partitioning
        partitioning.bind(child.output_schema)
        # explicit id: cross-process serving (two processes cannot
        # agree on a local identity); otherwise resolved lazily to the
        # subtree fingerprint at first execution (children are still
        # being rewritten by coalesce/transition insertion now)
        self._shuffle_id = shuffle_id

    @property
    def shuffle_id(self):
        if self._shuffle_id is None:
            self._shuffle_id = plan_fingerprint(self)
        return self._shuffle_id

    @property
    def output_schema(self) -> T.Schema:
        return self.children[0].output_schema

    def num_partitions(self, ctx: ExecCtx) -> int:
        return self.partitioning.num_partitions

    def _shuffled(self, ctx: ExecCtx):
        # keyed by the structural shuffle_id, NOT object identity:
        # duplicate exchange subtrees (a DataFrame used twice in one
        # query) materialize the map side ONCE per execution and both
        # consumers fetch from it (ReuseExchange)
        return ctx.cached(("shuffle", self.shuffle_id, ctx.backend),
                          lambda: self._do_shuffle(ctx))

    def _do_shuffle(self, ctx: ExecCtx):
        """Materialize the map side through the shuffle transport SPI
        (reference RapidsCachingWriter.write storing spillable partition
        tables, RapidsShuffleInternalManager.scala:90-155; transport
        loaded by reflection, RapidsShuffleTransport.scala:638-658).
        Host backend keeps plain batch lists (the oracle path).

        The device path also registers a ShuffleLineage handle in the
        ExecCtx: which child partition produced each map batch, and
        whether the tiny-input coalesce rewrite applied — everything
        needed to re-execute exactly the lost map partitions after a
        terminal fetch failure (exec/recovery.py; reference:
        MapOutputTracker lineage driving DAGScheduler stage
        resubmission)."""
        from spark_rapids_tpu.exec.core import drain_partitions
        child = self.children[0]
        if ctx.is_device:
            with ctx.trace_span("stage.map", "stage",
                                shuffle=str(self.shuffle_id)[:12],
                                node=self.node_desc()):
                return self._do_shuffle_device(ctx, child)
        batches = list(drain_partitions(ctx, child))
        self.partitioning.prepare(batches, False)
        n = self.partitioning.num_partitions
        out: list[list] = [[] for _ in range(n)]
        for bi, b in enumerate(batches):
            if b.num_rows == 0:
                continue
            ids = self.partitioning.host_ids(b, bi)
            for p in range(n):
                piece = hk.host_filter(b, ids == p)
                if piece.num_rows:
                    out[p].append(piece)
        return out

    def _do_shuffle_device(self, ctx: ExecCtx, child: PlanNode):
        from spark_rapids_tpu.exec.core import drain_partitions_indexed
        from spark_rapids_tpu.exec.recovery import ShuffleLineage
        from spark_rapids_tpu.shuffle import make_transport
        cluster = ctx.cache.get("cluster")
        if cluster is not None and getattr(self, "_cluster_ok", False):
            # cluster runtime: shard the map side over the worker pool
            # (cluster/exec.py); None means it could not run there
            # (unpicklable fragment, dead pool) and the classic
            # in-process path below stays the fallback
            from spark_rapids_tpu.cluster.exec import cluster_do_shuffle
            out = cluster_do_shuffle(cluster, self, ctx, child)
            if out is not None:
                return out
        indexed = list(drain_partitions_indexed(ctx, child))
        map_src = {bi: cpid for bi, (cpid, _) in enumerate(indexed)}
        batches = [b for _, b in indexed]
        self.partitioning.prepare(batches, True)
        n = self.partitioning.num_partitions
        transport = make_transport(ctx.conf, ctx)
        # Map-side tiny-input coalescing: when the whole map side is
        # below the advisory partition size, splitting it n ways
        # only buys n slice programs + n downstream per-partition
        # chains of dispatch latency.  Putting EVERYTHING in
        # partition 0 is correct for every partitioning (all rows of
        # any key land in one partition) — the map-side counterpart
        # of the reader's AQE small-partition coalescing
        # (GpuCustomShuffleReaderExec; Spark's AQE does this on the
        # read side only because its map side is fixed at plan time).
        # It is an ADAPTIVE rewrite, so it obeys the same gates as
        # the read side: off when spark.sql.adaptive.enabled is
        # false, and off when an allow_coalesce=False reader
        # consumes this exchange — explicit repartition(n) promises
        # n non-degenerate partitions (Spark's REPARTITION_BY_NUM
        # contract).
        coalesce_ok = (ADAPTIVE_ENABLED.get(ctx.conf.settings)
                       and not getattr(self, "_no_map_coalesce",
                                       False))
        coalesced = False
        if coalesce_ok and n > 1 and len(batches) >= 1:
            total_bytes = sum(b.device_size_bytes() for b in batches)
            coalesced = total_bytes <= ADVISORY_PARTITION_BYTES.get(
                ctx.conf.settings)
        for bi, b in enumerate(batches):
            self._write_map_batch(ctx, transport, bi, b, coalesced, n)
        ctx.register_lineage(self.shuffle_id, ShuffleLineage(
            exchange=self, coalesced=coalesced, num_parts=n,
            map_src=map_src, conf_fp=getattr(self, "_conf_fp", None)))
        return transport

    def _write_map_batch(self, ctx: ExecCtx, transport, bi: int, b,
                         coalesced: bool, n: int,
                         epoch: int | None = None) -> None:
        """Partition one map batch and hand its pieces to the transport.
        Shared by the initial materialization (epoch=None -> current) and
        recovery recomputation, which tags writes with the post-
        invalidation epoch so a straggler from the dead attempt can
        never displace them."""
        from spark_rapids_tpu.columnar.batch import round_capacity
        if coalesced:
            transport.write_partition(self.shuffle_id, bi, 0, b,
                                      epoch=epoch)
            return
        ids = self.partitioning.device_ids(b, bi)
        sb, counts_d, starts_d = ctx.dispatch(_jit_group_by_part, b, ids, n)
        # enginelint: disable=RL003 (per-partition counts gate host-side slicing; one sync per batch by design)
        counts = np.asarray(fetch_to_host(counts_d,
                                          "fetch@ShuffleExchangeExec"))
        for p in range(n):
            if counts[p] == 0:
                continue
            piece = ctx.dispatch(
                _jit_slice_part, sb, starts_d, counts_d,
                dk.device_scalar(p), round_capacity(int(counts[p])))
            # counts already crossed to host for the skip check above:
            # record the exact row count on the piece so downstream
            # numOutputRows never needs a fresh D2H sync (jit dispatch
            # strips known_rows at the trace boundary)
            piece.known_rows = int(counts[p])
            ctx.trace_event("shuffle.map_write", "shuffle", map=bi,
                            part=p, rows=int(counts[p]),
                            epoch=epoch if epoch is not None else 0)
            transport.write_partition(self.shuffle_id, bi, p, piece,
                                      epoch=epoch)

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        yield from self.partition_iter_slice(ctx, pid, 0, None)

    def partition_iter_slice(self, ctx: ExecCtx, pid: int, lo: int,
                             hi: int | None) -> Iterator:
        """One reduce partition's batches, restricted to map-batch slice
        [lo, hi) — each adaptive skew-split group materializes only its
        own range.  Device pulls run inside the stage-recovery loop:
        a terminal MapOutputLostError invalidates and recomputes exactly
        the lost map outputs, then resumes the pull where it stopped."""
        shuffled = self._shuffled(ctx)
        if ctx.is_device:
            from spark_rapids_tpu.exec.recovery import recovering_fetch
            with ctx.trace_span("shuffle.fetch", "shuffle",
                                shuffle=str(self.shuffle_id)[:12],
                                partition=pid, lo=lo,
                                hi=hi if hi is not None else -1):
                yield from recovering_fetch(ctx, self, shuffled, pid,
                                            lo, hi)
        else:
            yield from shuffled[pid][lo:hi]

    def node_desc(self) -> str:
        return (f"ShuffleExchangeExec[{type(self.partitioning).__name__}"
                f"({self.partitioning.num_partitions})]")


class AdaptiveShuffleReaderExec(PlanNode):
    """Adaptive shuffle reader: re-plans the reduce side from ACTUAL
    map-output sizes (the AQE analog; reference
    GpuCustomShuffleReaderExec.scala:131 reading CoalescedPartitionSpecs,
    plus Spark 3.0's skew-reader split).

    * adjacent partitions smaller than advisoryPartitionSizeInBytes are
      coalesced into one reader group;
    * a partition larger than skewedPartitionThresholdInBytes is SPLIT
      into several groups at map-batch granularity, each targeting the
      advisory size, so one hot key range cannot serialize the stage.

    The shuffle is its query-stage barrier: grouping is decided AFTER the
    map side materializes, per execution.  Each group is a list of
    ``(child_pid, lo, hi)`` map-batch slices (hi=None -> to the end).

    ``allow_skew_split`` is only set by the planner where the consumer
    has per-row semantics (join sides, writes): splitting one hash
    partition into several reader groups between a partial and a final
    aggregation would emit duplicate keys, so that path keeps
    coalesce-only (Spark scopes its skew reader to joins the same way,
    OptimizeSkewedJoin).  ``allow_coalesce=False`` makes the reader
    split-only: user-requested partition counts are never REDUCED
    (Spark's REPARTITION_BY_NUM contract), but a skewed partition may
    still fan out.
    """

    def __init__(self, child: ShuffleExchangeExec,
                 allow_skew_split: bool = False,
                 allow_coalesce: bool = True):
        super().__init__([child])
        assert isinstance(child, ShuffleExchangeExec)
        self.allow_skew_split = allow_skew_split
        self.allow_coalesce = allow_coalesce
        if not allow_coalesce:
            # the exchange materializes before its consumers run, so it
            # cannot discover this reader then — flag it at plan time:
            # the map side must keep all n partitions non-degenerate
            child._no_map_coalesce = True

    @property
    def output_schema(self) -> T.Schema:
        return self.children[0].output_schema

    def _groups(self, ctx: ExecCtx) -> list[list[tuple]]:
        return ctx.cached(("aqe_groups", id(self), ctx.backend),
                          lambda: self._compute_groups(ctx))

    def _compute_groups(self, ctx: ExecCtx) -> list[list[tuple]]:
        child = self.children[0]
        n = child.num_partitions(ctx)
        identity = [[(pid, 0, None)] for pid in range(n)]
        # transition insertion may have wrapped the shuffle (backend
        # switch); without direct access to map-output stats, do NOT
        # coalesce — unknown sizes must not serialize the reduce side
        if not ctx.is_device or not isinstance(child, ShuffleExchangeExec):
            return identity
        shuffled = child._shuffled(ctx)  # stage barrier: materialize maps
        target = ctx.conf.get(ADVISORY_PARTITION_BYTES)
        skew_at = ctx.conf.get(SKEWED_PARTITION_THRESHOLD)
        sizes = shuffled.partition_sizes(child.shuffle_id) \
            if hasattr(shuffled, "partition_sizes") else None
        if not sizes:
            return identity
        groups: list[list[tuple]] = []
        cur: list[tuple] = []
        cur_bytes = 0
        n_splits = 0

        def flush():
            nonlocal cur, cur_bytes
            if cur:
                groups.append(cur)
            cur, cur_bytes = [], 0

        for pid in range(n):
            sz = sizes.get(pid, 0)
            per_batch = shuffled.batch_sizes(child.shuffle_id, pid) \
                if (self.allow_skew_split and sz > skew_at
                    and hasattr(shuffled, "batch_sizes")) else None
            if per_batch and len(per_batch) > 1:
                flush()
                before = len(groups)
                lo, acc = 0, 0
                for i, bsz in enumerate(per_batch):
                    if acc > 0 and acc + bsz > target:
                        groups.append([(pid, lo, i)])
                        lo, acc = i, 0
                    acc += bsz
                groups.append([(pid, lo, None)])
                n_splits += len(groups) - before - 1
                continue
            if not self.allow_coalesce:
                groups.append([(pid, 0, None)])
                continue
            if cur and cur_bytes + sz > target:
                flush()
            cur.append((pid, 0, None))
            cur_bytes += sz
        flush()
        if not groups:
            return identity
        n_coalesced = sum(len(g) - 1 for g in groups)
        if n_coalesced or n_splits:
            from spark_rapids_tpu.obs.registry import get_registry
            reg = get_registry()
            if n_coalesced:
                reg.inc("aqe_partitions_coalesced", n_coalesced)
            if n_splits:
                reg.inc("aqe_skew_splits", n_splits)
            ctx.trace_event("aqe.replan", "aqe", node=self.node_desc(),
                            partitions=n, groups=len(groups),
                            coalesced=n_coalesced, skew_splits=n_splits)
        return groups

    def num_partitions(self, ctx: ExecCtx) -> int:
        return len(self._groups(ctx))

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        for child_pid, lo, hi in self._groups(ctx)[pid]:
            yield from self.children[0].partition_iter_slice(
                ctx, child_pid, lo, hi)

    def node_desc(self) -> str:
        return "AdaptiveShuffleReaderExec" + (
            "[skew-split]" if self.allow_skew_split else "")


class BroadcastExchangeExec(PlanNode):
    """Materialize the child once; every consumer partition sees the
    full (concatenated) output (reference GpuBroadcastExchangeExec:
    collect to host, torrent-broadcast, lazy device rebuild — here the
    single-process analog caches one batch per backend)."""

    def __init__(self, child: PlanNode):
        super().__init__([child])

    @property
    def output_schema(self) -> T.Schema:
        return self.children[0].output_schema

    def num_partitions(self, ctx: ExecCtx) -> int:
        return 1

    def materialize(self, ctx: ExecCtx):
        return ctx.cached(("broadcast", id(self), ctx.backend),
                          lambda: self._materialize(ctx))

    def _materialize(self, ctx: ExecCtx):
        from spark_rapids_tpu.exec.core import drain_partitions
        child = self.children[0]
        batches = list(drain_partitions(ctx, child))
        if ctx.is_device:
            if not batches:
                from spark_rapids_tpu.exec.core import host_to_device
                b = host_to_device(HostBatch.empty(child.output_schema))
            else:
                b = dk.concat_batches(batches) if len(batches) > 1 \
                    else batches[0]
        else:
            b = hk.host_concat(batches) if batches \
                else HostBatch.empty(child.output_schema)
        return b

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        yield self.materialize(ctx)

    def node_desc(self) -> str:
        return "BroadcastExchangeExec"
