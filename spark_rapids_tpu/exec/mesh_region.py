"""Mesh regions: whole pipelines as ONE per-device program, plus the
mesh-distributed sort and window.

A mesh *island* (exec/mesh_exec.py) runs one collective operator per
``shard_map`` program: the planner shards the operator's input, runs the
program, and splits the output back into per-device batches; what sits
between two islands runs per batch, per device.

A mesh *region* extends the island downward: the contiguous elementwise
pipeline feeding a collective operator (filter / project / fused stage —
the same absorbable set as whole-stage fusion, exec/fused.py) is spliced
INTO the per-device program, so batches are sharded once at the region's
leaves, flow shard-resident through the member pipeline and the
collective, and cross the device boundary only at the region's output —
one compiled executable per (pipeline, collective, mesh shape).  Island
and region launch through the same :class:`MeshLauncher`
(exec/mesh_exec.py): a bare terminal is a program of zero segments.

What the chip said of the two (``tpcds-sf1-mesh4.q6``, four v5e chips):
regions on, the default, 2.47 s a collect (ledger, PR 44); regions off,
every join an island, 0.64 s (PERF.md §6, PR 44).  An island join is the
one-chip JoinExec per device and gathers at the size it found; an
absorbed join runs at the static capacity of its input.  The hop a
region removes costs less than the padding it carries wherever joins
shrink their input (ROADMAP D14).

:class:`MeshSortExec` completes the operator set: a global sort (or
TopN) as a broadcast sort inside ``shard_map`` — all-gather the shard
rows over ICI, sort the gathered batch per device, and keep each
device's contiguous slice of the total order (reference: GpuSortExec's
total-order contract; the reference reaches distributed order via a
range exchange + per-partition sort, here the gather IS the exchange).
Device order equals global order, so a downstream limit or collect
reads partitions in order with zero cross-device traffic; with
``limit=n`` only device 0 keeps the first n rows (TopN), which a
``GlobalLimitExec`` above passes through untouched.
"""
from __future__ import annotations

from typing import Iterator, Sequence

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch, round_capacity
from spark_rapids_tpu.exec.core import ExecCtx, PlanNode
from spark_rapids_tpu.exec.fused import FusedStageExec
from spark_rapids_tpu.exec.mesh_exec import (MeshExchangeExec, MeshJoinExec,
                                             MeshLauncher, _MeshOutputMixin,
                                             _MeshTerminal,
                                             all_gather_batch,
                                             all_gather_rows, order_slice,
                                             with_key_columns)
from spark_rapids_tpu.exec.sortexec import SortExec
from spark_rapids_tpu.exec.window import WindowExec, _window_body
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.ops.kernels import gather_columns
from spark_rapids_tpu.ops.sort import sort_permutation
from spark_rapids_tpu.parallel.mesh_shuffle import (exchange_local,
                                                    partition_ids_for_keys)

__all__ = ["MeshSortExec", "MeshWindowExec", "MeshRegionExec"]


class MeshSortExec(_MeshTerminal, PlanNode):
    """Global sort / TopN over the mesh as one broadcast-sort program.

    Per-device body: all-gather every shard's rows and counts, build the
    segment-aware real-row mask (gathered segments are packed per shard,
    not globally), run ONE stable multi-operand sort whose leading
    padding-last flag simultaneously front-packs and orders, then keep
    this device's slice of the total order — device i holds rows
    [i*base + min(i, rem), ...), so partition order IS global order.
    With ``limit`` device 0 keeps the first ``limit`` rows and every
    other shard is empty.

    Broadcast cost: every device holds all P*cap gathered rows during
    the sort.  That is the TopN/ORDER-BY-tail shape TPC-H exercises
    (q2/q3/q10: small post-aggregation row sets); a terabyte-scale sort
    wants the range-exchange plan the in-process path already has.
    """

    _program_name = "mesh_sort"
    _fault_op = "meshsort"

    def __init__(self, orders: Sequence, child: PlanNode, mesh_size: int,
                 limit: int | None = None, axis_name: str = "data"):
        from spark_rapids_tpu.exec.sortexec import resolve_orders
        super().__init__([child])
        self._orders = resolve_orders(orders, child.output_schema)
        self.mesh_size = mesh_size
        self.limit = limit
        self.axis_name = axis_name
        self._launcher = MeshLauncher(self)

    @property
    def output_schema(self) -> T.Schema:
        return self.children[0].output_schema

    @property
    def output_ordering(self):
        return [self.output_schema.names[o.child_index]
                for o in self._orders]

    def num_partitions(self, ctx: ExecCtx) -> int:
        return self.mesh_size if ctx.is_device else 1

    # -- fallback ------------------------------------------------------
    def _single_exec(self) -> SortExec:
        # built lazily so tree-rewrite passes that replace the child are
        # picked up; the limit (if any) is enforced by the
        # GlobalLimitExec the planner keeps above this node
        return SortExec(list(self._orders), self.children[0],
                        global_sort=True)

    # -- distributed program -------------------------------------------
    def _local_step(self):
        """Per-device body (local view in, local view out) — the unit a
        MeshRegionExec splices into its shard_map program."""
        p = self.mesh_size
        axis = self.axis_name
        orders = self._orders
        limit = self.limit
        schema = self.children[0].output_schema

        def step(b: ColumnBatch) -> ColumnBatch:
            cap = b.capacity
            gcap = p * cap
            cols, counts, real = all_gather_rows(b, p, axis)
            total = jnp.sum(counts, dtype=jnp.int32)
            gb = ColumnBatch(cols, total, schema)
            perm = sort_permutation(gb, orders, real=real)
            if limit is None:
                # contiguous slice of the total order per device; each
                # count is <= cap because total <= p*cap
                start, cnt = order_slice(total, p, axis)
                out_cap = cap
            else:
                out_cap = round_capacity(max(1, min(limit, gcap)))
                start = jnp.int32(0)
                cnt = jnp.where(jax.lax.axis_index(axis) == 0,
                                jnp.minimum(jnp.int32(limit), total),
                                jnp.int32(0))
            pick = jnp.clip(start + jnp.arange(out_cap, dtype=jnp.int32),
                            0, gcap - 1)
            out_cols = gather_columns(gb.columns, perm[pick], cnt)
            return ColumnBatch(out_cols, cnt, schema)

        return step

    def _step_key_parts(self) -> tuple:
        return ("mesh_sort", tuple(self._orders),
                self.children[0].output_schema, self.limit, self.mesh_size)

    def _outputs_cache_key(self, ctx: ExecCtx) -> tuple:
        return ("meshsort", id(self), ctx.backend)

    def _fallback_outputs(self, ctx: ExecCtx):
        """Single-device recompute from lineage: the in-process global
        sort over the same child — also the degenerate path when the
        child produced nothing."""
        out = [list(self._single_exec().partition_iter(ctx, 0))]
        out += [[] for _ in range(self.mesh_size - 1)]
        return out

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        if not ctx.is_device:
            yield from self._single_exec().partition_iter(ctx, pid)
            return
        yield from self._aligned(iter(self._outputs(ctx)[pid]))

    def node_desc(self) -> str:
        lim = f", limit={self.limit}" if self.limit is not None else ""
        return f"MeshSortExec[mesh={self.mesh_size}, {self._orders}{lim}]"


class MeshWindowExec(_MeshTerminal, WindowExec):
    """Window functions distributed over the mesh, by spec shape:

    - **partitioned** (PARTITION BY present): rows hash-exchange on the
      partition keys in-program — Spark-bit-exact murmur3, the same ids
      a planner-inserted exchange would compute — so whole peer groups
      land on one device, then every device runs the columnar window
      kernel (``_window_body``) over its shard.  The reference shape is
      GpuWindowExec downstream of a hash partitioning on the window
      keys; here the exchange and the kernel are ONE program.
    - **global ordered** (no PARTITION BY, ORDER BY present): the frame
      spans the whole input, so every device all-gathers the rows,
      evaluates the global window, and keeps its contiguous slice of
      the ordered output — the MeshSortExec total-order machinery (the
      window body already sorts by the order keys).

    Unpartitioned AND unordered windows keep the in-process path (the
    bounded-memory `_stream_global` two-pass stream beats gathering).
    """

    _program_name = "mesh_window"
    _fault_op = "meshwindow"

    def __init__(self, window_exprs: Sequence, child: PlanNode,
                 mesh_size: int, axis_name: str = "data"):
        WindowExec.__init__(self, window_exprs, child,
                            keys_partitioned=False)
        self.mesh_size = mesh_size
        self.axis_name = axis_name
        self._launcher = MeshLauncher(self)

    @property
    def output_batching(self):
        # mesh output is one batch per device shard, not one per
        # partition group — never advertise the single-batch guarantee
        return None

    def num_partitions(self, ctx: ExecCtx) -> int:
        return self.mesh_size if ctx.is_device else 1

    # -- distributed program -------------------------------------------
    def _window_local(self, b: ColumnBatch) -> ColumnBatch:
        aug, orders, part_idx, order_idx, input_idx, nbase = \
            self._window_args(b)
        return _window_body(aug, orders, part_idx, order_idx, input_idx,
                            self._fns, nbase, self._schema)

    def _local_step(self):
        """Per-device body (local view in, local view out) — the unit a
        MeshRegionExec splices into its shard_map program."""
        p = self.mesh_size
        axis = self.axis_name
        part_b = self._part_b

        if part_b:
            def step(b: ColumnBatch) -> ColumnBatch:
                # route on the evaluated partition keys; the keys are
                # recomputed from the shipped raw columns after the
                # exchange (_window_args), so only the input schema
                # travels — no augmented columns on the wire
                aug, kidx = with_key_columns(b, part_b)
                pid = partition_ids_for_keys(aug, kidx, p)
                ex = exchange_local(b, pid, p, axis)
                return self._window_local(ex)
            return step

        def step(b: ColumnBatch) -> ColumnBatch:
            # global frame: gather, evaluate everywhere, keep this
            # device's contiguous slice of the ordered output
            cap = b.capacity
            gb = all_gather_batch(b, p, axis)
            out = self._window_local(gb)
            start, cnt = order_slice(out.num_rows, p, axis)
            pick = jnp.clip(start + jnp.arange(cap, dtype=jnp.int32),
                            0, p * cap - 1)
            out_cols = gather_columns(out.columns, pick, cnt)
            return ColumnBatch(out_cols, cnt, self._schema)
        return step

    def _step_key_parts(self) -> tuple:
        return ("mesh_window", tuple(self._wexprs), tuple(self._part_b),
                tuple((e, asc, nf) for e, asc, nf in self._order_b),
                tuple(self._fn_inputs),
                self.children[0].output_schema, self._schema,
                self.mesh_size)

    def _outputs_cache_key(self, ctx: ExecCtx) -> tuple:
        return ("meshwin", id(self), ctx.backend)

    def _fallback_outputs(self, ctx: ExecCtx):
        """Single-device recompute from lineage: the in-process window
        over the same child — also the degenerate path when the
        child produced nothing."""
        out = [list(WindowExec.partition_iter(self, ctx, 0))]
        out += [[] for _ in range(self.mesh_size - 1)]
        return out

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        if not ctx.is_device:
            yield from WindowExec.partition_iter(self, ctx, pid)
            return
        yield from self._aligned(iter(self._outputs(ctx)[pid]))

    def node_desc(self) -> str:
        mode = "partitioned" if self._part_b else "global"
        return (f"MeshWindowExec[mesh={self.mesh_size}, {mode}, "
                f"{self._names}]")


class MeshRegionExec(_MeshOutputMixin, PlanNode):
    """A contiguous pipeline + its terminal collective operator,
    compiled into ONE per-device ``shard_map`` program.

    ``members`` is innermost-first (members[0] consumes the region
    input); ``terminal`` is a MeshAggregateExec, MeshExchangeExec,
    MeshSortExec, or MeshWindowExec whose child is members[-1].
    Members are elementwise ops (filter / project / fused stage) plus
    the collective interiors: a :class:`MeshJoinExec` (in replicated mode
    its body probes a build prepared once outside the program and handed
    in replicated, by the one-chip executor's probe selection and gather
    plan; in partitioned mode both key exchanges run as in-program
    all-to-alls) and a
    :class:`MeshWindowExec` (in-program hash exchange or gather+slice),
    so a region can hold scan→filter→join→project→agg as one program
    per mesh shape.  Like FusedStageExec, every member and the terminal
    keep their ORIGINAL child links, so schema / ordering delegation
    and — critically — lineage-based recovery walk the unfused chain:
    on a lost mesh slice the terminal's own single-device fallback
    re-executes the members as ordinary per-batch operators (a join
    member recomputes BOTH its sides from lineage).

    The region's children are the pipeline leaf plus one build-side
    subtree per absorbed join — those stay real plan edges: they are
    drained on the host side (the replicated/partitioned mode pick
    needs the materialized size) and handed to the program as extra
    inputs: a replicated join's prepared build whole on every device
    (``MeshJoinExec._region_build``), a partitioned join's raw batches
    stacked onto the mesh.

    Execution primes the terminal's per-execution output cache — by the
    :class:`MeshLauncher` a bare terminal launches through, given the
    region's segments — and then delegates ``partition_iter`` to the
    terminal, so its partition serving (exchange partition slicing,
    alignment, shrink) is reused unchanged.  When the leaf is itself a
    mesh exchange — bare or a chained region's exchange terminal — the
    upstream output shards stay committed one-per-device and are stacked
    in place (``_chained_shards``): no gather, no host hop between
    regions.
    """

    combines_batches = True

    def __init__(self, terminal: PlanNode, members: Sequence[PlanNode]):
        assert members, "a region needs at least one absorbed member"
        self._terminal = terminal
        self._members = tuple(members)
        # elementary ops with fused stages unpacked, joins/windows kept
        # in place: the region body and key compose per element
        flat = []
        for m in self._members:
            if isinstance(m, FusedStageExec):
                flat.extend(m.fused_ops)
            else:
                flat.append(m)
        self._flat = tuple(flat)
        # segment the flat pipeline: maximal elementwise runs lower via
        # stage_body; each join/window is its own collective segment
        segs: list[tuple] = []
        run: list = []
        for op in flat:
            if isinstance(op, (MeshJoinExec, MeshWindowExec)):
                if run:
                    segs.append(("stage", tuple(run)))
                    run = []
                segs.append(("join" if isinstance(op, MeshJoinExec)
                             else "window", op))
            else:
                run.append(op)
        if run:
            segs.append(("stage", tuple(run)))
        # builds, launches, retries and recovers the region's program:
        # the launcher a bare terminal holds, given segments
        self._launcher = MeshLauncher(terminal, self, segs)
        super().__init__([members[0].children[0]]
                         + [j.children[1] for j in self._launcher._joins])
        self.mesh_size = terminal.mesh_size
        self.axis_name = terminal.axis_name
        # the member chain is the terminal's recovery lineage: after a
        # lost slice the fallback replays it per batch, so a fused
        # member must not have donated (deleted) its input buffers
        for m in self._members:
            if isinstance(m, FusedStageExec):
                m.donate_ok = False

    @property
    def output_schema(self) -> T.Schema:
        return self._terminal.output_schema

    @property
    def output_ordering(self):
        return self._terminal.output_ordering

    def num_partitions(self, ctx: ExecCtx) -> int:
        return self._terminal.num_partitions(ctx)

    @property
    def region_ops(self) -> tuple:
        return self._flat + (self._terminal,)

    # -- execution -----------------------------------------------------
    def _ensure(self, ctx: ExecCtx) -> None:
        """Prime the terminal's per-execution outputs with the region
        program's, once."""
        ctx.cached(self._terminal._outputs_cache_key(ctx),
                   lambda: self._launcher.run(ctx, self._chained_shards(ctx)))

    def _chained_shards(self, ctx: ExecCtx):
        """Region chaining: when the leaf IS a mesh exchange — bare, or
        an upstream region's exchange terminal — on the same mesh, its
        output shards are already committed one-per-device; consume
        them in place instead of slicing partitions out, shrinking,
        and re-sharding.  Returns None when the upstream degraded to
        host partitions (its fallback path) or the meshes differ — the
        caller then drains partitions normally."""
        leaf = self.children[0]
        if isinstance(leaf, MeshExchangeExec):
            up = leaf
        elif isinstance(leaf, MeshRegionExec) \
                and isinstance(leaf._terminal, MeshExchangeExec):
            leaf._ensure(ctx)
            up = leaf._terminal
        else:
            return None
        if up.mesh_size != self.mesh_size \
                or up.axis_name != self.axis_name:
            return None
        kind, out = up._outputs(ctx)
        if kind != "mesh":
            return None
        get_registry().inc("mesh_region_chains")
        return list(out)

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        if not ctx.is_device:
            # host backend: the terminal's host path walks the original
            # member chain as ordinary per-batch operators
            yield from self._terminal.partition_iter(ctx, pid)
            return
        self._ensure(ctx)
        yield from self._aligned(self._terminal.partition_iter(ctx, pid))

    def node_desc(self) -> str:
        inner = " -> ".join([op.node_desc() for op in self._members]
                            + [self._terminal.node_desc()])
        return (f"MeshRegionExec[mesh={self.mesh_size}, "
                f"{len(self._flat) + 1} ops: {inner}]")
