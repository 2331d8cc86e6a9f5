"""Mesh regions: whole pipelines as ONE per-device program, plus the
mesh-distributed sort.

A mesh *island* (exec/mesh_exec.py) runs one collective operator per
``shard_map`` program: the planner shards the operator's input, runs the
program, and splits the output back into per-device batches.  Between
two islands every batch used to take a host/device-0 round trip — the
exact gather the pod-scale plan shape must avoid.

A mesh *region* extends the island downward: the contiguous elementwise
pipeline feeding a collective operator (filter / project / fused stage —
the same absorbable set as whole-stage fusion, exec/fused.py) is spliced
INTO the per-device program, so batches are sharded once at the region's
leaves, flow shard-resident through the member pipeline and the
collective, and cross the device boundary only at the region's output —
one compiled executable per (pipeline, collective, mesh shape).

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

import time
from typing import Iterator, Sequence

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch, round_capacity
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.exec.core import ExecCtx, PlanNode, fetch_to_host
from spark_rapids_tpu.exec.fused import (FusedStageExec, filters_merged,
                                         has_filter, stage_body,
                                         stage_key_parts)
from spark_rapids_tpu.exec.mesh_exec import (MeshAggregateExec,
                                             MeshExchangeExec,
                                             MeshJoinExec,
                                             _MeshOutputMixin,
                                             _check_slice_fault,
                                             _note_a2a_bytes,
                                             _note_slice_recovery,
                                             _reraise_unless_slice_lost,
                                             all_gather_batch,
                                             concat_or_empty, drain_cached,
                                             mesh_for, place_shards)
from spark_rapids_tpu.exec.sortexec import SortExec
from spark_rapids_tpu.exec.window import WindowExec, _window_body
from spark_rapids_tpu.expr.core import eval_device
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.ops import kernels as dk
from spark_rapids_tpu.ops.kernels import gather_columns
from spark_rapids_tpu.ops.sort import sort_permutation
from spark_rapids_tpu.parallel.mesh import (local_view, restack,
                                            shard_batches, shard_map,
                                            split_shards)
from spark_rapids_tpu.parallel.mesh_shuffle import (exchange_local,
                                                    partition_ids_for_keys)

__all__ = ["MeshSortExec", "MeshWindowExec", "MeshRegionExec"]


class MeshSortExec(_MeshOutputMixin, PlanNode):
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

    def __init__(self, orders: Sequence, child: PlanNode, mesh_size: int,
                 limit: int | None = None, axis_name: str = "data"):
        from spark_rapids_tpu.exec.sortexec import resolve_orders
        super().__init__([child])
        self._orders = resolve_orders(orders, child.output_schema)
        self.mesh_size = mesh_size
        self.limit = limit
        self.axis_name = axis_name
        self._jitted = {}

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
            counts = jax.lax.all_gather(b.num_rows, axis)  # int32[P]
            cols = []
            for c in b.columns:
                data = jax.lax.all_gather(c.data, axis, tiled=True)
                val = jax.lax.all_gather(c.validity, axis, tiled=True)
                if c.is_string:
                    ln = jax.lax.all_gather(c.lengths, axis, tiled=True)
                    cols.append(DeviceColumn(data, val, c.dtype, ln))
                else:
                    cols.append(DeviceColumn(data, val, c.dtype))
            gcap = p * cap
            idx = jnp.arange(gcap, dtype=jnp.int32)
            # segment-aware real mask: rows are packed per gathered
            # shard segment, not globally
            real = (idx % cap) < counts[idx // cap]
            total = jnp.sum(counts, dtype=jnp.int32)
            gb = ColumnBatch(cols, total, schema)
            perm = sort_permutation(gb, orders, real=real)
            i = jax.lax.axis_index(axis)
            if limit is None:
                # contiguous slice of the total order per device; each
                # count is <= cap because total <= p*cap
                base = total // p
                rem = total % p
                start = i * base + jnp.minimum(i, rem)
                cnt = base + (i < rem).astype(jnp.int32)
                out_cap = cap
            else:
                out_cap = round_capacity(max(1, min(limit, gcap)))
                start = jnp.int32(0)
                cnt = jnp.where(i == 0,
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

    def _program(self, mesh):
        memo = id(mesh)
        if memo in self._jitted:
            return self._jitted[memo]
        from jax.sharding import PartitionSpec as P

        from spark_rapids_tpu.exec import compile_cache as cc
        axis = self.axis_name
        step = self._local_step()
        key = cc.fragment_key(*self._step_key_parts(),
                              cc.mesh_key_part(mesh, axis))

        def build():
            def prog(stacked: ColumnBatch) -> ColumnBatch:
                return restack(step(local_view(stacked)))
            return cc.instrument(jax.jit(shard_map(
                prog, mesh=mesh, in_specs=P(axis), out_specs=P(axis))),
                "mesh_sort")

        fn = cc.get_or_build(key, build)
        self._jitted[memo] = fn
        return fn

    def _outputs_cache_key(self, ctx: ExecCtx) -> tuple:
        return ("meshsort", id(self), ctx.backend)

    def _outputs(self, ctx: ExecCtx):
        return ctx.cached(self._outputs_cache_key(ctx),
                          lambda: self._compute_outputs(ctx))

    def _fallback_outputs(self, ctx: ExecCtx):
        """Single-device recompute from lineage: the in-process global
        sort over the same child — also the degenerate path when the
        child produced nothing."""
        out = [list(self._single_exec().partition_iter(ctx, 0))]
        out += [[] for _ in range(self.mesh_size - 1)]
        return out

    def _compute_outputs(self, ctx: ExecCtx):
        from spark_rapids_tpu.exec.core import drain_partitions
        batches = list(drain_partitions(ctx, self.children[0]))
        mesh = mesh_for(ctx, self.mesh_size, self.axis_name)
        t0 = None
        if batches:
            try:
                _check_slice_fault(ctx, "meshsort", mesh)
                shards = place_shards(batches, self.mesh_size)
                stacked = shard_batches(shards, mesh, self.axis_name)
                _note_a2a_bytes(stacked)
                result = self._program(mesh)(stacked)
                return [[b] for b in split_shards(result)]
            except Exception as err:
                _reraise_unless_slice_lost(err)
                t0 = time.perf_counter()
        out = self._fallback_outputs(ctx)
        if t0 is not None:
            _note_slice_recovery(ctx, time.perf_counter() - t0)
        return out

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        if not ctx.is_device:
            yield from self._single_exec().partition_iter(ctx, pid)
            return
        yield from self._aligned(iter(self._outputs(ctx)[pid]))

    def node_desc(self) -> str:
        lim = f", limit={self.limit}" if self.limit is not None else ""
        return f"MeshSortExec[mesh={self.mesh_size}, {self._orders}{lim}]"


class MeshWindowExec(_MeshOutputMixin, WindowExec):
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

    def __init__(self, window_exprs: Sequence, child: PlanNode,
                 mesh_size: int, axis_name: str = "data"):
        WindowExec.__init__(self, window_exprs, child,
                            keys_partitioned=False)
        self.mesh_size = mesh_size
        self.axis_name = axis_name
        self._jitted = {}

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
                cols = list(b.columns)
                fields = list(b.schema.fields)
                kidx = []
                for i, e in enumerate(part_b):
                    cols.append(eval_device(e, b))
                    fields.append(T.StructField(f"_wk{i}", e.dtype, True))
                    kidx.append(len(cols) - 1)
                aug = ColumnBatch(cols, b.num_rows, T.Schema(fields))
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
            total = out.num_rows
            i = jax.lax.axis_index(axis)
            base = total // p
            rem = total % p
            start = i * base + jnp.minimum(i, rem)
            cnt = base + (i < rem).astype(jnp.int32)
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

    def _program(self, mesh):
        memo = id(mesh)
        if memo in self._jitted:
            return self._jitted[memo]
        from jax.sharding import PartitionSpec as P

        from spark_rapids_tpu.exec import compile_cache as cc
        axis = self.axis_name
        step = self._local_step()
        key = cc.fragment_key(*self._step_key_parts(),
                              cc.mesh_key_part(mesh, axis))

        def build():
            def prog(stacked: ColumnBatch) -> ColumnBatch:
                return restack(step(local_view(stacked)))
            return cc.instrument(jax.jit(shard_map(
                prog, mesh=mesh, in_specs=P(axis), out_specs=P(axis))),
                "mesh_window")

        fn = cc.get_or_build(key, build)
        self._jitted[memo] = fn
        return fn

    def _outputs_cache_key(self, ctx: ExecCtx) -> tuple:
        return ("meshwin", id(self), ctx.backend)

    def _outputs(self, ctx: ExecCtx):
        return ctx.cached(self._outputs_cache_key(ctx),
                          lambda: self._compute_outputs(ctx))

    def _fallback_outputs(self, ctx: ExecCtx):
        """Single-device recompute from lineage: the in-process window
        over the same child — also the degenerate path when the
        child produced nothing."""
        out = [list(WindowExec.partition_iter(self, ctx, 0))]
        out += [[] for _ in range(self.mesh_size - 1)]
        return out

    def _compute_outputs(self, ctx: ExecCtx):
        from spark_rapids_tpu.exec.core import drain_partitions
        batches = list(drain_partitions(ctx, self.children[0]))
        mesh = mesh_for(ctx, self.mesh_size, self.axis_name)
        t0 = None
        if batches:
            try:
                _check_slice_fault(ctx, "meshwindow", mesh)
                shards = place_shards(batches, self.mesh_size)
                stacked = shard_batches(shards, mesh, self.axis_name)
                _note_a2a_bytes(stacked)
                result = self._program(mesh)(stacked)
                return [[b] for b in split_shards(result)]
            except Exception as err:
                _reraise_unless_slice_lost(err)
                t0 = time.perf_counter()
        out = self._fallback_outputs(ctx)
        if t0 is not None:
            _note_slice_recovery(ctx, time.perf_counter() - t0)
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

    Execution primes the terminal's per-execution output cache and then
    delegates ``partition_iter`` to the terminal, so its partition
    serving (exchange partition slicing, alignment, shrink) is reused
    unchanged.  When the leaf is itself a mesh exchange — bare or a
    chained region's exchange terminal — the upstream output shards
    stay committed one-per-device and are stacked in place
    (``_chained_shards``): no gather, no host hop between regions.
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
        self._segs = tuple(segs)
        self._merged = sum(filters_merged(seg) for kind, seg in segs
                           if kind == "stage")
        self._compacts = any(has_filter(seg) for kind, seg in segs
                             if kind == "stage")
        self._joins = tuple(op for k, op in segs if k == "join")
        super().__init__([members[0].children[0]]
                         + [j.children[1] for j in self._joins])
        self.mesh_size = terminal.mesh_size
        self.axis_name = terminal.axis_name
        self._jitted = {}
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

    # -- program -------------------------------------------------------
    def _is_exchange(self) -> bool:
        return isinstance(self._terminal, MeshExchangeExec)

    def _caps(self, leaf_cap: int, modes: tuple, send_cap: int | None,
              floors=None) -> tuple:
        """Symbolic per-device capacity walk over the segments, yielding
        the STATIC output capacity of each join (shard_map bodies cannot
        sync the probe total).  Elementwise stages and the global-window
        slice preserve capacity; a partitioned exchange's worst case is
        P*C; a join's output capacity starts as its post-exchange stream
        capacity and is floored by the measured total on a retry."""
        p = self.mesh_size
        cap = leaf_cap
        caps = []
        ji = 0
        for kind, seg in self._segs:
            if kind == "join":
                if modes[ji] == "partitioned":
                    c = cap if send_cap is None else min(send_cap, cap)
                    cap = p * c
                guess = round_capacity(max(cap, 8))
                if floors is not None and floors[ji]:
                    guess = max(guess, floors[ji])
                caps.append(guess)
                cap = guess
                ji += 1
            elif kind == "window" and seg._part_b:
                cap = p * cap
        return tuple(caps)

    def _body_key_parts(self, modes: tuple, caps: tuple,
                        send_capacity: int | None, probes: tuple) -> tuple:
        parts = []
        ji = 0
        for kind, seg in self._segs:
            if kind == "stage":
                parts.append(("stage", stage_key_parts(seg)))
            elif kind == "join":
                parts.append(seg._region_step_key_parts(
                    modes[ji], caps[ji], send_capacity, probes[ji]))
                ji += 1
            else:
                parts.append(seg._step_key_parts())
        return tuple(parts)

    def _program(self, mesh, send_capacity: int | None = None,
                 modes: tuple = (), caps: tuple = (), probes: tuple = ()):
        """``probes``: per absorbed join, the static ``(kind, packed,
        rkeys)`` of its prepared build (MeshJoinExec._region_build) in
        replicated mode, None in partitioned."""
        memo = (id(mesh), send_capacity, modes, caps, probes)
        if memo in self._jitted:
            return self._jitted[memo]
        from jax.sharding import PartitionSpec as P

        from spark_rapids_tpu.exec import compile_cache as cc
        axis = self.axis_name
        steps = []
        ji = 0
        for kind, seg in self._segs:
            if kind == "stage":
                steps.append(("stage", stage_body(seg)))
            elif kind == "join":
                steps.append(("join", seg._region_step(
                    modes[ji], caps[ji], send_capacity, probes[ji])))
                ji += 1
            else:
                steps.append(("window", seg._local_step()))
        is_ex = self._is_exchange()
        if is_ex:
            tstep = self._terminal._local_step(send_capacity)
            tparts = self._terminal._step_key_parts(send_capacity)
        else:
            tstep = self._terminal._local_step()
            tparts = self._terminal._step_key_parts()
        key = cc.fragment_key(
            "mesh_region",
            self._body_key_parts(modes, caps, send_capacity, probes),
            *tparts, tuple(c.output_schema for c in self.children),
            cc.mesh_key_part(mesh, axis))
        n_builds = len(self._joins)
        n_flags = 2 * sum(m == "partitioned" for m in modes) \
            + (1 if is_ex else 0)
        n_aux = n_builds + n_flags

        def build():
            def prog(stacked, *builds):
                b = local_view(stacked)
                # a partitioned join's build is this device's shard; a
                # replicated one's is the whole prepared build as it is
                blocal = [local_view(x) if m == "partitioned" else x
                          for m, x in zip(modes, builds)]
                totals, flags = [], []
                bi = 0
                for kind, step in steps:
                    if kind == "join":
                        # named in the ops' metadata, so a compiled module
                        # or a trace can tell one join's work from the
                        # next's and from the terminal's
                        with jax.named_scope(f"join{bi}"):
                            b, (total, fl) = step(b, blocal[bi])
                        # one join's work ends before the next's begins:
                        # its row stacks ([capacity, k] with k small pad
                        # to 512 bytes a row in HBM) are then dead, and
                        # the program's temporaries are the widest
                        # join's, not the sum over the joins (described
                        # v5e, three joins at 2^20 slots: 4.19 GB -> 2.17)
                        b, total = jax.lax.optimization_barrier((b, total))
                        totals.append(total)
                        flags.extend(fl)
                        bi += 1
                    else:
                        b = step(b)
                if is_ex:
                    out, ovf = tstep(b)
                    flags.append(ovf)
                else:
                    out = tstep(b)
                aux = tuple(restack(t) for t in totals) \
                    + tuple(restack(f) for f in flags)
                return restack(out), aux
            in_specs = (P(axis),) + tuple(
                P(axis) if m == "partitioned" else P() for m in modes)
            out_specs = (P(axis), (P(axis),) * n_aux)
            # a region with a join in it is another program to tune
            # than a chain of per-shard steps: the name says which
            return cc.instrument(jax.jit(shard_map(
                prog, mesh=mesh, in_specs=in_specs, out_specs=out_specs)),
                "mesh_region_join" if n_builds else "mesh_region_chain")

        fn = cc.get_or_build(key, build)
        self._jitted[memo] = fn
        return fn

    def _launch(self, ctx: ExecCtx, mesh, stacked, builds, leaf_cap: int,
                modes: tuple, probes: tuple):
        """Run the region program, re-running on the two loud
        under-capacity signals (never truncating): a join whose probe
        total exceeded its static output capacity recompiles at the
        rounded-up measured size; an overflowed bounded send buffer
        falls back to worst-case capacity (the mesh analog of the OOM
        split-and-retry ladder).  All join totals and overflow flags
        are read back in ONE stacked device fetch per attempt."""
        import numpy as np

        from spark_rapids_tpu.conf import MESH_SEND_CAPACITY
        send_cap = ctx.conf.get(MESH_SEND_CAPACITY) or None
        nj = len(self._joins)
        for probe in probes:
            if probe is not None:
                # which probe this join's body runs against its prepared
                # build, beside mesh_join_replicated (a retry at a larger
                # capacity runs the same probe and is not counted again)
                get_registry().inc(f"mesh_join.probe.{probe[0]}")
        floors = [0] * nj
        result = None
        for _ in range(nj + 2):
            caps = self._caps(leaf_cap, modes, send_cap, floors)
            if self._merged:
                get_registry().inc("fused.filters_merged", self._merged)
            if self._compacts:
                # the slots the region was handed, over all its devices
                dk.count_compaction(leaf_cap * self.mesh_size)
            result, aux = self._program(mesh, send_cap, modes, caps, probes)(
                stacked, *builds)
            if not aux or (nj == 0 and send_cap is None):
                return result
            vals = [np.asarray(v) for v in
                    # enginelint: disable=RL003 (join totals + overflow flags; one stacked sync gates the retry)
                    fetch_to_host(aux, "fetch@MeshRegionExec")]
            retry = False
            for i in range(nj):
                total = int(vals[i].max())
                if total > caps[i]:
                    get_registry().inc("mesh_join_capacity_retries")
                    floors[i] = max(floors[i],
                                    round_capacity(max(total, 1)))
                    retry = True
            if send_cap is not None and any(v.any() for v in vals[nj:]):
                get_registry().inc("mesh_send_overflows")
                send_cap = None
                retry = True
            if not retry:
                return result
        return result

    # -- execution -----------------------------------------------------
    def _ensure(self, ctx: ExecCtx) -> None:
        ctx.cached(("mesh_region", id(self), ctx.backend),
                   lambda: self._execute(ctx))

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
        elif isinstance(leaf, MeshRegionExec) and leaf._is_exchange():
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

    def _execute(self, ctx: ExecCtx) -> bool:
        tkey = self._terminal._outputs_cache_key(ctx)
        from spark_rapids_tpu.conf import MESH_REGION_CHAINING
        from spark_rapids_tpu.exec.core import drain_partitions
        mesh = mesh_for(ctx, self.mesh_size, self.axis_name)
        chained = None
        if ctx.conf.get(MESH_REGION_CHAINING):
            chained = self._chained_shards(ctx)
        batches = chained if chained is not None \
            else list(drain_partitions(ctx, self.children[0]))
        t0 = None
        if batches:
            try:
                _check_slice_fault(ctx, "meshregion", mesh)
                shards = chained if chained is not None \
                    else place_shards(batches, self.mesh_size)
                leaf_cap = shards[0].capacity
                stacked = shard_batches(shards, mesh, self.axis_name)
                if chained is None:
                    _note_a2a_bytes(stacked)
                modes = tuple("partitioned" if j._use_partitioned(ctx)
                              else "replicated" for j in self._joins)
                builds, probes = [], []
                for j, mode in zip(self._joins, modes):
                    if mode == "replicated":
                        build, probe = j._region_build(ctx, mesh)
                    else:
                        bl = drain_cached(ctx, j.children[1]) or \
                            [concat_or_empty([], j.children[1].output_schema)]
                        bshards = place_shards(bl, self.mesh_size)
                        build = shard_batches(bshards, mesh, self.axis_name)
                        _note_a2a_bytes(build)
                        probe = None
                    builds.append(build)
                    probes.append(probe)
                result = self._launch(ctx, mesh, stacked, builds, leaf_cap,
                                      modes, tuple(probes))
                if self._is_exchange():
                    ctx.cache[tkey] = ("mesh", split_shards(result))
                else:
                    ctx.cache[tkey] = [[b] for b in split_shards(result)]
                return True
            except Exception as err:
                _reraise_unless_slice_lost(err)
                t0 = time.perf_counter()
        # lost slice / empty input: the terminal's own
        # fallback recomputes through the intact member chain — a join
        # member's island path re-materializes BOTH its sides, so the
        # whole region lineage (build subtrees included) replays
        ctx.cache[tkey] = self._terminal._fallback_outputs(ctx)
        if t0 is not None:
            _note_slice_recovery(ctx, time.perf_counter() - t0)
        return True

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
