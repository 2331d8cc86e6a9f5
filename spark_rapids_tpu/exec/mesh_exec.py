"""Mesh-distributed execs: shuffle + aggregation under ``shard_map``.

This is the engine-level wiring of the ICI all-to-all data plane
(:mod:`spark_rapids_tpu.parallel.mesh_shuffle`): when the session conf
sets ``spark.rapids.tpu.mesh.deviceCount`` > 1, the planner lowers a
grouped aggregation to :class:`MeshAggregateExec` (one compiled
partial -> all-to-all -> final-merge program per device) and a hash
repartition to :class:`MeshExchangeExec`, instead of the in-process
stage-barrier loop in :mod:`spark_rapids_tpu.exec.exchange`.

Reference mapping (SURVEY.md §2.6, §3.4): the reference reaches its
accelerated shuffle through RapidsShuffleInternalManager.getWriter/
getReaderInternal (RapidsShuffleInternalManager.scala:285-345) with a
UCX peer-to-peer data plane; the TPU-native plane is one XLA
``all_to_all`` collective inside ``shard_map``, fused with the partial
and final aggregations so the compiler overlaps the collective with
compute.  Expression layout (pre-projection, update/merge specs, final
projection) is shared with :class:`HashAggregateExec` — the same
aggregation-buffer contract the reference's partial/final modes use
(aggregate.scala:77-169).
"""
from __future__ import annotations

import time
from typing import Iterator, Sequence

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch, round_capacity
from spark_rapids_tpu.exec.aggregate import HashAggregateExec, _relabel_d
from spark_rapids_tpu.exec.core import (ExecCtx, PlanNode, drain_partitions,
                                        fetch_to_host)
from spark_rapids_tpu.exec.fused import (filters_merged, has_filter,
                                         stage_body, stage_key_parts)
from spark_rapids_tpu.exec.joins import (JoinExec, probe_selected,
                                         probe_traced)
from spark_rapids_tpu.expr.core import Expression, bind, eval_device
from spark_rapids_tpu.ops import kernels as dk
from spark_rapids_tpu.ops.join import (PackedBuild, gather_join_output,
                                       join_indices_from_probe)
from spark_rapids_tpu.ops.segmented import sorted_group_by
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.parallel.mesh import (local_view, make_mesh, restack,
                                            shard_batches, shard_map,
                                            split_shards)
from spark_rapids_tpu.parallel.mesh_shuffle import (canonicalize,
                                                    exchange_local,
                                                    exchange_local_checked,
                                                    partition_ids_for_keys)

__all__ = ["DeviceSliceLost", "MeshSendOverflow", "MeshLauncher",
           "MeshAggregateExec", "MeshExchangeExec", "MeshJoinExec",
           "all_gather_batch", "all_gather_rows", "mesh_for", "order_slice",
           "with_key_columns"]


def _committed_device(b: ColumnBatch):
    """The single device ``b`` is committed to, or None (uncommitted
    batches live wherever the default device put them)."""
    if b.columns and getattr(b.columns[0].data, "committed", False):
        devs = b.columns[0].data.devices()
        if len(devs) == 1:
            return next(iter(devs))
    return None


def _note_a2a_bytes(stacked) -> None:
    """Static worst-case accounting for one collective launch: in an
    all-to-all every input row crosses the interconnect at most once, so
    the stacked program input's total byte size bounds the traffic.
    Incremented host-side at launch (a counter inside the jitted program
    is not expressible), so the counter moves per collective, not per
    byte actually routed off-device."""
    n = sum(getattr(leaf, "nbytes", 0)
            for leaf in jax.tree_util.tree_leaves(stacked))
    get_registry().inc("mesh_all_to_all_bytes", float(n))


class _MeshOutputMixin:
    """Mesh execs yield per-device committed batches.  When the planner
    sees a NON-mesh consumer above (which would mix devices inside its
    own jitted programs — per-batch join probes, window kernels), it
    sets ``align_output`` and the exec moves each yielded batch to the
    default device at the mesh->single-device boundary (review finding:
    patching individual consumers is whack-a-mole).

    Every batch that actually MOVES across devices here increments
    ``mesh_gather_fallbacks`` — the counter that tells you the plan fell
    off the mesh (docs/tuning-guide.md "Pod-scale execution"): a fully
    region-resident pipeline reads 0 because region members exchange
    inside one program and the boundary batches are consumed
    device-aware (place_shards affinity)."""

    align_output: bool = False

    def _aligned(self, it):
        if not self.align_output:
            yield from it
            return
        target = jax.devices()[0]
        for b in it:
            # host-backend batches (oracle path) carry no placement
            if not isinstance(b, ColumnBatch):
                yield b
                continue
            src = _committed_device(b)
            if src is not None and src != target:
                get_registry().inc("mesh_gather_fallbacks")
            yield jax.device_put(b, target)


class DeviceSliceLost(RuntimeError):
    """A mesh device slice died under a collective program (injected
    ``mesh.slice.lost`` fault, or an XLA/PJRT device-loss status): the
    on-mesh outputs are unrecoverable, but the child lineage is intact
    so the exec can recompute single-device."""


class MeshSendOverflow(RuntimeError):
    """A bounded [P, C] all-to-all send buffer
    (spark.rapids.tpu.mesh.exchange.sendCapacityRows) could not carry a
    skewed destination's rows.  Never silent: the overflow flag comes
    back from the program and the exchange retries at worst-case
    capacity (the mesh analog of PR 2's detect-then-split-and-retry —
    here the 'split' is the other direction: give the buffer room)."""


# status fragments PJRT/XLA surface when a participating device (or the
# ICI link to it) is gone mid-program, as opposed to a program bug
_DEVICE_LOSS_MARKERS = ("UNAVAILABLE", "DATA_LOSS", "device is lost",
                        "Device lost", "heartbeat timeout")


def _check_slice_fault(ctx: ExecCtx, op: str, mesh) -> None:
    """Deterministic injection point ``mesh.slice.lost`` (ctx: op,
    devices): fires before the collective launches, as a real slice
    loss would surface at program dispatch."""
    faults = getattr(ctx.catalog, "faults", None)
    if faults is None:
        return
    devices = ",".join(str(d.id) for d in mesh.devices.flat)
    if faults.check("mesh.slice.lost", op=op, devices=devices) is not None:
        raise DeviceSliceLost(
            f"injected fault: mesh slice lost under {op} "
            f"(devices [{devices}])")


def _reraise_unless_slice_lost(err: BaseException) -> None:
    """Let slice-loss errors fall through to the single-device
    recompute; anything else propagates unchanged."""
    if isinstance(err, DeviceSliceLost):
        return
    text = f"{type(err).__name__}: {err}"
    if any(m in text for m in _DEVICE_LOSS_MARKERS):
        return
    raise err


def _note_slice_recovery(ctx: ExecCtx, wall_s: float) -> None:
    """A lost slice was replaced by a single-device recompute: account
    it as one stage recovery so chaos/bench metrics see mesh losses and
    shuffle losses through the same counters (exec/recovery.py)."""
    m = ctx.catalog.metrics
    m["stage_recomputes"] = m.get("stage_recomputes", 0) + 1
    m["recovery_wall_s"] = m.get("recovery_wall_s", 0.0) + wall_s


def all_gather_rows(b: ColumnBatch, p: int, axis: str):
    """In-program replication of a sharded batch's storage: per-column
    tiled ``all_gather`` of every shard's ``cap`` slots, the shards' row
    counts, and the segment-aware real mask (gathered rows are packed per
    shard segment, not globally).  Returns ``(cols, counts, real)`` over
    ``p * cap`` slots — the MeshSortExec gather, which orders by the mask
    instead of compacting."""
    from spark_rapids_tpu.columnar.column import DeviceColumn
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
    idx = jnp.arange(p * cap, dtype=jnp.int32)
    return cols, counts, (idx % cap) < counts[idx // cap]


def all_gather_batch(b: ColumnBatch, p: int, axis: str) -> ColumnBatch:
    """Every device ends up with ALL rows of the sharded batch,
    front-packed: :func:`all_gather_rows`, then one compaction to
    restore the front-packed num_rows/row_mask contract downstream
    traced bodies rely on.  This is the global window's input gather
    (a replicated mesh join's build is prepared outside its program and
    handed in replicated: MeshJoinExec._region_build)."""
    cols, _, real = all_gather_rows(b, p, axis)
    # num_rows = gcap so compact's row_mask covers every gathered slot;
    # compact itself front-packs and sets the true count
    gb = ColumnBatch(cols, jnp.asarray(p * b.capacity, jnp.int32), b.schema)
    return dk.compact(gb, real)


def with_key_columns(b: ColumnBatch, bound: Sequence[Expression]):
    """``b`` with the evaluated ``bound`` key expressions appended, and
    their column indices: what a hash exchange computes its partition
    ids over (the keys never travel — the raw batch is what is sent)."""
    cols = list(b.columns)
    fields = list(b.schema.fields)
    for i, k in enumerate(bound):
        cols.append(eval_device(k, b))
        fields.append(T.StructField(f"_pk{i}", k.dtype, True))
    return (ColumnBatch(cols, b.num_rows, T.Schema(fields)),
            list(range(b.num_columns, len(cols))))


def order_slice(total, p: int, axis: str):
    """``(start, count)`` of this device's contiguous slice of a total
    order of ``total`` rows held whole on each of ``p`` devices: device i
    keeps rows [i*base + min(i, rem), ...), so partition order IS global
    order."""
    i = jax.lax.axis_index(axis)
    base = total // p
    rem = total % p
    return i * base + jnp.minimum(i, rem), base + (i < rem).astype(jnp.int32)


def _mesh_devices(size: int):
    """The first ``size`` devices; a configured mesh wider than the
    devices present raises instead of quietly running single-device."""
    devs = jax.devices()
    if len(devs) < size:
        raise RuntimeError(
            f"spark.rapids.tpu.mesh.deviceCount={size} but only "
            f"{len(devs)} device(s) are present; refusing to run a "
            "configured mesh on fewer devices")
    return devs[:size]


def mesh_for(ctx: ExecCtx, size: int, axis_name: str = "data"):
    """The ctx-cached 1-D device mesh over the first ``size`` devices."""
    key = ("mesh", size, axis_name)
    if key not in ctx.cache:
        ctx.cache[key] = make_mesh(size, axis_name, _mesh_devices(size))
    return ctx.cache[key]


def place_shards(batches: Sequence[ColumnBatch], p: int):
    """Assign child batches to device shards WITHOUT a central gather.

    Round-2 verdict item 7: the old implementation concatenated every
    child batch in the driver process and re-sliced — a full gather
    before the "distributed" program.  Here batches are greedily
    assigned to shards and concatenated only WITHIN their shard
    (each shard touches ~1/p of the data; on a multi-host plane each
    host would run its own group).  Capacities and string widths are
    made uniform across shards (stacking onto the mesh requires it) by
    padding, not by gathering.  Row->shard assignment is arbitrary —
    callers shuffle by key immediately after (the reference's map-side
    split has the same freedom).

    Placement is by REAL rows, not storage capacity: inputs arrive
    padded (a region's split output keeps its program's static
    capacity; a scan can hand over one table-sized batch), and
    capacity-based placement both skews every real row onto one device
    and inflates the shared shard capacity to the fattest padded input
    — a multi-join region then sorts mostly padding on every device.
    Oversized free batches are sliced into ~1/p row ranges; committed
    batches keep their device (cross-device concat is both an error
    and a needless ICI hop) and are shrunk to their real rows instead.
    """
    groups: list[list[ColumnBatch]] = [[] for _ in range(p)]
    loads = [0] * p
    # device affinity first: batches already committed to a mesh device
    # (e.g. MeshJoinExec probe output) stay on it
    devs = jax.devices()[:p]
    dev_index = {repr(d): i for i, d in enumerate(devs)}
    rest = []
    for b in batches:
        n = b.host_num_rows()
        i = None
        if b.columns and getattr(b.columns[0].data, "committed", False):
            bdevs = b.columns[0].data.devices()
            if len(bdevs) == 1:
                i = dev_index.get(repr(next(iter(bdevs))))
        if i is not None:
            groups[i].append(b)
            loads[i] += n
        else:
            rest.append((n, b))
    total = sum(n for n, _ in rest)
    chunk = max(1024, -(-total // p))
    parts = []
    for n, b in rest:
        if n <= chunk:
            parts.append((n, b))
        else:
            for lo in range(0, n, chunk):
                hi = min(lo + chunk, n)
                parts.append((hi - lo, dk.slice_rows(b, lo, hi)))
    for n, b in sorted(parts, key=lambda t: -t[0]):
        i = loads.index(min(loads))
        groups[i].append(b)
        loads[i] += n
    cap = round_capacity(max(max(loads), 8))
    # global string widths per column (concat pads only within a group)
    schema = batches[0].schema
    widths = [max((b.columns[ci].max_len for b in batches), default=1)
              if isinstance(f.data_type, T.StringType) else None
              for ci, f in enumerate(schema)]
    shards = []
    for g in groups:
        if not g:
            shards.append(_empty_shard(schema, cap, widths))
            continue
        # drop each member to its own real-row bucket first: a padded
        # upstream capacity must not leak into the group concat
        g = [dk.shrink_capacity(b, round_capacity(max(b.host_num_rows(), 1)))
             for b in g]
        if len(g) == 1:
            s = g[0] if g[0].capacity == cap \
                else dk.pad_capacity(g[0], cap)
        else:
            need = max(cap, round_capacity(sum(b.capacity for b in g)))
            s = dk.concat_batches(g, out_capacity=need)
            if s.capacity > cap:
                s = dk.shrink_capacity(s, cap)
        shards.append(_pad_widths(s, widths))
    return shards


def drain_cached(ctx: ExecCtx, node: PlanNode) -> list:
    """Drain a child ONCE per execution and cache the batch list, so a
    size probe, an exchange, and a build can share one materialization
    (review finding: the partitioned-join size check must not drain the
    build side twice)."""
    return ctx.cached(("drained", id(node), ctx.backend),
                      lambda: list(drain_partitions(ctx, node)))


def concat_or_empty(batches, schema: T.Schema) -> ColumnBatch:
    """One device batch from a drained list (empty-schema fallback).

    Region-era inputs may be committed to DIFFERENT mesh devices
    (split_shards keeps boundary batches device-resident); a concat
    must see them on one device, so mixed placements are aligned to the
    first committed device before concatenation — this is a build-side
    materialization (replicated to every device right after), not a
    gather fallback."""
    if not batches:
        from spark_rapids_tpu.exec.core import host_to_device
        from spark_rapids_tpu.host.batch import HostBatch
        return host_to_device(HostBatch.empty(schema))
    if len(batches) == 1:
        return batches[0]
    devs = {repr(_committed_device(b)) for b in batches}
    if len(devs) > 1:
        target = _committed_device(batches[0]) or jax.devices()[0]
        batches = [b if _committed_device(b) == target
                   else jax.device_put(b, target) for b in batches]
    return dk.concat_batches(batches)


def _empty_shard(schema: T.Schema, cap: int, widths) -> ColumnBatch:
    from spark_rapids_tpu.columnar.column import DeviceColumn
    cols = []
    for f, w in zip(schema, widths):
        validity = jnp.zeros(cap, jnp.bool_)
        if w is not None:
            cols.append(DeviceColumn(jnp.zeros((cap, w), jnp.uint8),
                                     validity, f.data_type,
                                     jnp.zeros(cap, jnp.int32)))
        else:
            cols.append(DeviceColumn(
                jnp.zeros(cap, f.data_type.np_dtype), validity,
                f.data_type))
    return ColumnBatch(cols, jnp.asarray(0, jnp.int32), schema)


def _pad_widths(b: ColumnBatch, widths) -> ColumnBatch:
    from spark_rapids_tpu.columnar.column import DeviceColumn
    cols = []
    changed = False
    for c, w in zip(b.columns, widths):
        if w is not None and c.max_len < w:
            cols.append(DeviceColumn(
                jnp.pad(c.data, ((0, 0), (0, w - c.max_len))), c.validity,
                c.dtype, c.lengths))
            changed = True
        else:
            cols.append(c)
    return ColumnBatch(cols, b.num_rows, b.schema) if changed else b


class MeshLauncher:
    """The one definition of *build, launch, retry, recover* for a mesh
    program: a ``shard_map`` executable whose per-device body is a
    region's segments (elementwise stages, absorbed joins and windows)
    followed by its terminal's ``_local_step`` — or, for a bare
    terminal, that step alone (zero segments).

    ``terminal`` supplies what is its own: ``_local_step``,
    ``_step_key_parts``, ``_fallback_outputs`` and the two names a bare
    launch goes by (``_program_name``, ``_fault_op``).  ``region`` is the
    MeshRegionExec holding the launcher, or None for a bare terminal: the
    plan node whose children feed the program, the fault op
    (``meshregion``), the program name (``mesh_region_join`` with joins,
    ``mesh_region_chain`` without), the fetch span and the fragment key
    follow it.  ``segs`` is the region's ``(kind, op)`` segmentation.
    ``drain`` materializes the leaf: a bare exchange passes
    ``drain_cached`` (MeshJoinExec._use_partitioned has already drained
    that subtree for its size probe); everything else drains its
    partitions and holds nothing for the rest of the query."""

    def __init__(self, terminal: PlanNode, region: PlanNode | None = None,
                 segs: Sequence[tuple] = (), drain=None):
        self._terminal = terminal
        self._node = terminal if region is None else region
        self._segs = tuple(segs)
        self._joins = tuple(op for kind, op in self._segs if kind == "join")
        stages = [seg for kind, seg in self._segs if kind == "stage"]
        self._merged = sum(filters_merged(seg) for seg in stages)
        self._compacts = any(has_filter(seg) for seg in stages)
        self._fault_op = terminal._fault_op if region is None \
            else "meshregion"
        self._drain = drain or (
            lambda ctx, node: list(drain_partitions(ctx, node)))
        self._is_ex = isinstance(terminal, MeshExchangeExec)
        self._jitted = {}

    def _caps(self, leaf_cap: int, modes: tuple, send_cap: int | None,
              floors=None) -> tuple:
        """Symbolic per-device capacity walk over the segments, yielding
        the STATIC output capacity of each join (shard_map bodies cannot
        sync the probe total).  Elementwise stages and the global-window
        slice preserve capacity; a partitioned exchange's worst case is
        P*C; a join's output capacity starts as its post-exchange stream
        capacity and is floored by the measured total on a retry."""
        p = self._terminal.mesh_size
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
        axis = self._terminal.axis_name
        steps, body_parts = [], []
        ji = 0
        for kind, seg in self._segs:
            if kind == "stage":
                steps.append((kind, stage_body(seg)))
                body_parts.append(("stage", stage_key_parts(seg)))
            elif kind == "join":
                jargs = (modes[ji], caps[ji], send_capacity, probes[ji])
                steps.append((kind, seg._region_step(*jargs)))
                body_parts.append(seg._region_step_key_parts(*jargs))
                ji += 1
            else:
                steps.append((kind, seg._local_step()))
                body_parts.append(seg._step_key_parts())
        is_ex = self._is_ex
        # only the exchange's step has a send buffer to bound
        targs = (send_capacity,) if is_ex else ()
        tstep = self._terminal._local_step(*targs)
        tparts = self._terminal._step_key_parts(*targs)
        if self._node is self._terminal:
            key = cc.fragment_key(*tparts, cc.mesh_key_part(mesh, axis))
        else:
            key = cc.fragment_key(
                "mesh_region", tuple(body_parts), *tparts,
                tuple(c.output_schema for c in self._node.children),
                cc.mesh_key_part(mesh, axis))
        n_flags = 2 * sum(m == "partitioned" for m in modes) \
            + (1 if is_ex else 0)
        n_aux = len(self._joins) + n_flags

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
                self._terminal._program_name if self._node is self._terminal
                else "mesh_region_join" if self._joins
                else "mesh_region_chain")

        fn = cc.get_or_build(key, build)
        self._jitted[memo] = fn
        return fn

    def _launch(self, ctx: ExecCtx, mesh, stacked, builds, leaf_cap: int,
                modes: tuple, probes: tuple):
        """Run the program, re-running on the two loud under-capacity
        signals (never truncating): a join whose probe total exceeded
        its static output capacity recompiles at the rounded-up measured
        size; an overflowed bounded send buffer falls back to worst-case
        capacity (the mesh analog of the OOM split-and-retry ladder).
        All join totals and overflow flags are read back in ONE stacked
        device fetch per attempt."""
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
                dk.count_compaction(leaf_cap * self._terminal.mesh_size)
            result, aux = self._program(mesh, send_cap, modes, caps, probes)(
                stacked, *builds)
            if not aux or (nj == 0 and send_cap is None):
                return result
            vals = [np.asarray(v) for v in
                    # enginelint: disable=RL003 (join totals + overflow flags; one stacked sync gates the retry)
                    fetch_to_host(aux,
                                  f"fetch@{type(self._node).__name__}")]
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

    def run(self, ctx: ExecCtx, chained=None):
        """One execution's outputs in the shape the terminal caches them
        in (``_outputs_cache_key``): the exchange's ``("mesh", shards)``,
        else one list per device.  ``chained`` are an upstream exchange's
        committed shards, stacked in place instead of a drained leaf
        (MeshRegionExec._chained_shards).  A lost slice or an empty input
        is answered by the terminal's own single-device fallback, which
        recomputes through the intact member chain — a join member's
        island path re-materializes BOTH its sides, so a whole region's
        lineage (build subtrees included) replays."""
        t = self._terminal
        p, axis = t.mesh_size, t.axis_name
        mesh = mesh_for(ctx, p, axis)
        batches = chained if chained is not None \
            else self._drain(ctx, self._node.children[0])
        t0 = None
        if batches:
            try:
                _check_slice_fault(ctx, self._fault_op, mesh)
                shards = chained if chained is not None \
                    else place_shards(batches, p)
                stacked = shard_batches(shards, mesh, axis)
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
                        build = shard_batches(place_shards(bl, p), mesh, axis)
                        _note_a2a_bytes(build)
                        probe = None
                    builds.append(build)
                    probes.append(probe)
                out = split_shards(self._launch(
                    ctx, mesh, stacked, builds, shards[0].capacity, modes,
                    tuple(probes)))
                return ("mesh", out) if self._is_ex else [[b] for b in out]
            except Exception as err:
                _reraise_unless_slice_lost(err)
                t0 = time.perf_counter()
        out = t._fallback_outputs(ctx)
        if t0 is not None:
            _note_slice_recovery(ctx, time.perf_counter() - t0)
        return out


class _MeshTerminal(_MeshOutputMixin):
    """A collective operator a :class:`MeshLauncher` can end a program
    with.  Its own: ``_local_step``, ``_step_key_parts``,
    ``_fallback_outputs``, ``_outputs_cache_key`` and two names — the
    program a bare launch is counted under and the op its injected slice
    loss is checked under."""

    _program_name: str
    _fault_op: str

    def _outputs(self, ctx: ExecCtx):
        """Per-execution outputs: primed by the region that absorbed this
        terminal, else launched bare (on the host backend only the
        exchange serves partitions from here: its in-process path)."""
        return ctx.cached(
            self._outputs_cache_key(ctx),
            lambda: self._launcher.run(ctx) if ctx.is_device
            else self._fallback_outputs(ctx))


class MeshAggregateExec(_MeshTerminal, PlanNode):
    """Grouped aggregation as ONE distributed program over the mesh.

    Device plan per shard: pre-project -> partial sorted group-by ->
    all-to-all exchange of buffer rows by key hash -> merge group-by ->
    final projection.  Falls back to a complete-mode
    :class:`HashAggregateExec` on the host backend or on empty input.
    """

    _program_name = "mesh_aggregate"
    _fault_op = "meshagg"

    def __init__(self, group_exprs: Sequence[Expression],
                 result_exprs: Sequence[Expression], child: PlanNode,
                 mesh_size: int, axis_name: str = "data"):
        super().__init__([child])
        self.mesh_size = mesh_size
        self.axis_name = axis_name
        self._group_exprs = list(group_exprs)
        self._result_exprs = list(result_exprs)
        # expression layout (pre/update/merge/final) — HashAggregateExec
        # owns this contract; partial mode exposes the buffer schema.
        self._layout = HashAggregateExec(group_exprs, result_exprs, child,
                                         mode="partial")
        self._output_schema = T.Schema(
            [T.StructField(f.name, f.data_type, True)
             for f in HashAggregateExec.final_from_partial(
                 self._layout, child).output_schema])
        self._launcher = MeshLauncher(self)

    @property
    def output_schema(self) -> T.Schema:
        return self._output_schema

    def num_partitions(self, ctx: ExecCtx) -> int:
        return self.mesh_size if ctx.is_device else 1

    # -- fallback ------------------------------------------------------
    def _complete_exec(self) -> HashAggregateExec:
        # built lazily so transition-inserted wrappers around the child
        # (same schema) are picked up
        return HashAggregateExec(self._group_exprs, self._result_exprs,
                                 self.children[0], mode="complete")

    # -- distributed program -------------------------------------------
    def _local_step(self):
        """The per-device body (local view in, local view out) — the
        unit a MeshRegionExec splices into ITS shard_map program so a
        whole pipeline compiles as one per-device executable."""
        L = self._layout
        key_idx = list(range(len(L._group_bound)))
        p = self.mesh_size
        axis = self.axis_name
        out_schema = self._output_schema

        def step(b: ColumnBatch) -> ColumnBatch:
            cols = [eval_device(e, b) for e in L._pre_exprs]
            pre = ColumnBatch(cols, b.num_rows, L._pre_schema)
            part_out = _relabel_d(
                sorted_group_by(pre, key_idx, L._update_specs),
                L._buffer_schema)
            if key_idx:
                pid = partition_ids_for_keys(part_out, key_idx, p)
            else:
                # grand aggregate: merge all partial rows on device 0
                pid = jnp.where(part_out.row_mask(), 0, p)
            ex = _relabel_d(exchange_local(part_out, pid, p, axis),
                            L._buffer_schema)
            merged = _relabel_d(
                sorted_group_by(ex, key_idx, L._merge_specs),
                L._buffer_schema)
            out_cols = [eval_device(e, merged) for e in L._final_exprs]
            out = ColumnBatch(out_cols, merged.num_rows, out_schema)
            if not key_idx:
                # grand-aggregate finalization stays ON-device: device 0
                # carries the merged row, every other shard suppresses
                # its identity row — no host hop before the final value
                on0 = jax.lax.axis_index(axis) == 0
                out = canonicalize(ColumnBatch(
                    out.columns, jnp.where(on0, out.num_rows, 0),
                    out.schema))
            return out

        return step

    def _step_key_parts(self) -> tuple:
        """Fragment-key material for the local step (mesh part added by
        the program builder — a region key composes these per member)."""
        L = self._layout
        return ("mesh_agg", tuple(L._pre_exprs), L._pre_schema,
                tuple(L._update_specs), tuple(L._merge_specs),
                tuple(L._final_exprs), self._output_schema,
                len(L._group_bound), self.mesh_size)

    def _outputs_cache_key(self, ctx: ExecCtx) -> tuple:
        return ("meshagg", id(self), ctx.backend)

    def _fallback_outputs(self, ctx: ExecCtx):
        """Single-device recompute: the complete-mode aggregation is the
        mesh program's lineage (same layout contract), re-run on the
        default device — also the degenerate path when the mesh never
        existed or the child produced nothing."""
        out = [list(self._complete_exec().partition_iter(ctx, 0))]
        out += [[] for _ in range(self.mesh_size - 1)]
        return out

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        if not ctx.is_device:
            yield from self._complete_exec().partition_iter(ctx, pid)
            return
        yield from self._aligned(iter(self._outputs(ctx)[pid]))

    def node_desc(self) -> str:
        return (f"MeshAggregateExec[mesh={self.mesh_size}, "
                f"keys={self._layout._group_names}, "
                f"out={self._output_schema.names}]")


class MeshExchangeExec(_MeshTerminal, PlanNode):
    """Hash repartition as an all-to-all collective over the mesh.

    Device path: pack child output into per-device shards, then ONE
    compiled program computes Spark-bit-exact murmur3 partition ids and
    exchanges rows (reference write path GpuHashPartitioning +
    RapidsCachingWriter, read path RapidsShuffleIterator — here both
    sides are the same collective).  Host backend delegates to the
    in-process ShuffleExchangeExec.
    """

    _program_name = "mesh_exchange"
    _fault_op = "meshex"

    def __init__(self, keys: Sequence[Expression], child: PlanNode,
                 mesh_size: int, axis_name: str = "data",
                 num_partitions: int | None = None):
        super().__init__([child])
        self.mesh_size = mesh_size
        self.axis_name = axis_name
        # output partition count is independent of the device count
        # (round-2 verdict: the old num_partitions == deviceCount gate
        # silently sent other repartitions down the in-process loop):
        # rows route to device (pid % mesh_size); each device then serves
        # its owned subset of the N output partitions.
        self._num_parts = num_partitions or mesh_size
        self._keys = list(keys)
        self._bound = [bind(k, child.output_schema) for k in self._keys]
        # drain_cached, not drain_partitions: in partitioned mesh-join
        # mode _use_partitioned already drained this subtree for its size
        # probe — share that materialization instead of executing twice
        self._launcher = MeshLauncher(self, drain=drain_cached)

    @property
    def output_schema(self) -> T.Schema:
        return self.children[0].output_schema

    def num_partitions(self, ctx: ExecCtx) -> int:
        return self._num_parts

    def _host_exchange(self):
        from spark_rapids_tpu.exec.exchange import ShuffleExchangeExec
        from spark_rapids_tpu.exec.partitioning import HashPartitioning
        return ShuffleExchangeExec(
            HashPartitioning(self._keys, self._num_parts), self.children[0])

    def _local_step(self, send_capacity: int | None = None):
        """Per-device body returning ``(batch, overflow)`` — the region
        splices this into its own shard_map program; overflow is
        statically False at worst-case capacity (send_capacity=None)."""
        p = self.mesh_size
        n = self._num_parts
        axis = self.axis_name

        def step(b: ColumnBatch):
            aug, kidx = with_key_columns(b, self._bound)
            pid = partition_ids_for_keys(aug, kidx, n)
            dev = jnp.where(pid < n, pid % p, p)  # padding -> p (dropped)
            return exchange_local_checked(b, dev, p, axis,
                                          send_capacity=send_capacity)

        return step

    def _step_key_parts(self, send_capacity: int | None = None) -> tuple:
        return ("mesh_exchange", tuple(self._bound),
                self.children[0].output_schema, self._num_parts,
                send_capacity, self.mesh_size)

    def _pick_jit(self):
        # per output partition: keep rows of the device shard whose
        # recomputed partition id matches (device-local slice of the N
        # output partitions; no cross-device traffic)
        if not hasattr(self, "_pick"):
            n = self._num_parts

            def pick(b, pid):
                aug, kidx = with_key_columns(b, self._bound)
                ids = partition_ids_for_keys(aug, kidx, n)
                return dk.compact(b, ids == pid)

            from spark_rapids_tpu.exec import compile_cache as cc
            self._pick = cc.instrument(jax.jit(pick), "mesh_exchange_pick")
        return self._pick

    def _outputs_cache_key(self, ctx: ExecCtx) -> tuple:
        return ("meshex", id(self), ctx.backend)

    def _fallback_outputs(self, ctx: ExecCtx):
        """Single-device recompute from lineage: the in-process exchange
        over the same child and keys — also the degenerate path when
        the child produced nothing."""
        he = self._host_exchange()
        return ("host", [list(he.partition_iter(ctx, pid))
                         for pid in range(self._num_parts)])

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        yield from self._aligned(self._partition_iter_mesh(ctx, pid))

    def _partition_iter_mesh(self, ctx: ExecCtx, pid: int) -> Iterator:
        kind, out = self._outputs(ctx)
        if kind == "host":
            yield from out[pid]
            return
        # device shard (pid % mesh) holds every row of output partition
        # pid; slice it out locally and right-size the capacity (the
        # exchange shard capacity is p*C — passing it through would make
        # every downstream op pay O(n_parts * p * C))
        shard = out[pid % self.mesh_size]
        b = ctx.dispatch(self._pick_jit(), shard,
                         jnp.asarray(pid, jnp.int32))
        count = b.host_num_rows("fetch@MeshExchangeExec")
        if count > 0 or self._num_parts == 1:
            yield ctx.dispatch(dk.shrink_capacity, b,
                               round_capacity(max(count, 1)))

    def node_desc(self) -> str:
        return (f"MeshExchangeExec[mesh={self.mesh_size}, "
                f"parts={self._num_parts}, "
                f"keys={[output_name_safe(k) for k in self._keys]}]")


def output_name_safe(e: Expression) -> str:
    from spark_rapids_tpu.expr.core import output_name
    try:
        return output_name(e)
    # enginelint: disable=RL001 (descriptive label only; falls back to repr)
    except Exception:  # noqa: BLE001 - descriptive label only
        return repr(e)


class MeshJoinExec(_MeshOutputMixin, JoinExec):
    """Equi-join distributed over the mesh, broadcast OR partitioned.

    Two modes, selected at runtime by the materialized build-side size
    against ``spark.rapids.tpu.mesh.join.buildThresholdBytes``:

    - **replicated build** (the GpuBroadcastHashJoinExec analog,
      SURVEY §2.4): the build side is materialized once and REPLICATED
      to every mesh device (torrent-broadcast analog — small table
      resident per chip); the stream side is placed as per-device
      shards (place_shards, no central gather) and each device probes
      its own shard.  No collectives at all.  Absorbed into a region,
      the same prepared build is an argument of the region program
      (:meth:`_region_build`), not a collective inside it.
    - **partitioned** (the GpuShuffledHashJoinExec.scala:162 analog):
      BOTH sides hash-exchange on the join keys over the mesh
      (:class:`MeshExchangeExec` — exchange_local all-to-all inside
      shard_map), then each device joins its co-partitioned shards
      locally.  Equal keys land on the same device because both
      exchanges compute the same murmur3 over type-identical key
      columns, so a build side larger than one device's HBM share
      scales instead of replicating.

    Full outer joins keep the in-process path (their unmatched-build
    tail needs a cross-shard matched union).
    """

    def __init__(self, left: PlanNode, right: PlanNode, left_keys,
                 right_keys, join_type: str, mesh_size: int,
                 condition=None, build_threshold_bytes: int = 128 << 20):
        assert join_type != "full", "full outer stays in-process"
        super().__init__(left, right, left_keys, right_keys, join_type,
                         condition)
        self.mesh_size = mesh_size
        # the island path never names the mesh axis (its collectives run
        # through MeshExchangeExec), but the in-region body issues its
        # own all-to-alls (partitioned mode) under the region's axis
        self.axis_name = "data"
        self.build_threshold_bytes = build_threshold_bytes
        # unbound key exprs in POST-swap orientation (children[0] =
        # stream, children[1] = build) for the partitioned exchanges
        if self._swapped:
            left_keys, right_keys = right_keys, left_keys
        self._stream_keys_unbound = list(left_keys)
        self._build_keys_unbound = list(right_keys)
        # constructed eagerly (cheap PlanNodes): partition_iter runs on
        # concurrent drain workers, and a lazy check-then-set here would
        # race into duplicate exchanges doing the all-to-all twice
        self._exchanges = (
            MeshExchangeExec(self._stream_keys_unbound, self.children[0],
                             mesh_size, num_partitions=mesh_size),
            MeshExchangeExec(self._build_keys_unbound, self.children[1],
                             mesh_size, num_partitions=mesh_size))

    def num_partitions(self, ctx: ExecCtx) -> int:
        if not ctx.is_device:
            return self.children[0].num_partitions(ctx)
        return self.mesh_size

    # -- hooks ---------------------------------------------------------
    def _shard_devices(self, ctx: ExecCtx):
        return _mesh_devices(self.mesh_size)

    def _mesh_shards(self, ctx: ExecCtx):
        def make():
            devs = self._shard_devices(ctx)
            batches = drain_cached(ctx, self.children[0]) or \
                [concat_or_empty([], self.children[0].output_schema)]
            shards = place_shards(batches, len(devs))
            return [jax.device_put(s, d) for s, d in zip(shards, devs)]
        return ctx.cached((id(self), "mesh_stream_shards"), make)

    def _stream_batches(self, ctx: ExecCtx, pid: int):
        if self._use_partitioned(ctx):
            yield from self._exchanges[0].partition_iter(ctx, pid)
            return
        shards = self._mesh_shards(ctx)
        if pid < len(shards):
            yield shards[pid]

    # -- partitioned mode ---------------------------------------------
    def _use_partitioned(self, ctx: ExecCtx) -> bool:
        """Runtime mode pick: partitioned when the materialized build
        side exceeds the conf threshold (the reference decides build
        strategy from plan statistics, GpuShuffledHashJoinExec vs
        GpuBroadcastHashJoinExec; the engine decides from the ACTUAL
        drained size — exact, at the cost of one central
        materialization that a stats-based planner would avoid)."""
        if not ctx.is_device:
            return False

        def decide() -> bool:
            if self.build_threshold_bytes == 0:
                get_registry().inc("mesh_join_partitioned")
                ctx.trace_event(
                    "aqe.replan", "aqe", node=self.node_desc(),
                    build_bytes=-1, threshold=0, decision="partitioned")
                return True
            # cheap probe: sum bytes over the drained batch list (no
            # concat, no build prep); the list is ctx-cached so the
            # chosen path reuses it instead of draining again
            batches = drain_cached(ctx, self.children[1])
            nbytes = sum(getattr(x, "nbytes", 0)
                         for b in batches
                         for x in jax.tree_util.tree_leaves(b))
            partitioned = nbytes > self.build_threshold_bytes
            # the mesh analog of plan/adaptive.py's broadcast switch:
            # record the measured-size strategy pick on the trace (no
            # aqe_* counter — this is the static mesh join's built-in
            # decision, not a stage-boundary re-plan) and on the counter
            # registry (EXPLAIN ANALYZE renders these next to
            # mesh_all_to_all_bytes)
            reg = get_registry()
            if partitioned:
                reg.inc("mesh_join_partitioned")
            else:
                reg.inc("mesh_join_replicated")
                reg.inc("mesh_join_broadcast_bytes", float(nbytes))
            ctx.trace_event(
                "aqe.replan", "aqe", node=self.node_desc(),
                build_bytes=int(nbytes),
                threshold=int(self.build_threshold_bytes),
                decision="partitioned" if partitioned else "replicated")
            return partitioned
        return ctx.cached((id(self), "mesh_join_partitioned"), decide)

    # -- region interior -----------------------------------------------
    def _region_build(self, ctx: ExecCtx, mesh):
        """A replicated-mode region's build side: the ctx-cached
        ``(rb2, rkeys, prep)`` of :meth:`JoinExec._build_device` — prepared
        ONCE, outside the program, by the code the one-chip executor
        uses, and read again by the lost-slice fallback's island path —
        with ``(rb2, prep)`` placed replicated over ``mesh`` (the
        torrent-broadcast analog: a device-to-device copy per chip, no
        collective in the program).  Returns ``((rb2, prep), probe)``,
        ``probe`` the static ``(kind, packed, rkeys)`` the body is built
        and keyed by."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        rb2, rkeys, prep = self._build_device(ctx)
        kind, _ = probe_selected(prep, rkeys)

        def rep():
            return jax.device_put((rb2, prep), NamedSharding(mesh, P()))
        placed = ctx.cached((id(self), "mesh_region_build", id(mesh)), rep)
        return placed, (kind, isinstance(prep, PackedBuild), rkeys)

    def _region_step(self, mode: str, out_cap: int,
                     send_capacity: int | None = None, probe=None):
        """Per-device traceable join body for MeshRegionExec interiors:
        ``(stream_local, build) -> (joined, (total, flags))``.

        ``mode`` is the host-side replicated/partitioned pick
        (_use_partitioned).  **Replicated:** ``build`` is the replicated
        ``(rb2, prep)`` of :meth:`_region_build` and ``probe`` its static
        ``(kind, packed, rkeys)``; the body augments the stream shard and
        runs the probe ``kind`` names — the one-chip executor's own
        selection and probes (exec/joins.probe_selected / probe_traced):
        a table read by address for dense keys, a merge into the sorted
        keys otherwise, the sort path for keys that cannot be prepared.
        No collective, no sort of the build in the program.
        **Partitioned:** ``build`` is this device's raw build shard; BOTH
        key exchanges run as in-program all-to-alls (reusing the
        eagerly-built MeshExchangeExec steps, so partition ids are
        Spark-bit-exact and co-partitioning is guaranteed by
        construction), and the co-partitioned shards join by the sort
        path: a build shard that exists only inside the program cannot
        be prepared outside it.  Either way the output is gathered by the
        expanding plan of ops/join.py, the one-chip executor's.

        ``out_cap`` is the STATIC join output capacity — a host sync of
        the probe total is impossible inside shard_map, so the region
        launcher guesses, reads the returned ``total`` in ONE stacked
        aux fetch, and retries at the rounded-up measured capacity when
        the guess was short (the output is discarded, never truncated
        silently).  ``flags`` carries the bounded-send-buffer overflow
        bits of the partitioned exchanges (empty when replicated)."""
        jt = self.join_type
        n_right_raw = len(self.children[1].output_schema.fields)

        def step(sb: ColumnBatch, build):
            flags = ()
            prep = None
            if mode == "partitioned":
                sb, s_ovf = self._exchanges[0]._local_step(send_capacity)(sb)
                bb, b_ovf = self._exchanges[1]._local_step(send_capacity)(
                    build)
                flags = (s_ovf, b_ovf)
                rb2, rkeys = self._augment_device(bb, self._rkeys_b)
            else:
                rb2, prep = build
                rkeys = probe[2]
            lb2, lkeys = self._augment_device(sb, self._lkeys_b)
            kind, lkey = probe_selected(prep, lkeys)
            probe_arrays, total = probe_traced(kind, lb2, rb2, prep, lkey,
                                               rkeys, jt)
            plan = join_indices_from_probe(lb2.capacity, probe_arrays, jt,
                                           out_cap)
            kf = T.Schema(list(lb2.schema.fields)
                          + (list(rb2.schema.fields)
                             if self.include_right else []))
            out = gather_join_output(lb2, rb2, *plan, kf,
                                     self.include_right)
            out = self._project_out(out, sb.num_columns, lb2.num_columns,
                                    n_right_raw, device=True)
            if self._condition is not None:
                c = eval_device(self._cond_b, out)
                out = dk.compact(out, c.data & c.validity)
            if self._swapped and self.include_right:
                out = self._reorder_device(out, sb.num_columns)
            out = ColumnBatch(out.columns, out.num_rows, self._schema)
            return out, (total, flags)

        return step

    def _region_step_key_parts(self, mode: str, out_cap: int,
                               send_capacity: int | None = None,
                               probe=None) -> tuple:
        """Fragment-key material for the in-region join body (the region
        key composes these per member; mesh part added by the builder):
        a replicated body is keyed by the probe it was built for."""
        parts = ("mesh_join", mode, probe, out_cap, self.join_type,
                 self._swapped,
                 tuple(self._lkeys_b), tuple(self._rkeys_b),
                 self.children[0].output_schema,
                 self.children[1].output_schema,
                 self._cond_b if self._condition is not None else None,
                 self._schema, self.mesh_size)
        if mode == "partitioned":
            parts = parts + self._exchanges[0]._step_key_parts(send_capacity)
            parts = parts + self._exchanges[1]._step_key_parts(send_capacity)
        return parts

    def _materialize(self, ctx: ExecCtx, which: int):
        # route through the shared drained-list cache so the size probe
        # and the replicated build share one drain of the build child
        if ctx.is_device:
            child = self.children[which]
            return concat_or_empty(drain_cached(ctx, child),
                                   child.output_schema)
        return super()._materialize(ctx, which)

    def _device_build(self, ctx: ExecCtx, pid: int):
        if not self._use_partitioned(ctx):
            return MeshJoinExec._device_build_replicated(self, ctx, pid)

        def build():
            rb = concat_or_empty(
                list(self._exchanges[1].partition_iter(ctx, pid)),
                                 self.children[1].output_schema)
            rb2, rkeys = self._augment_device(rb, self._rkeys_b)
            return rb2, rkeys, self._prepare_build(rb2, rkeys)
        return ctx.cached((id(self), "mesh_part_build", pid), build)

    def _device_build_replicated(self, ctx: ExecCtx, pid: int):
        rb2, rkeys, prep = self._build_device(ctx)
        devs = self._shard_devices(ctx)
        d = devs[pid % len(devs)]

        def rep():
            return (jax.device_put(rb2, d), rkeys,
                    None if prep is None else jax.device_put(prep, d))
        return ctx.cached((id(self), "mesh_build", repr(d)), rep)

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        fn = JoinExec.partition_iter
        fn = getattr(fn, "__wrapped__", fn)
        yield from self._aligned(fn(self, ctx, pid))

    def node_desc(self) -> str:
        jt = "right" if self._swapped else self.join_type
        return f"MeshJoinExec[{jt}, mesh={self.mesh_size}]"
