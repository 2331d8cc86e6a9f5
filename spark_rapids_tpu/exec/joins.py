"""Join execs over the sort-merge device kernel.

Reference join surface (SURVEY.md §2.4): GpuShuffledHashJoinExec /
GpuBroadcastHashJoinExec (GpuHashJoin.doJoin, shims/spark300/
GpuHashJoin.scala:193-249), GpuBroadcastNestedLoopJoinExec and
GpuCartesianProductExec (crossJoin + condition filter), with
GpuSortMergeJoinMeta replacing SMJ by shuffled hash join.  Here one
`JoinExec` covers the equi-join types over ops/join.py's sort-merge
kernel, and `CrossJoinExec` the nested-loop/cartesian shape; right outer
runs as a side-swapped left outer (the reference's build-side flip).

Conditions: like the reference's tagJoin (GpuHashJoin.scala:30-45), a
residual non-equi condition is only allowed on inner/cross joins, where
it is applied as a post-join filter.
"""
from __future__ import annotations

from functools import partial
from typing import Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch, round_capacity
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.exec.compile_cache import guarded_jit
from spark_rapids_tpu.exec.core import ExecCtx, PlanNode, fetch_to_host
from spark_rapids_tpu.expr.core import (BoundReference, Expression, bind,
                                        eval_device, eval_host)
from spark_rapids_tpu.host.batch import HostBatch
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.ops import kernels as dk
from spark_rapids_tpu.ops import host_kernels as hk
from spark_rapids_tpu.ops.join import (JOIN_TYPES, DirectBuild, PackedBuild,
                                       build_direct_table, build_key_stats,
                                       build_prepare_fast,
                                       build_prepare_packed,
                                       direct_table_size, gather_join_output,
                                       join_indices_from_probe, join_probe,
                                       matched_build_rows, packed_key_span,
                                       probe_counts, probe_direct, probe_fast,
                                       probe_merges)

__all__ = ["JoinExec", "CrossJoinExec", "BroadcastHashJoinExec"]

#: span of this operator's blocking total fetches (exec/core.fetch_to_host)
_FETCH = "fetch@JoinExec"


def _probed(lb, probe_arrays, total):
    """What each probe program returns: the probe's arrays without the
    None placeholder of a join that is not full outer (pytree-stable
    output) and ``[total, aligned]`` (ops/join.probe_counts), the one
    array a flush fetches of each batch."""
    if probe_arrays[-1] is None:
        probe_arrays = probe_arrays[:-1]
    return probe_arrays, probe_counts(probe_arrays[3], lb.row_mask(), total)


def probe_selected(prep, lkeys: tuple) -> tuple:
    """``(kind, lkey)``: THE probe selection, from what the prepared build
    side IS (:func:`prepare_fast_build`) and nothing else: ``sorted`` for
    None (the sort path over all the keys: a string, fractional or
    boolean key, keys that cannot be packed), ``direct`` for a
    :class:`DirectBuild` (read by address), ``search`` for sorted keys
    (merged or stepped through); a :class:`PackedBuild` takes all the key
    columns, any other prepared build its one.  The one-chip executor
    picks its probe program by it and a mesh region's join body its probe
    (exec/mesh_exec.py), so the two cannot drift apart."""
    if prep is None:
        return "sorted", lkeys
    build, packing = _unpacked(prep)
    kind = "direct" if isinstance(build, DirectBuild) else "search"
    return kind, (lkeys[0] if packing is None else lkeys)


def probe_traced(kind: str, lb, rb, prep, lkey, rkeys, join_type):
    """The probe ``kind`` names (:func:`probe_selected`), traceable:
    ``(probe_arrays, total)``.  The three one-chip probe programs are this
    under their names, each with its kind fixed; a mesh region's join
    body traces it inside its ``shard_map`` program.  ``rb`` and
    ``rkeys`` serve the sort path alone, ``prep`` the other two."""
    if kind == "sorted":
        return join_probe(lb, rb, lkey, rkeys, join_type)
    build, packing = _unpacked(prep)
    if kind == "direct":
        return probe_direct(lb, lkey, build, join_type, packing)
    return probe_fast(lb, lkey, *build, join_type, packing)


@guarded_jit("join_probe", static_argnames=("lkeys", "rkeys", "join_type"))
def _jit_probe(lb, rb, lkeys, rkeys, join_type):
    """Heavy rank-path phase (all sorts): compiled once per capacity pair."""
    return _probed(lb, *probe_traced("sorted", lb, rb, None, lkeys, rkeys,
                                     join_type))


@guarded_jit("join_build_prep", static_argnames=("rkey",))
def _jit_build_prep(rb, rkey):
    """``rkey``: one key column, or a tuple of them to pack into one."""
    if isinstance(rkey, tuple):
        prep, packing = build_prepare_packed(rb, rkey)
        return (PackedBuild(prep, packing),
                build_key_stats(prep[0], prep[2], packing))
    prep = build_prepare_fast(rb, rkey)
    return prep, build_key_stats(prep[0], prep[2])


@guarded_jit("join_build_table", static_argnames=("size",))
def _jit_build_table(prep, size):
    return build_direct_table(*prep[:3], size)


def _unpacked(prep) -> tuple:
    """``(build, packing)`` of a prepared build side; the packing is None
    where it was prepared from one key."""
    return prep if isinstance(prep, PackedBuild) else (prep, None)


@guarded_jit("join_probe_fast", static_argnames=("lkey", "join_type"))
def _jit_probe_fast(lb, prep, lkey, join_type):
    return _probed(lb, *probe_traced("search", lb, None, prep, lkey, None,
                                     join_type))


@guarded_jit("join_probe_direct", static_argnames=("lkey", "join_type"))
def _jit_probe_direct(lb, prep, lkey, join_type):
    return _probed(lb, *probe_traced("direct", lb, None, prep, lkey, None,
                                     join_type))


def prepare_fast_build(rb, rkeys: tuple):
    """Prepare a build side for the streaming probe, once per build:
    sort it by its key (several integral keys packed into one,
    ops/join.build_prepare_packed), look at the keys it holds (``nv``,
    each key's smallest and largest: ONE blocking fetch) and, where they
    are dense (ops/join.direct_table_size), make the direct-address
    table.  Returns a :class:`DirectBuild` for ``join_probe_direct`` or
    the sorted ``(sorted_key, perm, nv, run_len)`` for
    ``join_probe_fast`` (which merges each stream batch into the sorted
    keys, or steps through them where the batch is far smaller than the
    build: ops/join.probe_merges), inside a :class:`PackedBuild` where
    the keys were packed (all pytrees of device arrays), or None where
    several keys span more than an ``int64`` holds: that build stays on
    the sort path."""
    prep, stats = _jit_build_prep(
        rb, rkeys if len(rkeys) > 1 else rkeys[0])
    build, packing = _unpacked(prep)
    # enginelint: disable=RL003 (once per build, before any stream batch: the probe's program is chosen from it)
    nv, *ranges = (int(x) for x in fetch_to_host(stats, _FETCH))
    get_registry().inc("join.build.rows", nv)
    if packing is not None:
        span = packed_key_span(nv, ranges)
        if span is None:
            return None
        get_registry().inc("join.keys.packed")
        ranges = (0, span - 1)
    size = direct_table_size(nv, *ranges, rb.capacity)
    if size is None:
        return prep
    get_registry().inc("join.build.table_entries", size)
    table = _jit_build_table(build, size)
    return table if packing is None else PackedBuild(table, packing)


@guarded_jit("join_gather",
             static_argnames=("cl", "join_type", "out_cap", "include_right",
                              "schema", "track_matched", "aligned"))
def _jit_gather(lb, rb, probe_arrays, cl, join_type, out_cap, include_right,
                schema, track_matched=False, aligned=False):
    """Light phase (gathers only): re-specialized per output capacity and
    per plan (ops/join.join_indices_from_probe).  ``aligned``: the batch's
    fetched flag said every live stream row comes out exactly once, so the
    stream's columns are sliced, not gathered, and only ``perm[start]`` and
    the build's stacks move: 34 ms a launch on the chip where the
    expanding plan takes 99 at 2^20 slots x 9 columns (37 against 101 in
    q93's collects; PERF.md section 6 PR 43).  Any other batch takes the expanding plan; the output is
    the same batch array for array whichever ran."""
    if len(probe_arrays) == 4:
        probe_arrays = probe_arrays + (None,)
    plan = join_indices_from_probe(cl, probe_arrays, join_type, out_cap,
                                   aligned=aligned)
    out = gather_join_output(lb, rb, *plan, schema, include_right)
    if track_matched:
        li, ri, l_take, r_take, total = plan
        return out, matched_build_rows(ri, r_take, rb.capacity)
    return out


def _nullable_schema(s: T.Schema) -> list[T.StructField]:
    return [T.StructField(f.name, f.data_type, True) for f in s]


class JoinExec(PlanNode):
    """Equi-join: inner | left | right | full | semi | anti.

    ``left_keys``/``right_keys`` are expressions over the respective
    child schemas (the planner has already inserted casts so each pair
    has equal types).  Key expressions are appended as projected columns
    before the kernel and dropped from the output, so non-trivial keys
    (e.g. casts) join correctly.
    """

    #: stream batches whose probe totals sync to host in one stacked
    #: device_get (see _run_device_stream)
    _SYNC_CHUNK = 8

    def __init__(self, left: PlanNode, right: PlanNode,
                 left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression],
                 join_type: str, condition: Expression | None = None):
        user_join_type = join_type  # pre-swap, for user-facing errors
        if join_type == "right":
            # run as side-swapped left join; output reordered in
            # partition_iter (reference build-side flip)
            self._swapped = True
            left, right = right, left
            left_keys, right_keys = right_keys, left_keys
            join_type = "left"
        else:
            self._swapped = False
        assert join_type in JOIN_TYPES and join_type != "cross", join_type
        if condition is not None and join_type != "inner":
            raise ValueError(
                f"non-equi condition not supported for {user_join_type} "
                "join (reference tagJoin, GpuHashJoin.scala:30-45)")
        super().__init__([left, right])
        from spark_rapids_tpu.expr.misc import reject_partition_aware
        reject_partition_aware(list(left_keys) + list(right_keys)
                               + [condition], "join keys/conditions")
        self.join_type = join_type
        self._lkeys_b = [bind(k, left.output_schema) for k in left_keys]
        self._rkeys_b = [bind(k, right.output_schema) for k in right_keys]
        assert len(self._lkeys_b) == len(self._rkeys_b) and self._lkeys_b
        for a, b in zip(self._lkeys_b, self._rkeys_b):
            if type(a.dtype) is not type(b.dtype):
                raise ValueError(f"join key type mismatch: {a.dtype} vs "
                                 f"{b.dtype} (planner must insert casts)")
            if isinstance(a.dtype, T.ArrayType):
                raise ValueError("cannot join on an array column")
        self.include_right = join_type not in ("semi", "anti")

        lf = list(left.output_schema.fields)
        rf = list(right.output_schema.fields)
        if join_type == "full":
            lf, rf = _nullable_schema(left.output_schema), \
                _nullable_schema(right.output_schema)
        elif join_type == "left":
            rf = _nullable_schema(right.output_schema)
        joined = lf + rf if self.include_right else lf
        if self._swapped and self.include_right:
            joined = joined[len(lf):] + joined[:len(lf)]
        self._schema = T.Schema(joined)

        self._condition = condition
        if condition is not None:
            cond_schema = T.Schema(list(left.output_schema.fields)
                                   + list(right.output_schema.fields))
            self._cond_b = bind(condition, cond_schema)

    @property
    def output_schema(self) -> T.Schema:
        return self._schema

    @property
    def bound_exprs(self):
        out = list(self._lkeys_b) + list(self._rkeys_b)
        if self._condition is not None:
            out.append(self._cond_b)
        return out

    def num_partitions(self, ctx: ExecCtx) -> int:
        # stream-side partitioning is preserved (per-left-row join types);
        # full outer needs one pass to emit unmatched build rows at the end
        if self.join_type == "full":
            return 1
        return self.children[0].num_partitions(ctx)

    # ------------------------------------------------------------------
    def _augment_device(self, batch: ColumnBatch, keys) -> tuple:
        """Append evaluated key columns; return (batch', key_indices).

        Also traced inside mesh-region programs (MeshJoinExec._region_step
        runs it under shard_map): must stay free of host syncs and of
        control flow on traced values."""
        n = batch.num_columns
        cols = list(batch.columns)
        fields = list(batch.schema.fields)
        idx = []
        for i, k in enumerate(keys):
            if isinstance(k, BoundReference):
                idx.append(k.index)
                continue
            v = eval_device(k, batch)
            cols.append(v)
            fields.append(T.StructField(f"_jk{i}", k.dtype, True))
            idx.append(len(cols) - 1)
        return ColumnBatch(cols, batch.num_rows, T.Schema(fields)), tuple(idx)

    def _augment_host(self, batch: HostBatch, keys) -> tuple:
        cols = list(batch.columns)
        fields = list(batch.schema.fields)
        idx = []
        for i, k in enumerate(keys):
            if isinstance(k, BoundReference):
                idx.append(k.index)
                continue
            v = eval_host(k, batch)
            cols.append(v)
            fields.append(T.StructField(f"_jk{i}", k.dtype, True))
            idx.append(len(cols) - 1)
        return HostBatch(cols, T.Schema(fields)), tuple(idx)

    def _materialize(self, ctx: ExecCtx, which: int):
        from spark_rapids_tpu.exec.core import drain_partitions
        child = self.children[which]
        batches = list(drain_partitions(ctx, child))
        if ctx.is_device:
            if not batches:
                from spark_rapids_tpu.exec.core import host_to_device
                return host_to_device(HostBatch.empty(child.output_schema))
            return dk.concat_batches(batches) if len(batches) > 1 \
                else batches[0]
        if not batches:
            return HostBatch.empty(child.output_schema)
        return hk.host_concat(batches)

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        if ctx.is_device:
            yield from self._run_device_stream(ctx, pid)
        else:
            rb = ctx.cached((id(self), "host_build"),
                            lambda: self._materialize(ctx, 1))
            child = self.children[0]
            pids = range(child.num_partitions(ctx)) \
                if self.join_type == "full" else [pid]
            batches = [b for p in pids for b in child.partition_iter(ctx, p)]
            lb = hk.host_concat(batches) if batches \
                else HostBatch.empty(child.output_schema)
            yield from self._run_host(ctx, lb, rb)

    # ------------------------------------------------------------------
    # Device path: build side prepared once (sorted keys, and a
    # direct-address table where they are dense: prepare_fast_build;
    # reference GpuHashJoin's build-side table, GpuHashJoin.scala:193-249),
    # then the stream side is joined PER BATCH — no whole-side concat, no
    # per-batch sort on the fast path.
    def _use_fast_path(self) -> bool:
        """Every key integral (a cross join has none)."""
        def integral(lt, rt):
            return (not lt.fractional and not rt.fractional
                    and not isinstance(lt, (T.StringType, T.BooleanType))
                    and not isinstance(rt, (T.StringType, T.BooleanType))
                    and type(lt) is type(rt))
        return bool(self._lkeys_b) and all(
            integral(a.dtype, b.dtype)
            for a, b in zip(self._lkeys_b, self._rkeys_b))

    def _prepare_build(self, rb2: ColumnBatch, rkeys: tuple):
        """The build side prepared for the streaming probes, or None for
        the sort path: a key that is a string, fractional or boolean, or
        several keys whose spans' product no ``int64`` holds."""
        prep = prepare_fast_build(rb2, rkeys) \
            if self._use_fast_path() else None
        if prep is None and len(rkeys) > 1:
            get_registry().inc("join.keys.unpackable")
        return prep

    def _build_device(self, ctx: ExecCtx):
        def build():
            rb = self._materialize(ctx, 1)
            rb2, rkeys = self._augment_device(rb, self._rkeys_b)
            return rb2, rkeys, self._prepare_build(rb2, rkeys)
        return ctx.cached((id(self), "build"), build)

    def _stream_batches(self, ctx: ExecCtx, pid: int):
        """Stream-side batches for one output partition (hook:
        MeshJoinExec serves device-placed shards instead)."""
        child = self.children[0]
        pids = range(child.num_partitions(ctx)) \
            if self.join_type == "full" else [pid]
        for lpid in pids:
            yield from child.partition_iter(ctx, lpid)

    def _device_build(self, ctx: ExecCtx, pid: int):
        """(build batch, key idx, prep) for one output partition (hook:
        MeshJoinExec replicates the build side onto partition devices)."""
        return self._build_device(ctx)

    def _run_device_stream(self, ctx: ExecCtx, pid: int):
        rb2, rkeys, prep = self._device_build(ctx, pid)
        jt = self.join_type
        stream_jt = "left" if jt == "full" else jt
        n_right_raw = len(self.children[1].output_schema.fields)
        kf = (list(self._stream_aug_fields())
              + (list(rb2.schema.fields) if self.include_right else []))
        kf_schema = T.Schema(kf)
        matched = None

        # Probe counts sync in CHUNKS: each stream batch's match count
        # (and beside it whether every row came out once: the aligned
        # gather plan) must reach the host to pick the static gather
        # capacity, but a
        # host round trip is pure latency with the device idle behind
        # it — so up to _SYNC_CHUNK probes are dispatched
        # asynchronously and their totals fetched in ONE device_get of
        # a stacked vector (one barrier per chunk, not per batch).
        # Each pending entry retains its stream batch: an OOM surfacing
        # at the stacked sync (where async backends report it) is
        # recovered by re-probing from the retained batches through the
        # splitting retry scope — a split stream batch just produces
        # two gathers instead of one.
        def probe(piece):
            lb2, lkeys = self._augment_device(piece, self._lkeys_b)
            if jt == "cross":
                get_registry().inc("join.cross.launches")
            elif jt in ("semi", "anti"):
                get_registry().inc("join.semi.batches")
            kind, lkey = probe_selected(prep, lkeys)
            if jt != "cross":
                get_registry().inc(f"join.probe.{kind}")
            if kind == "sorted":
                probe_arrays, counts_dev = _jit_probe(
                    lb2, rb2, lkeys, rkeys, stream_jt)
            else:
                sorted_key = _unpacked(prep)[0][0]
                if kind == "search" and probe_merges(lb2.capacity,
                                                     sorted_key.shape[0]):
                    get_registry().inc("join.probe.search.merged")
                run = _jit_probe_direct if kind == "direct" \
                    else _jit_probe_fast
                probe_arrays, counts_dev = run(lb2, prep, lkey, stream_jt)
            return lb2, counts_dev, probe_arrays

        def probe_entries(lb) -> list:
            return [(piece, l2, cd, pa) for piece, (l2, cd, pa)
                    in ctx.dispatch_retry(probe, lb, op="join_probe",
                                          pairs=True)]

        def flush(pending):
            nonlocal matched
            if not pending:
                return

            def redo() -> None:
                pending[:] = [e for p in pending
                              for e in probe_entries(p[0])]

            def sync_counts():
                """``[total, aligned]`` of every pending probe."""
                if len(pending) == 1:
                    # enginelint: disable=RL003 (single-entry fast path; one two-number sync)
                    return fetch_to_host(pending[0][2], _FETCH)[None]
                # enginelint: disable=RL003 (stacked transfer for all pending probes; this IS the batched sync)
                return fetch_to_host(ctx.dispatch(
                    jnp.stack, [p[2] for p in pending]), _FETCH)

            counts = [(int(t), bool(a)) for t, a in ctx.retry_sync(
                sync_counts, redo=redo, op="join_flush")]
            get_registry().inc_many((
                ("join.probe.rows_out", sum(t for t, _ in counts)),
                ("join.gather.aligned", sum(a for t, a in counts if t))))
            for (lb, lb2, _cd, probe_arrays), (total, aligned) in zip(
                    pending, counts):
                if total == 0:
                    if jt == "full" and matched is None:
                        matched = jnp.zeros(rb2.capacity, jnp.bool_)
                    continue
                out_cap = round_capacity(max(total, 1))
                gather = partial(
                    ctx.dispatch, _jit_gather, lb2, rb2, probe_arrays,
                    lb2.capacity, stream_jt, out_cap, self.include_right,
                    kf_schema, aligned=aligned)
                if jt == "full":
                    out, bm = gather(track_matched=True)
                    matched = bm if matched is None else matched | bm
                else:
                    out = gather()
                out = self._project_out(
                    out, lb.num_columns, lb2.num_columns, n_right_raw,
                    device=True)
                if self._condition is not None:
                    dk.count_compaction(out.capacity)
                    out = self._condition_jit()(out)
                if self._swapped and self.include_right:
                    out = self._reorder_device(out, lb.num_columns)
                # the fetched total IS the gather's row count unless a
                # residual condition filtered after it
                yield ColumnBatch(out.columns, out.num_rows, self._schema,
                                  known_rows=total if self._condition is None
                                  else None)

        pending = []
        for lb in self._stream_batches(ctx, pid):
            pending.extend(probe_entries(lb))
            if len(pending) >= self._SYNC_CHUNK:
                yield from flush(pending)
                pending = []
        yield from flush(pending)
        if jt == "full":
            if matched is None:
                matched = jnp.zeros(rb2.capacity, jnp.bool_)
            dk.count_compaction(rb2.capacity)
            tail = self._unmatched_right_jit()(rb2, matched)
            unmatched = tail.host_num_rows(_FETCH)
            get_registry().inc("join.full.unmatched_rows", unmatched)
            if unmatched > 0:
                yield tail

    def _stream_aug_fields(self):
        """Fields of an augmented stream batch (left schema + appended
        non-BoundReference key columns)."""
        fields = list(self.children[0].output_schema.fields)
        for i, k in enumerate(self._lkeys_b):
            if not isinstance(k, BoundReference):
                fields.append(T.StructField(f"_jk{i}", k.dtype, True))
        return fields

    def _condition_jit(self):
        if not hasattr(self, "_cond_jit"):
            from spark_rapids_tpu.exec import compile_cache as cc

            def filt(out):
                c = eval_device(self._cond_b, out)
                return dk.compact(out, c.data & c.validity)
            self._cond_jit = cc.shared_jit(
                cc.fragment_key("join_cond", self._cond_b), filt,
                name="join_post_filter")
        return self._cond_jit

    def _unmatched_right_jit(self):
        """Full outer tail: build rows never matched by any stream batch,
        null-extended on the left (reference fullJoin's right coverage)."""
        if not hasattr(self, "_unmatched_jit"):
            left_fields = list(self.children[0].output_schema.fields)
            right_schema = self.children[1].output_schema
            n_right = len(right_schema.fields)

            def fn(rb2, matched):
                keep = rb2.row_mask() & ~matched
                rraw = ColumnBatch(rb2.columns[:n_right], rb2.num_rows,
                                   right_schema)
                rc = dk.compact(rraw, keep)
                cap = rb2.capacity
                null_cols = []
                for f in left_fields:
                    validity = jnp.zeros(cap, jnp.bool_)
                    if isinstance(f.data_type,
                                  (T.StringType, T.ArrayType)):
                        elem = np.uint8 if isinstance(
                            f.data_type, T.StringType) \
                            else f.data_type.np_dtype
                        null_cols.append(DeviceColumn(
                            jnp.zeros((cap, 1), elem), validity,
                            f.data_type, jnp.zeros(cap, jnp.int32)))
                    else:
                        null_cols.append(DeviceColumn(
                            jnp.zeros(cap, f.data_type.np_dtype), validity,
                            f.data_type))
                return ColumnBatch(null_cols + list(rc.columns),
                                   rc.num_rows, self._schema)

            from spark_rapids_tpu.exec import compile_cache as cc
            self._unmatched_jit = cc.shared_jit(
                cc.fragment_key("join_unmatched", left_fields, right_schema,
                                self._schema), fn,
                name="join_unmatched_right")
        return self._unmatched_jit

    def _run_host(self, ctx: ExecCtx, lb: HostBatch, rb: HostBatch):
        lb2, lkeys = self._augment_host(lb, self._lkeys_b)
        rb2, rkeys = self._augment_host(rb, self._rkeys_b)
        li, ri, lt, rt = hk.host_join(lb2, rb2, list(lkeys), list(rkeys),
                                      self.join_type)
        kf = (list(lb2.schema.fields)
              + (list(rb2.schema.fields) if self.include_right else []))
        out = hk.host_join_output(lb2, rb2, li, ri, lt, rt, T.Schema(kf),
                                  self.include_right)
        out = self._project_out(out, lb.num_columns, lb2.num_columns,
                                rb.num_columns, device=False)
        if self._condition is not None:
            c = eval_host(self._cond_b, out)
            out = hk.host_filter(out, c.data.astype(np.bool_) & c.validity)
        cols = list(out.columns)
        if self._swapped and self.include_right:
            nl = lb.num_columns
            cols = cols[nl:] + cols[:nl]
        yield HostBatch(cols, self._schema)

    def _project_out(self, out, n_left_raw: int, n_left_aug: int,
                     n_right_raw: int, device: bool):
        """Drop appended key columns from the kernel output.

        The device branch is traced inside mesh-region programs; keep the
        column selection static (pure python ints, no traced values)."""
        keep = list(range(n_left_raw))
        if self.include_right:
            keep += [n_left_aug + i for i in range(n_right_raw)]
        cols = [out.columns[i] for i in keep]
        fields = [out.schema.fields[i] for i in keep]
        if device:
            return ColumnBatch(cols, out.num_rows, T.Schema(fields))
        return HostBatch(cols, T.Schema(fields))

    def _reorder_device(self, out: ColumnBatch, nl: int) -> ColumnBatch:
        cols = list(out.columns)
        cols = cols[nl:] + cols[:nl]
        return ColumnBatch(cols, out.num_rows, self._schema)

    def node_desc(self) -> str:
        jt = "right" if self._swapped else self.join_type
        return f"JoinExec[{jt}, keys={len(self._lkeys_b)}]"


class BroadcastHashJoinExec(JoinExec):
    """Broadcast-build equi-join: the build child is a single-partition
    node (BroadcastExchangeExec) materialized whole, the stream side is
    probed per batch with no shuffle (reference GpuBroadcastHashJoinExec).

    Execution is exactly JoinExec's device/host paths — the build side's
    ``_materialize`` drains one broadcast partition instead of a shuffled
    exchange.  Exists as its own class so AQE's shuffle-join -> broadcast
    switch is visible in EXPLAIN (ANALYZE) and so plan fingerprints stay
    honest about the strategy that actually ran."""

    @classmethod
    def from_shuffled(cls, join: JoinExec, probe: PlanNode,
                      build: PlanNode) -> "BroadcastHashJoinExec":
        """Re-strategize an existing JoinExec around (probe, build)
        children without re-binding: key expressions were bound against
        the child SCHEMAS, which the new children preserve — so the
        compile-cache fragment keys (join_cond/join_unmatched) and the
        guarded-jit structural keys are byte-identical to the static
        plan's, and a warm rerun of the re-planned query compiles
        nothing."""
        nj = object.__new__(cls)
        nj.__dict__.update(join.__dict__)
        # lazily-built jit wrappers close over the originating node; let
        # the new node rebuild its own (same fragment keys -> cache hits)
        nj.__dict__.pop("_cond_jit", None)
        nj.__dict__.pop("_unmatched_jit", None)
        nj.children = (probe, build)
        return nj

    def node_desc(self) -> str:
        jt = "right" if self._swapped else self.join_type
        return f"BroadcastHashJoinExec[{jt}, keys={len(self._lkeys_b)}]"


class CrossJoinExec(JoinExec):
    """Cartesian product with optional condition (reference
    GpuCartesianProductExec / GpuBroadcastNestedLoopJoinExec)."""

    def __init__(self, left: PlanNode, right: PlanNode,
                 condition: Expression | None = None):
        PlanNode.__init__(self, [left, right])
        self._swapped = False
        self.join_type = "cross"
        self._lkeys_b = []
        self._rkeys_b = []
        self.include_right = True
        self._schema = T.Schema(list(left.output_schema.fields)
                                + list(right.output_schema.fields))
        self._condition = condition
        if condition is not None:
            self._cond_b = bind(condition, self._schema)

    def node_desc(self) -> str:
        return "CrossJoinExec" + (
            "[cond]" if self._condition is not None else "")
