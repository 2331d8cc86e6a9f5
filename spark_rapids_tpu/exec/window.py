"""Window exec: evaluate window expressions over sorted partitions.

Reference: GpuWindowExec (GpuWindowExec.scala:92, doExecuteColumnar:130)
— requires a single batch per partition group and lowers to cuDF rolling
windows.  Here the whole input is materialized (RequireSingleBatch, like
the reference's child goal), sorted once by (partition keys, order
keys), and every window expression is computed from the shared
SegmentInfo arrays (ops/window.py).  Output rows are in sorted order
(Spark leaves window output order undefined).

All window expressions in one exec must share one WindowSpec — Spark's
planner creates one WindowExec per distinct spec, and the planner here
does the same.
"""
from __future__ import annotations

from functools import partial
from typing import Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.exec.core import ExecCtx, PlanNode, RequireSingleBatch
from spark_rapids_tpu.exec.compile_cache import fingerprint, guarded_jit
from spark_rapids_tpu.expr.core import (BoundReference, Expression, Literal,
                                        bind, eval_device, eval_host,
                                        output_name)
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr.window import (DenseRank, Lag, Lead, Rank,
                                          RowNumber, WindowExpression,
                                          window_agg_op)
from spark_rapids_tpu.host.batch import HostBatch, HostColumn
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.ops import cents
from spark_rapids_tpu.ops import host_kernels as hk
from spark_rapids_tpu.ops import kernels as dk
from spark_rapids_tpu.ops import window as W
from spark_rapids_tpu.ops.sort import SortOrder, sort_permutation

__all__ = ["WindowExec", "spec_key"]


def spec_key(spec) -> str:
    """A ``WindowSpec``'s content: the canonical fingerprint of its
    partition-by, order-by (direction and null order resolved) and frame.
    Window expressions are grouped into one ``WindowExec`` by this key,
    never by ``==`` or ``hash`` of the spec: an ``Expression`` hashes by
    identity, and its ``==`` builds an ``EqualTo`` node, which is
    truthy whatever it compares."""
    orders = tuple((o[0], o[1] if len(o) > 1 else True,
                    o[2] if len(o) > 2 else None) for o in spec.order_by)
    return fingerprint(tuple(spec.partition_by), orders,
                       spec.resolved_frame())


def _fn_desc(w: WindowExpression) -> tuple:
    """What ``_window_body`` needs of a window expression, as plain
    hashable data (a static argument of the jitted program):
    ``(kind,)`` for the ranking functions, ``("offset", rows, default)``
    for lead / lag, ``("agg", op, frame)`` for an aggregate."""
    f = w.function
    for cls, kind in ((RowNumber, "row_number"), (Rank, "rank"),
                      (DenseRank, "dense_rank")):
        if isinstance(f, cls):
            return (kind,)
    if isinstance(f, (Lead, Lag)):
        # Lag subclasses Lead: a lag reads backwards
        default = None
        if f.default is not None:
            assert isinstance(f.default, Literal)
            default = f.default.value
        return ("offset", -f.offset if isinstance(f, Lag) else f.offset,
                default)
    return ("agg", window_agg_op(f), w.spec.resolved_frame())


def _wexpr_dtype(w: WindowExpression, bound_input) -> T.DataType:
    """Output type computed from the BOUND function input (the raw
    WindowExpression.dtype needs resolved children)."""
    from spark_rapids_tpu.ops.segmented import AggSpec
    f = w.function
    if isinstance(f, A.AggregateFunction):
        op = window_agg_op(f)
        in_t = bound_input.dtype if bound_input is not None else T.LongType()
        return AggSpec(op, 0).result_type(in_t)
    if isinstance(f, (Lead, Lag)):
        return bound_input.dtype
    return f.dtype


class WindowExec(PlanNode):
    """Append one output column per window expression.

    ``window_exprs``: WindowExpression (optionally Alias-wrapped), all
    sharing the same WindowSpec.
    """

    def __init__(self, window_exprs: Sequence[Expression], child: PlanNode,
                 keys_partitioned: bool = False):
        super().__init__([child])
        # when the planner hash-partitioned the child on the window
        # partition keys, each child partition holds whole partition
        # groups and the window program runs per partition, preserving
        # upstream task parallelism (reference GpuWindowExec requires a
        # single batch only per partition GROUP, GpuWindowExec.scala:92;
        # collapsing the world was the round-3 scaling cliff)
        self._keys_partitioned = bool(keys_partitioned)
        from spark_rapids_tpu.expr.core import Alias
        self._names = [output_name(e) for e in window_exprs]
        self._wexprs: list[WindowExpression] = []
        for e in window_exprs:
            if isinstance(e, Alias):
                e = e.children[0]
            assert isinstance(e, WindowExpression), e
            self._wexprs.append(e)
        assert self._wexprs, "need at least one window expression"
        self.spec = self._wexprs[0].spec
        if len({spec_key(e.spec) for e in self._wexprs}) > 1:
            raise ValueError("one WindowExec handles one WindowSpec; "
                             "split plans per spec as Spark does")
        self._fns = tuple(_fn_desc(w) for w in self._wexprs)
        cs = child.output_schema
        # bind partition/order/function-input expressions against the child
        self._part_b = [bind(p, cs) for p in self.spec.partition_by]
        self._order_b = [(bind(o[0], cs), o[1] if len(o) > 1 else True,
                          o[2] if len(o) > 2 else None)
                         for o in self.spec.order_by]
        self._fn_inputs: list[Expression | None] = []
        for w in self._wexprs:
            f = w.function
            if isinstance(f, (Lead, Lag)):
                self._fn_inputs.append(bind(f.children[0], cs))
            elif isinstance(f, A.AggregateFunction) and f.input is not None:
                self._fn_inputs.append(bind(f.input, cs))
            else:
                self._fn_inputs.append(None)
        self._out_dtypes = [_wexpr_dtype(w, b)
                            for w, b in zip(self._wexprs, self._fn_inputs)]
        self._schema = T.Schema(
            list(cs.fields)
            + [T.StructField(n, dt, True)
               for n, dt in zip(self._names, self._out_dtypes)])

        # one launch's counters, all from what the host holds: the
        # aggregate frames answered by a scan; the sum / avg frames over
        # doubles, which the program takes over int64 cents (ops/cents.py;
        # a frame holding an addend that is not whole cents falls back,
        # inside the program, to adding doubles); and, where the batch
        # carries its row count (known_rows: an exchange's piece, an
        # aggregate's or a join's output, a projection of such), its rows
        # by total and by the operator's schema, window.rows@<input
        # types>><appended types>: what a roofline over rows and schema
        # needs
        aggs = [(fn, inp) for fn, inp in zip(self._fns, self._fn_inputs)
                if fn[0] == "agg"]
        self._launch_counts = [
            ("window.launches", 1),
            ("window.frames.scanned",
             sum(1 for fn, _ in aggs if W.frame_scans(fn[2]))),
            ("window.sum.cents",
             sum(1 for fn, inp in aggs if fn[1] in ("sum", "avg")
                 and inp.dtype.fractional))]
        self._rows_by_schema = "window.rows@{}>{}".format(
            ",".join(f.data_type.name for f in cs.fields),
            ",".join(dt.name for dt in self._out_dtypes))

    @property
    def output_schema(self) -> T.Schema:
        return self._schema

    @property
    def output_batching(self):
        # the bounded-memory global stream emits one batch per input
        # batch; only the grouped single-batch path guarantees one
        if self._global_streamable():
            return None
        return RequireSingleBatch

    def num_partitions(self, ctx: ExecCtx) -> int:
        if self._keys_partitioned:
            return self.children[0].num_partitions(ctx)
        return 1

    # ------------------------------------------------------------------
    def _global_streamable(self) -> bool:
        """True when the whole-input window can run as a bounded-memory
        two-pass stream: empty partition-by + empty order-by makes every
        row's frame the ENTIRE input, so plain aggregates reduce to one
        running state + a broadcast — no single giant batch (VERDICT r4
        item 10; the reference's contract is single batch per GROUP, not
        per world, GpuWindowExec.scala:92)."""
        if self.spec.partition_by or self.spec.order_by:
            return False
        for w, inp in zip(self._wexprs, self._fn_inputs):
            f = w.function
            if not isinstance(f, A.AggregateFunction):
                return False
            try:
                op = window_agg_op(f)
            except ValueError:
                return False
            if op not in ("sum", "count", "count_star", "min", "max",
                          "avg"):
                return False
            if inp is not None and (inp.dtype.np_dtype is None
                                    or isinstance(inp.dtype,
                                                  (T.StringType,
                                                   T.ArrayType))):
                return False
        return True

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        child = self.children[0]
        if ctx.is_device and not self._keys_partitioned \
                and self._global_streamable():
            it = self._stream_global(ctx)
            first = next(it, None)
            if first is not None:
                yield first
                yield from it
                return
            # empty input: fall through to the single-batch path so the
            # empty-schema contract stays identical
        if self._keys_partitioned:
            batches = list(child.partition_iter(ctx, pid))
            if not batches:
                return
        else:
            batches = []
            for p in range(child.num_partitions(ctx)):
                batches.extend(child.partition_iter(ctx, p))
        if ctx.is_device:
            # the gather's witnesses: inputs, concats into one batch, and
            # windows with no partition-by holding their whole input
            get_registry().inc_many((
                ("window.batches_in", len(batches)),
                ("window.concat", int(len(batches) > 1)),
                ("window.global", int(not self.spec.partition_by))))
            if not batches:
                from spark_rapids_tpu.exec.core import host_to_device
                big = host_to_device(HostBatch.empty(child.output_schema))
            else:
                big = dk.concat_batches(batches) if len(batches) > 1 \
                    else batches[0]
            yield self._run_device(big)
        else:
            big = hk.host_concat(batches) if batches \
                else HostBatch.empty(child.output_schema)
            yield self._run_host(big)

    # ------------------------------------------------------------------
    def _stream_global(self, ctx: ExecCtx) -> Iterator[ColumnBatch]:
        """Two-pass bounded-memory whole-input window: pass 1 streams
        child batches, folding each into an O(1) running state per
        expression and parking the batch SPILLABLE in the BufferCatalog
        (HBM -> host -> disk, so the working set never needs one giant
        batch); pass 2 un-parks each batch and appends the broadcast
        finals."""
        import jax
        import jax.numpy as jnp
        from spark_rapids_tpu.memory.catalog import (SpillableColumnarBatch,
                                                     SpillPriority)
        inputs = self._fn_inputs

        def update(b: ColumnBatch):
            """Per-wexpr state (sum, count, min, max, rows, cents, odd).
            Integral inputs accumulate in int64 (an f64 fold would round
            sums and extremes past 2^53 — the single-batch segment
            kernels are exact there, and the two paths must agree), and
            doubles that are whole cents also as ``cents``, with ``odd``
            counting the addends that are not (ops/cents.py): the sum is
            then the same double whatever batches the rows came in."""
            out = []
            real = b.row_mask()
            rows = jnp.sum(real, dtype=jnp.int64)
            z = jnp.zeros((), jnp.int64)
            for e in inputs:
                if e is None:
                    out.append((z, rows, z, z, rows, z, z))
                    continue
                c = eval_device(e, b)
                valid = c.validity & real
                acc = jnp.float64 if c.data.dtype.kind == "f" else jnp.int64
                x = jnp.where(valid, c.data, 0).astype(acc)
                cnt = jnp.sum(valid, dtype=jnp.int64)
                big = jnp.asarray(jnp.inf if acc == jnp.float64
                                  else jnp.iinfo(jnp.int64).max, acc)
                small = jnp.asarray(-jnp.inf if acc == jnp.float64
                                    else jnp.iinfo(jnp.int64).min, acc)
                xd = c.data.astype(acc)
                mn = jnp.min(jnp.where(valid, xd, big))
                mx = jnp.max(jnp.where(valid, xd, small))
                money, odd = z, z
                if acc == jnp.float64:
                    in_cents, whole = cents.as_cents(
                        jnp, x, min(cents.ROW_LIMIT,
                                    (1 << 62) // max(b.capacity, 1)))
                    money = jnp.sum(in_cents)
                    odd = jnp.sum(~whole, dtype=jnp.int64)
                out.append((jnp.sum(x), cnt, mn, mx, rows, money, odd))
            return tuple(out)

        def merge(a, b):
            def money(ma, mb, oa, ob):
                # a total past SUM_LIMIT is no longer given as cents, and
                # must not grow on towards int64's end
                m = ma + mb
                return m, oa + ob + (jnp.abs(m) >= cents.SUM_LIMIT)
            return tuple((sa + sb, ca + cb, jnp.minimum(mna, mnb),
                          jnp.maximum(mxa, mxb), ra + rb,
                          *money(ma, mb, oa, ob))
                         for (sa, ca, mna, mxa, ra, ma, oa),
                             (sb, cb, mnb, mxb, rb, mb, ob) in zip(a, b))

        if not hasattr(self, "_gs_jits"):
            from spark_rapids_tpu.exec import compile_cache as cc
            # update folds exactly `inputs`; merge captures nothing —
            # the pair is one process-wide entry keyed on the inputs
            self._gs_jits = cc.get_or_build(
                cc.fragment_key("window_gs_update", tuple(inputs)),
                lambda: (cc.instrument(jax.jit(update), "window_update"),
                         cc.instrument(jax.jit(merge), "window_merge")))
        upd_jit, merge_jit = self._gs_jits[:2]

        child = self.children[0]
        parked, state = [], None
        for p in range(child.num_partitions(ctx)):
            for b in child.partition_iter(ctx, p):
                # splitting retry scope: the state merge is associative,
                # so an OOMed update re-run over row-halves folds to the
                # identical state (reference withRetry over the
                # pre-process step, GpuWindowExec)
                for part in ctx.dispatch_retry(upd_jit, b,
                                               op="window_update"):
                    state = part if state is None \
                        else ctx.dispatch(merge_jit, state, part)
                parked.append(SpillableColumnarBatch(
                    b, ctx.catalog, SpillPriority.READ_SHUFFLE))
        if state is None:
            return

        def append(b: ColumnBatch, st):
            cols = list(b.columns)
            real = b.row_mask()
            for (s, cnt, mn, mx, rows, money, odd), w, dt in zip(
                    st, self._wexprs, self._out_dtypes):
                op = window_agg_op(w.function)
                if s.dtype == jnp.float64:
                    s = jnp.where((odd == 0)
                                  & (jnp.abs(money) < cents.SUM_LIMIT),
                                  cents.from_cents(jnp, money), s)
                if op == "count_star":
                    val, ok = rows, jnp.bool_(True)
                elif op == "count":
                    val, ok = cnt, jnp.bool_(True)
                elif op == "sum":
                    val, ok = s, cnt > 0
                elif op == "avg":
                    val = cents.mean(jnp, s.astype(jnp.float64),
                                     jnp.maximum(cnt, 1))
                    ok = cnt > 0
                elif op == "min":
                    val, ok = mn, cnt > 0
                else:
                    val, ok = mx, cnt > 0
                np_dt = dt.np_dtype
                data = jnp.broadcast_to(
                    jnp.where(ok, val, 0).astype(np_dt.str),
                    (b.capacity,))
                validity = real & ok
                cols.append(DeviceColumn(
                    jnp.where(validity, data, jnp.zeros((), data.dtype)),
                    validity, dt))
            return ColumnBatch(cols, b.num_rows, self._schema)

        if len(self._gs_jits) == 2:
            from spark_rapids_tpu.exec import compile_cache as cc
            self._gs_jits = self._gs_jits + (cc.shared_jit(
                cc.fragment_key("window_gs_append", tuple(self._wexprs),
                                tuple(self._out_dtypes), self._schema),
                append, name="window_append"),)
        app_jit = self._gs_jits[2]
        for sb in parked:
            b = sb.get()
            sb.close()
            # appending broadcast finals is elementwise given the fixed
            # state: splitting on OOM yields the same rows in order
            yield from ctx.dispatch_retry(
                lambda bb: app_jit(bb, state), b, op="window_apply")

    # ------------------------------------------------------------------
    def _window_args(self, big: ColumnBatch) -> tuple:
        """Augment ``big`` with evaluated partition/order/input columns
        and build the sort-order spec: ``(aug, orders, part_idx,
        order_idx, input_idx, nbase)``.  Pure eval_device — callable
        both eagerly (the single-device path jits the body separately)
        and INSIDE a trace (MeshWindowExec splices the whole window into
        a per-device shard_map program)."""
        nbase = big.num_columns
        cols = list(big.columns)
        fields = list(big.schema.fields)

        def column(e, name: str) -> int:
            # a plain column is sorted where it stands: appended again
            # it would be moved twice by the row sort
            if isinstance(e, BoundReference):
                return e.index
            cols.append(eval_device(e, big))
            fields.append(T.StructField(f"{name}{len(cols)}", e.dtype, True))
            return len(cols) - 1
        part_idx = [column(e, "_wp") for e in self._part_b]
        order_idx = [column(e, "_wo") for e, _, _ in self._order_b]
        input_idx = [None if e is None else column(e, "_wi")
                     for e in self._fn_inputs]
        aug = ColumnBatch(cols, big.num_rows, T.Schema(fields))
        orders = [SortOrder(i, True, True) for i in part_idx] + \
            [SortOrder(i, asc, nf)
             for i, (_, asc, nf) in zip(order_idx, self._order_b)]
        return (aug, tuple(orders), tuple(part_idx), tuple(order_idx),
                tuple(input_idx), nbase)

    def _run_device(self, big: ColumnBatch) -> ColumnBatch:
        aug, orders, part_idx, order_idx, input_idx, nbase = \
            self._window_args(big)
        rows = big.known_rows
        get_registry().inc_many(self._launch_counts if rows is None else
                                self._launch_counts + [
                                    ("window.rows", rows),
                                    (self._rows_by_schema, rows)])
        return _jit_window(aug, orders, part_idx, order_idx, input_idx,
                           self._fns, nbase, self._schema)

    def _run_host(self, big: HostBatch) -> HostBatch:
        n = big.num_rows
        part_cols = [eval_host(e, big) for e in self._part_b]
        order_cols = [eval_host(e, big) for e, _, _ in self._order_b]
        in_cols = [None if e is None else eval_host(e, big)
                   for e in self._fn_inputs]
        # sort indices by (partition, order) with host sort machinery
        tmp_fields = [T.StructField(f"c{i}", c.dtype, True)
                      for i, c in enumerate(part_cols + order_cols)]
        tmp = HostBatch(part_cols + order_cols, T.Schema(tmp_fields))
        orders = [SortOrder(i, True, True) for i in range(len(part_cols))] + \
            [SortOrder(len(part_cols) + i, asc,
                       nf if nf is not None else None)
             for i, (_, asc, nf) in enumerate(self._order_b)]
        # empty spec: the zero-column tmp batch reports num_rows 0, so
        # host_sort_permutation would return an EMPTY identity — an
        # unordered global window keeps the input order directly
        perm = hk.host_sort_permutation(tmp, orders) if n and orders else \
            np.arange(n, dtype=np.int64)
        base = big.take(perm)
        sp = [c.take(perm) for c in part_cols]
        so = [c.take(perm) for c in order_cols]
        si = [None if c is None else c.take(perm) for c in in_cols]

        def key_tuple(colset, i):
            out = []
            for c in colset:
                if not c.validity[i]:
                    out.append(("\0null",))
                else:
                    v = c.data[i]
                    if isinstance(c.dtype, (T.FloatType, T.DoubleType)):
                        f = float(v)
                        v = "NaN" if f != f else (0.0 if f == 0.0 else f)
                    out.append((v,))
            return tuple(out)

        seg_start = np.zeros(n, np.int64)
        seg_end = np.zeros(n, np.int64)
        peer_start = np.zeros(n, np.int64)
        peer_end = np.zeros(n, np.int64)
        s = 0
        for i in range(1, n + 1):
            if i == n or key_tuple(sp, i) != key_tuple(sp, s):
                seg_start[s:i] = s
                seg_end[s:i] = i - 1
                ps = s
                for j in range(s + 1, i + 1):
                    if j == i or key_tuple(so, j) != key_tuple(so, ps):
                        peer_start[ps:j] = ps
                        peer_end[ps:j] = j - 1
                        ps = j
                s = i

        new_cols = []
        for w, inc, out_dt in zip(self._wexprs, si, self._out_dtypes):
            f = w.function
            frame = w.spec.resolved_frame()
            if isinstance(f, RowNumber):
                data = np.arange(n) - seg_start + 1
                new_cols.append(HostColumn(data.astype(np.int32),
                                           np.ones(n, bool), out_dt))
            elif isinstance(f, Rank):
                data = peer_start - seg_start + 1
                new_cols.append(HostColumn(data.astype(np.int32),
                                           np.ones(n, bool), out_dt))
            elif isinstance(f, DenseRank):
                data = np.zeros(n, np.int32)
                r = 0
                for i in range(n):
                    if i == seg_start[i]:
                        r = 1
                    elif peer_start[i] == i:
                        r += 1
                    data[i] = r
                new_cols.append(HostColumn(data, np.ones(n, bool), out_dt))
            elif isinstance(f, (Lead, Lag)):
                # Lag subclasses Lead: test Lag first (same fix as the
                # device path — both sides previously read forward, which
                # differential testing could not catch)
                off = -f.offset if isinstance(f, Lag) else f.offset
                data = np.empty(n, object)
                validity = np.zeros(n, bool)
                defv = None
                if f.default is not None:
                    from spark_rapids_tpu.expr.core import Literal
                    assert isinstance(f.default, Literal)
                    defv = f.default.value
                for i in range(n):
                    j = i + off
                    if seg_start[i] <= j <= seg_end[i]:
                        if inc.validity[j]:
                            data[i] = inc.data[j]
                            validity[i] = True
                    elif defv is not None:
                        data[i] = defv
                        validity[i] = True
                new_cols.append(_objs_to_host(data, validity, out_dt))
            else:
                op = window_agg_op(f)
                data = np.empty(n, object)
                validity = np.zeros(n, bool)
                for i in range(n):
                    if frame.mode == "rows":
                        lo = seg_start[i] if frame.lower is None else \
                            max(i + frame.lower, seg_start[i])
                        hi = seg_end[i] if frame.upper is None else \
                            min(i + frame.upper, seg_end[i])
                    else:
                        lo = seg_start[i] if frame.lower is None \
                            else peer_start[i]
                        hi = seg_end[i] if frame.upper is None \
                            else peer_end[i]
                    vals = []
                    cnt_rows = 0
                    for j in range(lo, hi + 1):
                        cnt_rows += 1
                        if inc is not None and inc.validity[j]:
                            vals.append(inc.data[j])
                    data[i], validity[i] = _host_agg(op, vals, cnt_rows,
                                                     out_dt)
                new_cols.append(_objs_to_host(data, validity, out_dt))
        return HostBatch(list(base.columns) + new_cols, self._schema)

    def node_desc(self) -> str:
        return f"WindowExec[{self._names}]"


def _host_agg(op, vals, cnt_rows, dtype):
    import math
    if op == "count_star":
        return cnt_rows, True
    if op == "count":
        return len(vals), True
    if not vals:
        return None, False
    if op == "sum":
        if isinstance(dtype, T.LongType):
            return int(sum(int(v) for v in vals)), True
        return _host_sum(vals), True
    if op == "avg":
        return float(cents.mean(np, np.float64(_host_sum(vals)),
                                np.int64(len(vals)))), True
    has_nan = any(isinstance(v, float) and math.isnan(v) for v in vals)
    if op == "min":
        nn = [v for v in vals
              if not (isinstance(v, float) and math.isnan(v))]
        if nn:
            return min(nn), True
        return float("nan"), True
    if op == "max":
        if has_nan:
            return float("nan"), True
        return max(vals), True
    raise ValueError(op)


def _host_sum(vals) -> float:
    """A frame's sum of doubles as the device takes it (ops/cents.py):
    over the cents as integers, rounded once, where every addend is
    whole cents; one addend after another elsewhere."""
    x = np.asarray([float(v) for v in vals], np.float64)
    c, whole = cents.as_cents(np, x)
    total = int(c.sum())
    if whole.all() and abs(total) < cents.SUM_LIMIT:
        return float(cents.from_cents(np, np.int64(total)))
    return float(sum(x.tolist()))


def _objs_to_host(data, validity, dtype) -> HostColumn:
    if isinstance(dtype, T.StringType):
        return HostColumn(data, validity, dtype)
    npdt = dtype.np_dtype
    arr = np.zeros(len(data), npdt)
    for i, v in enumerate(data):
        if validity[i]:
            arr[i] = v
    return HostColumn(arr, validity, dtype)


def _window_body(aug: ColumnBatch, orders, part_idx, order_idx, input_idx,
                 fns, nbase: int, schema: T.Schema) -> ColumnBatch:
    """The traceable window kernel: sort by (partition, order), derive
    the shared segment arrays, evaluate every expression (``fns``:
    ``_fn_desc`` of each).  ``_jit_window`` is its eager jitted wrapper;
    MeshWindowExec calls the body directly inside its per-device
    program."""
    # the rows move in one gather a dtype, not one a leaf
    sb = ColumnBatch(
        dk.gather_stacked(aug.columns, sort_permutation(aug, list(orders)),
                          aug.row_mask()), aug.num_rows, aug.schema)
    seg = W.sorted_segments(sb, part_idx, order_idx)
    out_cols = list(sb.columns[:nbase])
    for fn, ii in zip(fns, input_idx):
        kind = fn[0]
        if kind in ("row_number", "rank", "dense_rank"):
            data = getattr(W, kind)(seg).astype(jnp.int32)
            out_cols.append(DeviceColumn(
                jnp.where(seg.real, data, 0), seg.real, T.IntegerType()))
        elif kind == "offset":
            _, off, default = fn
            col = sb.columns[ii]
            dd = dv = dl = None
            if default is not None:
                if col.is_string:
                    from spark_rapids_tpu.columnar.column import \
                        round_string_width
                    bs = str(default).encode("utf-8")
                    width = max(col.max_len,
                                round_string_width(max(len(bs), 1)))
                    row = np.zeros(width, np.uint8)
                    row[:len(bs)] = np.frombuffer(bs, np.uint8)
                    dd = jnp.broadcast_to(jnp.asarray(row),
                                          (sb.capacity, width))
                    dl = jnp.full(sb.capacity, len(bs), jnp.int32)
                else:
                    dd = jnp.full(sb.capacity, default, col.data.dtype)
                dv = jnp.ones(sb.capacity, jnp.bool_)
            data, validity, lengths = W.lead_lag(col, seg, off, dd, dv, dl)
            out_cols.append(DeviceColumn(data, validity, col.dtype, lengths))
        else:
            _, op, frame = fn
            data, validity, rtype = W.running_or_bounded_agg(
                op, None if op == "count_star" else sb.columns[ii], seg,
                frame)
            zero = jnp.zeros((), data.dtype)
            out_cols.append(DeviceColumn(jnp.where(validity, data, zero),
                                         validity, rtype))
    return ColumnBatch(out_cols, sb.num_rows, schema)


_jit_window = guarded_jit(
    "window_frame", static_argnames=("orders", "part_idx", "order_idx", "input_idx",
                     "fns", "nbase", "schema"))(_window_body)
