"""Arrow-eval Python (pandas) UDF exec.

Reference: GpuArrowEvalPythonExec (GpuArrowEvalPythonExec.scala:46-456)
streams device batches as Arrow IPC to external python workers running
pandas scalar UDFs, reads Arrow results back to the device, with
PythonWorkerSemaphore capping concurrent workers.  This engine is
already a python process, so the data plane degenerates to an in-process
Arrow conversion: device batch -> pandas Series -> vectorized UDF ->
device column; the semaphore survives as a concurrency bound
(spark.rapids.python.concurrentPythonWorkers) because pandas UDFs run on
drain worker threads.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Callable, Iterator, Sequence

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.conf import ConfEntry, register
from spark_rapids_tpu.exec.core import ExecCtx, PlanNode
from spark_rapids_tpu.expr.core import Expression, bind
from spark_rapids_tpu.host.batch import HostBatch, HostColumn
from spark_rapids_tpu.ops import host_kernels as hk

__all__ = ["PandasUDF", "pandas_udf", "ArrowEvalPythonExec",
           "PandasAggUDF", "pandas_agg_udf", "MapInPandasExec",
           "FlatMapGroupsInPandasExec", "AggregateInPandasExec",
           "FlatMapCoGroupsInPandasExec"]

CONCURRENT_PYTHON = register(ConfEntry(
    "spark.rapids.python.concurrentPythonWorkers", 2,
    "Concurrent pandas-UDF evaluations (reference PythonWorkerSemaphore,"
    " PythonWorkerSemaphore.scala:42-100).", conv=int))

_sem_lock = threading.Lock()
_sems: dict[int, threading.BoundedSemaphore] = {}


def _py_semaphore(n: int) -> threading.BoundedSemaphore:
    with _sem_lock:
        if n not in _sems:
            _sems[n] = threading.BoundedSemaphore(n)
        return _sems[n]


_slot_tls = threading.local()


@contextmanager
def _udf_slot(sem: threading.BoundedSemaphore, lifecycle=None):
    """Per-thread REENTRANT semaphore hold: a chain of streaming pandas
    execs in one thread (map_in_pandas over map_in_pandas) pulls child
    batches while the downstream UDF slot is held — counting each level
    against the semaphore would self-deadlock once the chain is longer
    than the permit count, so the whole chain consumes ONE worker slot
    (the reference's semaphore also counts python WORKERS, not plan
    depth — PythonWorkerSemaphore.scala:42-100).

    ``lifecycle`` (the query's exec/lifecycle.py handle) makes the
    acquire a cancellation point: a cancelled query never queues new
    UDF evaluations behind the concurrentPythonWorkers semaphore, and
    one already waiting wakes at the next poll instead of after the
    UDF ahead of it finishes."""
    depth = getattr(_slot_tls, "depth", 0)
    if depth == 0:
        if lifecycle is None:
            sem.acquire()
        else:
            lifecycle.check()
            while not sem.acquire(timeout=0.05):
                lifecycle.check()
    _slot_tls.depth = depth + 1
    try:
        yield
    finally:
        _slot_tls.depth = depth
        if depth == 0:
            sem.release()


class PandasUDF(Expression):
    """Vectorized python UDF over pandas Series — planned into an
    ArrowEvalPythonExec, never evaluated inline (like WindowExpression)."""

    sql_name = "PandasUDF"

    def __init__(self, fn: Callable, children: Sequence[Expression],
                 return_type: T.DataType):
        self.fn = fn
        self.children = tuple(children)
        self.return_type = return_type

    def with_new_children(self, children):
        return PandasUDF(self.fn, children, self.return_type)

    @property
    def dtype(self):
        return self.return_type

    @property
    def nullable(self):
        return True

    def _eval(self, vals, ctx):
        raise ValueError(
            "PandasUDF must be planned by ArrowEvalPythonExec "
            "(use it directly inside select())")

    def __repr__(self):
        name = getattr(self.fn, "__name__", "<lambda>")
        return f"PandasUDF({name}, {', '.join(map(repr, self.children))})"


def _host_col_to_series(v, exact_int=False):
    """HostColumn -> pandas Series with nulls surfaced as None/NaN
    (numeric columns upcast to float64 only when nulls are present).

    exact_int: nullable INTEGRAL columns use pandas' nullable Int64
    instead of the float64 upcast — int64 values >= 2**53 are not
    representable in float64, so group keys routed through float would
    merge distinct keys and round-trip lossily.  Used for group-key
    columns; UDF inputs keep the float64 convention (Spark's own Arrow
    path hands pandas UDFs float64 for nullable ints)."""
    import pandas as pd
    if isinstance(v.dtype, T.StringType):
        return pd.Series(v.data)
    if not np.all(v.validity) and v.dtype.numeric:
        if exact_int and v.dtype.integral:
            s = pd.Series(v.data, dtype="Int64")
            s[~np.asarray(v.validity)] = pd.NA
            return s
        data = v.data.astype("float64")
    else:
        data = v.data
    s = pd.Series(data)
    if not np.all(v.validity):
        s[~np.asarray(v.validity)] = None
    return s


def pandas_udf(fn: Callable, return_type: T.DataType | None = None):
    """``df.select(pandas_udf(lambda s: s * 2)(col("a")))`` — ``fn``
    receives pandas Series and returns a Series/array of the same
    length."""

    def apply(*cols):
        return PandasUDF(fn, list(cols), return_type or T.DoubleType())

    return apply


class ArrowEvalPythonExec(PlanNode):
    """Append one column per pandas UDF to each child batch.

    The child batch crosses D2H as Arrow, the UDFs run vectorized over
    pandas Series, and results transfer back H2D (reference
    GpuArrowPythonRunner's writeArrowIPCChunked round trip :376-432)."""

    def __init__(self, udfs: Sequence, child: PlanNode):
        super().__init__([child])
        self._udfs = []  # (name, PandasUDF with bound children)
        cs = child.output_schema
        fields = list(cs.fields)
        for name, u in udfs:
            bound = [bind(c, cs) for c in u.children]
            self._udfs.append((name, PandasUDF(u.fn, bound, u.return_type)))
            fields.append(T.StructField(name, u.return_type, True))
        self._schema = T.Schema(fields)

    @property
    def output_schema(self) -> T.Schema:
        return self._schema

    @property
    def bound_exprs(self):
        # PandasUDF itself is exec-planned; expose only its INPUT
        # expressions for tagging
        return [c for _, u in self._udfs for c in u.children]

    def _series_inputs(self, hb: HostBatch, u: PandasUDF):
        from spark_rapids_tpu.expr.core import eval_host
        return [_host_col_to_series(eval_host(c, hb)) for c in u.children]

    def _apply_udfs(self, hb: HostBatch, ctx: ExecCtx) -> HostBatch:
        import pandas as pd
        sem = _py_semaphore(ctx.conf.get(CONCURRENT_PYTHON))
        cols = list(hb.columns)
        for name, u in self._udfs:
            with _udf_slot(sem, ctx.lifecycle):
                result = u.fn(*self._series_inputs(hb, u))
            r = pd.Series(result)
            if len(r) != hb.num_rows:
                raise ValueError(
                    f"pandas UDF {name} returned {len(r)} rows for "
                    f"{hb.num_rows} input rows")
            validity = ~r.isna().to_numpy()
            if isinstance(u.return_type, T.StringType):
                data = np.array([None if not v else str(x)
                                 for x, v in zip(r, validity)], dtype=object)
            else:
                data = r.fillna(0).to_numpy().astype(u.return_type.np_dtype)
            cols.append(HostColumn(data, validity, u.return_type))
        return HostBatch(cols, self._schema)

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        from spark_rapids_tpu.exec.core import device_to_host, host_to_device
        for b in self.children[0].partition_iter(ctx, pid):
            if ctx.is_device:
                hb = device_to_host(b)
                out = self._apply_udfs(hb, ctx)
                yield host_to_device(out)
            else:
                yield self._apply_udfs(b, ctx)

    def node_desc(self) -> str:
        return (f"ArrowEvalPythonExec[{[n for n, _ in self._udfs]}]")


# ---------------------------------------------------------------------------
# pandas exec family: iterator / grouped / cogrouped / aggregating variants
# (reference sql-plugin .../execution/python/: GpuMapInPandasExec.scala:141,
# GpuFlatMapGroupsInPandasExec.scala:180, GpuAggregateInPandasExec.scala:198,
# GpuFlatMapCoGroupsInPandasExec.scala:167 — all stream device batches over
# the Arrow boundary to pandas workers; here the worker is in-process and
# the semaphore bounds concurrent UDF evaluation the same way)
# ---------------------------------------------------------------------------

def _to_pandas(hb: HostBatch, exact_keys: "list[str] | None" = None):
    """Arrow-convention pandas frame (nullable ints with nulls become
    float64, what Spark's Arrow path hands pandas UDFs) — except the
    ``exact_keys`` columns, which convert as nullable Int64: GROUPS are
    formed from these frames here (Spark forms them JVM-side, exactly),
    and a float64 round trip merges distinct int64 keys >= 2**53
    (advisor r4 / review finding)."""
    pdf = hb.to_arrow().to_pandas()
    for k in exact_keys or ():
        f = hb.schema.field(k)
        if f.data_type.integral:
            pdf[k] = _host_col_to_series(
                hb.columns[hb.schema.index_of(k)], exact_int=True)
    return pdf


def _from_pandas(pdf, schema: T.Schema, what: str) -> HostBatch:
    """Validate + convert a UDF's output DataFrame against the declared
    schema: labeled columns match by NAME, unlabeled (RangeIndex) by
    position — Spark's assignment rules for mapInPandas/applyInPandas."""
    import pandas as pd
    import pyarrow as pa
    if not isinstance(pdf, pd.DataFrame):
        raise TypeError(f"{what} must produce pandas DataFrames, got "
                        f"{type(pdf).__name__}")
    names = list(schema.names)
    if all(isinstance(c, int) for c in pdf.columns):
        if len(pdf.columns) != len(names):
            raise ValueError(
                f"{what} returned {len(pdf.columns)} unlabeled columns "
                f"for schema {names}")
        pdf = pdf.set_axis(names, axis=1)
    else:
        missing = [n for n in names if n not in pdf.columns]
        if missing:
            raise ValueError(f"{what} output is missing columns {missing} "
                             f"(has {list(pdf.columns)})")
        pdf = pdf[names]
    arrays = [pa.array(pdf[n], type=T.to_arrow(f.data_type),
                       from_pandas=True) for n, f in zip(names, schema)]
    rb = pa.RecordBatch.from_arrays(arrays, schema=schema.to_arrow())
    return HostBatch.from_arrow(rb)


def _host_batches(node: PlanNode, ctx: ExecCtx, pid: int):
    from spark_rapids_tpu.exec.core import device_to_host
    for b in node.partition_iter(ctx, pid):
        yield device_to_host(b) if ctx.is_device else b


def _emit(hb: HostBatch, ctx: ExecCtx):
    from spark_rapids_tpu.exec.core import host_to_device
    return host_to_device(hb) if ctx.is_device else hb


class MapInPandasExec(PlanNode):
    """df.map_in_pandas(fn, schema): ``fn`` receives an ITERATOR of
    pandas DataFrames (one partition's batches) and yields DataFrames
    conforming to ``schema`` — output row count is unconstrained
    (reference GpuMapInPandasExec.scala:60-141)."""

    def __init__(self, fn: Callable, out_schema: T.Schema, child: PlanNode):
        super().__init__([child])
        self._fn = fn
        self._schema = out_schema

    @property
    def output_schema(self) -> T.Schema:
        return self._schema

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        sem = _py_semaphore(ctx.conf.get(CONCURRENT_PYTHON))
        # materialize the partition's inputs BEFORE taking a worker
        # slot: next(it) runs arbitrary UDF code, and if it also pulled
        # un-executed upstream stages (a shuffle drain on other worker
        # threads, themselves competing for permits) a held permit
        # could deadlock the pool — with a plain list the pull is pure
        # python (review finding; FlatMapGroups/Aggregate already
        # materialize their partition the same way)
        pdfs = [_to_pandas(hb) for hb in
                _host_batches(self.children[0], ctx, pid)]
        it = self._fn(iter(pdfs))
        while True:
            # slot held only around the UDF body (runs inside next()
            # for generator UDFs); reentrant so chained pandas execs in
            # one thread consume a single worker slot
            with _udf_slot(sem, ctx.lifecycle):
                try:
                    out = next(it)
                except StopIteration:
                    return
            hb = _from_pandas(out, self._schema, "map_in_pandas")
            if hb.num_rows:
                yield _emit(hb, ctx)

    def node_desc(self) -> str:
        name = getattr(self._fn, "__name__", "<lambda>")
        return f"MapInPandasExec[{name}]"


def _group_frames(pdf, key_names: list):
    """Per-group sub-frames, null keys kept as their own groups and
    group order deterministic (sorted, nulls last — pandas sort=True)."""
    return pdf.groupby(list(key_names), dropna=False, sort=True)


class FlatMapGroupsInPandasExec(PlanNode):
    """group_by(keys).apply_in_pandas(fn, schema): ``fn`` receives each
    group as one pandas DataFrame (ALL child columns, keys included) and
    returns a DataFrame conforming to ``schema``.  The planner inserts a
    hash exchange on the keys so each group lands wholly in one
    partition (reference GpuFlatMapGroupsInPandasExec.scala:75
    requiredChildDistribution = ClusteredDistribution)."""

    def __init__(self, key_names: Sequence[str], fn: Callable,
                 out_schema: T.Schema, child: PlanNode):
        super().__init__([child])
        self._keys = list(key_names)
        self._fn = fn
        self._schema = out_schema

    @property
    def output_schema(self) -> T.Schema:
        return self._schema

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        batches = list(_host_batches(self.children[0], ctx, pid))
        if not batches:
            return
        pdf = _to_pandas(HostBatch.concat(batches), exact_keys=self._keys)
        if not len(pdf):
            return
        sem = _py_semaphore(ctx.conf.get(CONCURRENT_PYTHON))
        for _, g in _group_frames(pdf, self._keys):
            with _udf_slot(sem, ctx.lifecycle):
                out = self._fn(g.reset_index(drop=True))
            hb = _from_pandas(out, self._schema, "apply_in_pandas")
            if hb.num_rows:
                yield _emit(hb, ctx)

    def node_desc(self) -> str:
        return f"FlatMapGroupsInPandasExec[keys={self._keys}]"


class PandasAggUDF(Expression):
    """Grouped-aggregate pandas UDF: Series in, ONE scalar out per
    group — planned into AggregateInPandasExec, never evaluated inline
    (reference GpuAggregateInPandasExec's PythonUDAF plan)."""

    sql_name = "PandasAggUDF"

    def __init__(self, fn: Callable, children: Sequence[Expression],
                 return_type: T.DataType):
        self.fn = fn
        self.children = tuple(children)
        self.return_type = return_type

    def with_new_children(self, children):
        return PandasAggUDF(self.fn, children, self.return_type)

    @property
    def dtype(self):
        return self.return_type

    @property
    def nullable(self):
        return True

    def _eval(self, vals, ctx):
        raise ValueError("PandasAggUDF must be planned by "
                         "AggregateInPandasExec (use it in group_by("
                         ").agg())")

    def __repr__(self):
        name = getattr(self.fn, "__name__", "<lambda>")
        return f"PandasAggUDF({name}, {', '.join(map(repr, self.children))})"


def pandas_agg_udf(fn: Callable, return_type: T.DataType | None = None):
    """``df.group_by("k").agg(pandas_agg_udf(lambda s: s.mean())(col("v"))
    .alias("m"))`` — ``fn`` receives pandas Series and returns one
    scalar per group."""

    def apply(*cols):
        return PandasAggUDF(fn, list(cols), return_type or T.DoubleType())

    return apply


class AggregateInPandasExec(PlanNode):
    """One output row per group: key columns + one column per pandas
    aggregate UDF (Series -> scalar).  A black-box aggregate cannot be
    split partial/final, so the planner clusters rows by key first
    (reference GpuAggregateInPandasExec.scala:63-198)."""

    def __init__(self, key_names: Sequence[str], udfs: Sequence,
                 child: PlanNode):
        super().__init__([child])
        self._keys = list(key_names)
        cs = child.output_schema
        self._udfs = []  # (name, PandasAggUDF bound to child schema)
        fields = [cs.field(k) for k in self._keys]
        for name, u in udfs:
            bound = [bind(c, cs) for c in u.children]
            self._udfs.append((name, PandasAggUDF(u.fn, bound,
                                                  u.return_type)))
            fields.append(T.StructField(name, u.return_type, True))
        self._schema = T.Schema(fields)

    @property
    def output_schema(self) -> T.Schema:
        return self._schema

    @property
    def bound_exprs(self):
        return [c for _, u in self._udfs for c in u.children]

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        import pandas as pd
        from spark_rapids_tpu.expr.core import eval_host
        batches = list(_host_batches(self.children[0], ctx, pid))
        if not batches:
            if self._keys:
                return
            # keyless grand aggregate over empty input still produces
            # ONE row — the UDF sees empty Series (Spark global
            # aggregation semantics; this engine's HashAggregateExec
            # default-values row does the same)
            batches = [HostBatch.empty(self.children[0].output_schema)]
        hb = HostBatch.concat(batches)
        if not hb.num_rows and self._keys:
            return
        # key columns + each UDF's evaluated input series, side by side
        # (keys convert individually — a whole-batch _to_pandas would
        # pay for every non-key column just to read the keys)
        frame = {}
        for k in self._keys:
            frame[k] = _host_col_to_series(
                hb.columns[hb.schema.index_of(k)], exact_int=True)
        in_names: list[list[str]] = []
        for ui, (name, u) in enumerate(self._udfs):
            cols = []
            for ci, c in enumerate(u.children):
                s = _host_col_to_series(eval_host(c, hb))
                cn = f"_in_{ui}_{ci}"
                frame[cn] = s
                cols.append(cn)
            in_names.append(cols)
        pdf = pd.DataFrame(frame, index=range(hb.num_rows))
        sem = _py_semaphore(ctx.conf.get(CONCURRENT_PYTHON))
        rows: dict[str, list] = {n: [] for n in self._schema.names}
        if self._keys:
            groups = _group_frames(pdf, self._keys)
        else:
            groups = [((), pdf)]
        for key, g in groups:
            if not isinstance(key, tuple):
                key = (key,)
            for k, kv in zip(self._keys, key):
                rows[k].append(None if pd.isna(kv) else kv)
            for (name, u), cols in zip(self._udfs, in_names):
                with _udf_slot(sem, ctx.lifecycle):
                    r = u.fn(*[g[c] for c in cols])
                rows[name].append(None if r is None or
                                  (np.isscalar(r) and pd.isna(r)) else r)
        # integral output columns build as nullable Int64: a plain
        # pd.Series over ints + None coerces to float64, which merges
        # int64 key values >= 2**53 (advisor r4 — the group keys were
        # exact all the way here, only to collapse in this constructor)
        def out_series(n):
            f = self._schema.field(n)
            if f.data_type.integral and any(v is None for v in rows[n]):
                return pd.Series(rows[n], dtype="Int64")
            return pd.Series(rows[n])
        out = pd.DataFrame({n: out_series(n) for n in
                            self._schema.names})
        hb_out = _from_pandas(out, self._schema, "pandas agg")
        if hb_out.num_rows:
            yield _emit(hb_out, ctx)

    def node_desc(self) -> str:
        return (f"AggregateInPandasExec[keys={self._keys}, "
                f"aggs={[n for n, _ in self._udfs]}]")


def _null_safe_key(key) -> tuple:
    """Normalize a group-key tuple so null keys compare equal across the
    two cogrouped sides (NaN != NaN would otherwise split them)."""
    import pandas as pd
    if not isinstance(key, tuple):
        key = (key,)
    return tuple("\x00<null>" if pd.isna(k) else k for k in key)


class FlatMapCoGroupsInPandasExec(PlanNode):
    """df1.group_by(k).cogroup(df2.group_by(k)).apply_in_pandas(fn,
    schema): ``fn(left_pdf, right_pdf)`` once per key present on EITHER
    side; the absent side arrives as an empty DataFrame with its full
    column set (reference GpuFlatMapCoGroupsInPandasExec.scala:70-167,
    requiredChildDistribution clusters both children on their keys)."""

    def __init__(self, left_keys: Sequence[str], right_keys: Sequence[str],
                 fn: Callable, out_schema: T.Schema, left: PlanNode,
                 right: PlanNode):
        super().__init__([left, right])
        self._lkeys = list(left_keys)
        self._rkeys = list(right_keys)
        self._fn = fn
        self._schema = out_schema

    @property
    def output_schema(self) -> T.Schema:
        return self._schema

    def num_partitions(self, ctx: ExecCtx) -> int:
        return self.children[0].num_partitions(ctx)

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        def side_groups(node, keys):
            batches = list(_host_batches(node, ctx, pid))
            empty = _to_pandas(HostBatch.empty(node.output_schema))
            if not batches:
                return {}, empty
            pdf = _to_pandas(HostBatch.concat(batches), exact_keys=keys)
            if not len(pdf):
                return {}, empty
            return {_null_safe_key(k): g.reset_index(drop=True)
                    for k, g in _group_frames(pdf, keys)}, empty

        lgroups, lempty = side_groups(self.children[0], self._lkeys)
        rgroups, rempty = side_groups(self.children[1], self._rkeys)
        keys = sorted(set(lgroups) | set(rgroups), key=repr)
        sem = _py_semaphore(ctx.conf.get(CONCURRENT_PYTHON))
        for k in keys:
            # absent side gets a fresh copy: UDFs commonly mutate their
            # input in place, and a shared empty frame would leak those
            # mutations into later calls (review finding)
            lg = lgroups.get(k)
            rg = rgroups.get(k)
            with _udf_slot(sem, ctx.lifecycle):
                out = self._fn(lg if lg is not None else lempty.copy(),
                               rg if rg is not None else rempty.copy())
            hb = _from_pandas(out, self._schema, "cogroup apply_in_pandas")
            if hb.num_rows:
                yield _emit(hb, ctx)

    def node_desc(self) -> str:
        return (f"FlatMapCoGroupsInPandasExec[{self._lkeys} x "
                f"{self._rkeys}]")


class PandasWindowUDF(Expression):
    """Window-aggregate pandas UDF: evaluated over each row's window
    frame (Series slice in, ONE scalar out per row) — planned into
    WindowInPandasExec, never evaluated inline (reference
    GpuWindowInPandasExec's PythonUDF-in-WindowExpression plan,
    shims/spark300/.../GpuWindowInPandasExec.scala:1-408)."""

    sql_name = "PandasWindowUDF"

    def __init__(self, fn: Callable, children: Sequence[Expression],
                 return_type: T.DataType):
        self.fn = fn
        self.children = tuple(children)
        self.return_type = return_type

    def with_new_children(self, children):
        return PandasWindowUDF(self.fn, children, self.return_type)

    @property
    def dtype(self):
        return self.return_type

    @property
    def nullable(self):
        return True

    def _eval(self, vals, ctx):
        raise ValueError("PandasWindowUDF must be planned by "
                         "WindowInPandasExec (use .over(window_spec))")

    def over(self, spec):
        """``udf(col).over(window_spec)`` — Spark's pandas-UDF-over-
        window surface (WindowInPandasExec plan)."""
        from spark_rapids_tpu.expr.window import WindowExpression
        return WindowExpression(self, spec)

    def __repr__(self):
        name = getattr(self.fn, "__name__", "<lambda>")
        return f"PandasWindowUDF({name}, {', '.join(map(repr, self.children))})"


def pandas_window_udf(fn: Callable, return_type: T.DataType | None = None):
    """``pandas_window_udf(lambda s: s.mean())(col("v")).over(spec)`` —
    ``fn`` receives each row's frame as pandas Series and returns one
    scalar for that row (Spark's GROUPED_AGG pandas UDF over a window)."""

    def apply(*cols):
        return PandasWindowUDF(fn, list(cols), return_type or T.DoubleType())

    return apply


class WindowInPandasExec(PlanNode):
    """Append one column per pandas window UDF expression.

    The reference streams (window-bound columns + UDF inputs) to Python
    workers, which evaluate the UDF over each row's slice
    (GpuWindowInPandasExec.scala:107-180 computeWindowBoundHelpers and
    :234-330 bounds-column projection).  Here the same shape runs
    in-process: per partition group, compute each row's [lower, upper)
    frame indices from the shared WindowSpec, then call the UDF with the
    input Series sliced to that frame.  Like the reference
    (requiredChildDistribution, :88-97) the planner clusters rows by the
    partition keys first; an empty partition-by collapses to a single
    group with the reference's own performance warning semantics.
    """

    def __init__(self, window_exprs: Sequence[Expression], child: PlanNode,
                 keys_partitioned: bool = False):
        super().__init__([child])
        from spark_rapids_tpu.expr.core import Alias, output_name
        from spark_rapids_tpu.expr.window import WindowExpression
        self._keys_partitioned = bool(keys_partitioned)
        self._names = [output_name(e) for e in window_exprs]
        self._wexprs = []
        for e in window_exprs:
            if isinstance(e, Alias):
                e = e.children[0]
            assert isinstance(e, WindowExpression), e
            assert isinstance(e.function, PandasWindowUDF), e.function
            self._wexprs.append(e)
        from spark_rapids_tpu.exec.window import spec_key
        self.spec = self._wexprs[0].spec
        if len({spec_key(e.spec) for e in self._wexprs}) > 1:
            raise ValueError("one WindowInPandasExec handles one "
                             "WindowSpec; split plans per spec")
        cs = child.output_schema
        self._part_b = [bind(p, cs) for p in self.spec.partition_by]
        self._order_b = [(bind(o[0], cs), o[1] if len(o) > 1 else True,
                          o[2] if len(o) > 2 else None)
                         for o in self.spec.order_by]
        self._udfs = [PandasWindowUDF(w.function.fn,
                                      [bind(c, cs)
                                       for c in w.function.children],
                                      w.function.return_type)
                      for w in self._wexprs]
        self._schema = T.Schema(
            list(cs.fields)
            + [T.StructField(n, u.return_type, True)
               for n, u in zip(self._names, self._udfs)])

    @property
    def output_schema(self) -> T.Schema:
        return self._schema

    @property
    def bound_exprs(self):
        return ([e for e in self._part_b] + [e for e, _, _ in self._order_b]
                + [c for u in self._udfs for c in u.children])

    def num_partitions(self, ctx: ExecCtx) -> int:
        return self.children[0].num_partitions(ctx) \
            if self._keys_partitioned else 1

    @staticmethod
    def _bounds(gn: int, peer_start: np.ndarray, peer_end: np.ndarray,
                frame) -> tuple[np.ndarray, np.ndarray]:
        """[lower, upper) frame rows for one group (group-local).
        ``peer_start``/``peer_end``: each row's order-peer group extent
        (Spark's default ordered frame is RANGE UNBOUNDED..CURRENT ROW =
        peers included; GpuWindowExpression's frame resolution)."""
        i = np.arange(gn)
        from spark_rapids_tpu.ops.window import CURRENT_ROW, UNBOUNDED
        if frame.mode == "rows":
            lo = np.zeros(gn, np.int64) if frame.lower is UNBOUNDED \
                else np.clip(i + frame.lower, 0, gn)
            hi = np.full(gn, gn, np.int64) if frame.upper is UNBOUNDED \
                else np.clip(i + frame.upper + 1, 0, gn)
        else:  # range: UNBOUNDED/CURRENT_ROW only (planner contract)
            lo = peer_start if frame.lower is CURRENT_ROW \
                else np.zeros(gn, np.int64)
            hi = peer_end if frame.upper is CURRENT_ROW \
                else np.full(gn, gn, np.int64)
        return lo, hi

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        import pandas as pd
        from spark_rapids_tpu.expr.core import eval_host
        from spark_rapids_tpu.ops.sort import SortOrder
        child = self.children[0]
        if self._keys_partitioned:
            batches = list(_host_batches(child, ctx, pid))
        else:
            batches = [b for p in range(child.num_partitions(ctx))
                       for b in _host_batches(child, ctx, p)]
        if not batches:
            return
        hb = HostBatch.concat(batches)
        n = hb.num_rows
        if not n:
            return
        # sort rows by (partition keys, order keys) — required child
        # ordering, reference GpuWindowInPandasExec.scala:99-100
        key_cols = [eval_host(e, hb) for e in self._part_b] \
            + [eval_host(e, hb) for e, _, _ in self._order_b]
        tmp = HostBatch(key_cols, T.Schema(
            [T.StructField(f"k{i}", c.dtype, True)
             for i, c in enumerate(key_cols)]))
        orders = [SortOrder(i, True, True)
                  for i in range(len(self._part_b))] \
            + [SortOrder(len(self._part_b) + i, asc, nf)
               for i, (_, asc, nf) in enumerate(self._order_b)]
        perm = hk.host_sort_permutation(tmp, orders)
        hb = hk.host_take(hb, perm)

        def change_flags(cols):
            """bool[n] over the SORTED batch: row differs from its
            predecessor on any of ``cols`` (row 0 True; per-column
            factorize codes, so no composite product to overflow;
            nulls are one group, Spark window key semantics)."""
            ch = np.zeros(n, bool)
            if n:
                ch[0] = True
            for c in cols:
                s = _host_col_to_series(c.take(perm), exact_int=True)
                code = pd.factorize(s, use_na_sentinel=False)[0]
                ch[1:] |= code[1:] != code[:-1]
            return ch

        gchange = change_flags(key_cols[:len(self._part_b)])
        ochange_g = change_flags(key_cols[len(self._part_b):])
        seg_starts = np.flatnonzero(gchange)
        seg_ends = np.concatenate([seg_starts[1:], [n]])

        in_series = [[_host_col_to_series(eval_host(c, hb))
                      for c in u.children] for u in self._udfs]
        sem = _py_semaphore(ctx.conf.get(CONCURRENT_PYTHON))
        out_vals: list[list] = [[None] * n for _ in self._udfs]
        for s0, s1 in zip(seg_starts, seg_ends):
            gn = s1 - s0
            ochange = ochange_g[s0:s1].copy()
            if gn:
                ochange[0] = True
            peer_id = np.cumsum(ochange) - 1
            # each row's order-peer group extent [start, end), group-local
            pstarts = np.flatnonzero(ochange)
            peer_start = pstarts[peer_id]
            peer_end = np.concatenate([pstarts[1:], [gn]])[peer_id]
            for ui, (w, u) in enumerate(zip(self._wexprs, self._udfs)):
                lo, hi = self._bounds(gn, peer_start, peer_end,
                                      w.spec.resolved_frame())
                series = [s.iloc[s0:s1].reset_index(drop=True)
                          for s in in_series[ui]]
                vals = out_vals[ui]
                with _udf_slot(sem, ctx.lifecycle):
                    for i in range(gn):
                        r = u.fn(*[s.iloc[lo[i]:hi[i]] for s in series])
                        vals[s0 + i] = None if r is None or (
                            np.isscalar(r) and pd.isna(r)) else r
        out_cols = list(hb.columns)
        for (name, u), vals in zip(zip(self._names, self._udfs), out_vals):
            f = self._schema.field(name)
            if f.data_type.integral and any(v is None for v in vals):
                s = pd.Series(vals, dtype="Int64")
            else:
                s = pd.Series(vals)
            hcol = _from_pandas(pd.DataFrame({name: s}),
                                T.Schema([f]), "pandas window").columns[0]
            out_cols.append(hcol)
        yield _emit(HostBatch(out_cols, self._schema), ctx)

    def node_desc(self) -> str:
        return (f"WindowInPandasExec[{self._names}, "
                f"part={len(self._part_b)}, order={len(self._order_b)}]")
