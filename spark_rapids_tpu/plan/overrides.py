"""Lowering + TpuOverrides: the plan-rewrite/tagging engine.

Reference (SURVEY.md §2.1, §3.2): GpuOverrides wraps every plan node in
a RapidsMeta, tags nodes that cannot run on the accelerator with
reasons (RapidsMeta.willNotWorkOnGpu / tagForGpu,
RapidsMeta.scala:189-216), converts the tagged tree, prints
`spark.rapids.sql.explain`, and GpuTransitionOverrides inserts
transitions.  Here:

* `lower()` turns the logical plan into dual-backend physical execs
  while recording, per node, the expressions it evaluates;
* `TpuOverrides.apply()` tags each node — per-exec conf key
  ``spark.rapids.sql.exec.<Name>``, per-expression key
  ``spark.rapids.sql.expression.<Name>`` plus a device-capability
  check — assigns device/host backends, inserts `BackendSwitchExec`
  at boundaries, and renders the explain tree (``*`` = on TPU,
  ``!`` = falls back, with reasons).
"""
from __future__ import annotations

from dataclasses import dataclass, field

from spark_rapids_tpu import types as T
from spark_rapids_tpu.conf import TpuConf
from spark_rapids_tpu.exec import (CrossJoinExec, FilterExec,
                                   GlobalLimitExec, HashAggregateExec,
                                   HashPartitioning, JoinExec,
                                   ProjectExec, RoundRobinPartitioning,
                                   ShuffleExchangeExec, SortExec, UnionExec,
                                   WindowExec)
from spark_rapids_tpu.exec.core import ExecCtx, PlanNode
from spark_rapids_tpu.exec.window import spec_key
from spark_rapids_tpu.exec.transitions import BackendSwitchExec
from spark_rapids_tpu.expr.core import (Alias, Expression, col, output_name)
from spark_rapids_tpu.expr.window import WindowExpression
from spark_rapids_tpu.plan import logical as L

__all__ = ["PlannedNode", "lower", "TpuOverrides"]


@dataclass
class PlannedNode:
    """Physical exec + planning metadata (the RapidsMeta analog)."""
    exec_node: PlanNode
    exprs: list = field(default_factory=list)
    children: list = field(default_factory=list)
    backend: str = "device"
    reasons: list = field(default_factory=list)

    @property
    def name(self) -> str:
        return type(self.exec_node).__name__

    def will_not_work(self, reason: str) -> None:
        if reason not in self.reasons:
            self.reasons.append(reason)


# ---------------------------------------------------------------------------
# lowering
# ---------------------------------------------------------------------------

def lower(node: L.LogicalPlan, conf: TpuConf) -> PlannedNode:
    if isinstance(node, L.Scan):
        return PlannedNode(node.exec_node)
    if isinstance(node, L.Filter):
        c = lower(node.child, conf)
        from spark_rapids_tpu.udf import maybe_compile_udfs
        cond = maybe_compile_udfs([node.condition], conf)[0]
        ex = FilterExec(cond, c.exec_node)
        return PlannedNode(ex, [cond], [c])
    if isinstance(node, L.Project):
        return _lower_project(node, conf)
    if isinstance(node, L.Aggregate):
        return _lower_aggregate(node, conf)
    if isinstance(node, L.Join):
        lc = lower(node.left, conf)
        rc = lower(node.right, conf)
        if node.how in ("inner", "left", "right", "semi", "anti"):
            lc = _aqe_join_exchange(lc, node.left_on, conf)
            rc = _aqe_join_exchange(rc, node.right_on, conf)
        lc, rc = (_aqe_join_reader(c, conf) for c in (lc, rc))
        if node.how == "cross":
            ex = CrossJoinExec(lc.exec_node, rc.exec_node, node.condition)
        elif conf.mesh_device_count > 1 and node.how != "full" \
                and not _schema_has_arrays(lc.exec_node, rc.exec_node):
            # mesh mode: replicated-build join, one probe shard per
            # device (the GpuBroadcastHashJoinExec analog over ICI)
            from spark_rapids_tpu.conf import MESH_JOIN_BUILD_THRESHOLD
            from spark_rapids_tpu.exec.mesh_exec import MeshJoinExec
            ex = MeshJoinExec(lc.exec_node, rc.exec_node, node.left_on,
                              node.right_on, node.how,
                              conf.mesh_device_count, node.condition,
                              build_threshold_bytes=conf.get(
                                  MESH_JOIN_BUILD_THRESHOLD))
        else:
            ex = JoinExec(lc.exec_node, rc.exec_node, node.left_on,
                          node.right_on, node.how, node.condition)
        exprs = list(node.left_on) + list(node.right_on)
        if node.condition is not None:
            exprs.append(node.condition)
        # meta children MUST mirror exec children: JoinExec runs a right
        # join side-swapped, and a tree-rewrite pass (coalesce /
        # transition insertion) reassigns exec children from meta order —
        # un-swapped metas silently flipped the join back (latent until a
        # right join with asymmetric schemas hit a rewrite pass)
        metas = [rc, lc] if getattr(ex, "_swapped", False) else [lc, rc]
        return PlannedNode(ex, exprs, metas)
    if isinstance(node, L.Sort):
        c = lower(node.child, conf)
        orders = _mesh_sort_orders(node.orders, c.exec_node, conf)
        if orders is not None:
            from spark_rapids_tpu.exec.mesh_region import MeshSortExec
            ex = MeshSortExec(orders, c.exec_node, conf.mesh_device_count)
        else:
            ex = SortExec(node.orders, c.exec_node, global_sort=True)
        return PlannedNode(ex, [], [c])
    if isinstance(node, L.Limit):
        if isinstance(node.child, L.Sort):
            # ORDER BY + LIMIT under the mesh: distributed TopN — the
            # broadcast sort keeps only the first n rows on device 0,
            # and the GlobalLimitExec above drains partitions in order
            # so the result passes through with no cross-device gather
            sc = lower(node.child.child, conf)
            orders = _mesh_sort_orders(node.child.orders, sc.exec_node,
                                       conf)
            if orders is not None:
                from spark_rapids_tpu.exec.mesh_region import MeshSortExec
                ms = MeshSortExec(orders, sc.exec_node,
                                  conf.mesh_device_count, limit=node.n)
                smeta = PlannedNode(ms, [], [sc])
                return PlannedNode(GlobalLimitExec(node.n, ms), [],
                                   [smeta])
            c = PlannedNode(SortExec(node.child.orders, sc.exec_node,
                                     global_sort=True), [], [sc])
        else:
            c = lower(node.child, conf)
        return PlannedNode(GlobalLimitExec(node.n, c.exec_node), [], [c])
    if isinstance(node, L.Union):
        cs = [lower(i, conf) for i in node.inputs]
        return PlannedNode(UnionExec([c.exec_node for c in cs]), [], cs)
    if isinstance(node, L.Window):
        c = lower(node.child, conf)
        # partition on the first expression's spec; WindowExec itself
        # validates that every expression shares it (window.py)
        first = node.window_exprs[0]
        inner = first.children[0] if isinstance(first, Alias) else first
        if _mesh_window_ok(c.exec_node, inner.spec, conf,
                           node.window_exprs):
            # the mesh window exchanges (or gathers) in-program, so no
            # planner exchange is inserted on this path
            return _stack_window_execs(c, node.window_exprs, False,
                                       conf=conf, mesh=True)
        cur, keys_partitioned = _ensure_window_distribution(
            c, inner.spec, conf)
        return _stack_window_execs(cur, node.window_exprs,
                                   keys_partitioned)
    if isinstance(node, L.Expand):
        c = lower(node.child, conf)
        from spark_rapids_tpu.exec.expand import ExpandExec
        ex = ExpandExec(node.projections, c.exec_node)
        exprs = [e for proj in node.projections for e in proj]
        return PlannedNode(ex, exprs, [c])
    if isinstance(node, L.Generate):
        c = lower(node.child, conf)
        from spark_rapids_tpu.exec.generate import GenerateExec
        ex = GenerateExec(node.generator, c.exec_node, outer=node.outer,
                          pos=node.pos, output_names=node.output_names)
        return PlannedNode(ex, [node.generator], [c])
    if isinstance(node, L.Repartition):
        c = lower(node.child, conf)
        if node.keys and conf.mesh_device_count > 1 \
                and not _schema_has_arrays(c.exec_node):
            # any hash-partition count rides the mesh collective (rows
            # route to device pid % mesh; round-2 verdict dropped the
            # num_partitions == deviceCount gate)
            from spark_rapids_tpu.exec.mesh_exec import MeshExchangeExec
            ex = MeshExchangeExec(node.keys, c.exec_node,
                                  conf.mesh_device_count,
                                  num_partitions=node.num_partitions)
            return PlannedNode(ex, list(node.keys), [c])
        if node.keys:
            part = HashPartitioning(node.keys, node.num_partitions)
        else:
            part = RoundRobinPartitioning(node.num_partitions)
        ex = ShuffleExchangeExec(part, c.exec_node)
        # NOTE: explicit repartition(n) is never coalesced below n
        # (Spark does not AQE-coalesce user-requested counts); only
        # planner-inserted shuffles (aggregation) get the coalescing
        # reader.  A downstream JOIN may still wrap this exchange in a
        # split-only skew reader (_aqe_join_reader), which can raise —
        # never lower — the effective partition count.  The map-side
        # tiny-input coalescer obeys the same contract: flag the
        # exchange so a sub-advisory map side still keeps all n
        # partitions non-degenerate (REPARTITION_BY_NUM).
        ex._no_map_coalesce = True
        return PlannedNode(ex, list(node.keys), [c])
    if isinstance(node, L.MapInPandas):
        from spark_rapids_tpu.exec.python_exec import MapInPandasExec
        c = lower(node.child, conf)
        ex = MapInPandasExec(node.fn, node.out_schema, c.exec_node)
        return PlannedNode(ex, [], [c])
    if isinstance(node, L.FlatMapGroupsInPandas):
        from spark_rapids_tpu.exec.python_exec import \
            FlatMapGroupsInPandasExec
        c = _cluster_on_keys(lower(node.child, conf), node.keys, conf)
        ex = FlatMapGroupsInPandasExec(
            [output_name(k) for k in node.keys], node.fn, node.out_schema,
            c.exec_node)
        return PlannedNode(ex, list(node.keys), [c])
    if isinstance(node, L.AggregateInPandas):
        from spark_rapids_tpu.exec.python_exec import AggregateInPandasExec
        c = _cluster_on_keys(lower(node.child, conf), node.keys, conf)
        ex = AggregateInPandasExec([output_name(k) for k in node.keys],
                                   node.udfs, c.exec_node)
        return PlannedNode(ex, list(node.keys), [c])
    if isinstance(node, L.FlatMapCoGroupsInPandas):
        from spark_rapids_tpu.exec.python_exec import \
            FlatMapCoGroupsInPandasExec
        lc = _cluster_on_keys(lower(node.left, conf), node.left_keys, conf,
                              force=True)
        rc = _cluster_on_keys(lower(node.right, conf), node.right_keys,
                              conf, force=True)
        ex = FlatMapCoGroupsInPandasExec(
            [output_name(k) for k in node.left_keys],
            [output_name(k) for k in node.right_keys],
            node.fn, node.out_schema, lc.exec_node, rc.exec_node)
        return PlannedNode(ex, list(node.left_keys) + list(node.right_keys),
                           [lc, rc])
    if isinstance(node, L.DataWrite):
        from spark_rapids_tpu.exec.write_exec import CreateDataWriteExec
        c = lower(node.child, conf)
        ex = CreateDataWriteExec(c.exec_node, node.path, node.fmt,
                                 partition_by=node.partition_by,
                                 options=node.options)
        return PlannedNode(ex, [], [c])
    raise TypeError(f"cannot lower {node!r}")


def _cluster_on_keys(c: PlannedNode, keys: list, conf: TpuConf,
                     force: bool = False) -> PlannedNode:
    """Hash-exchange on the grouping keys so every group lands wholly in
    one partition (Spark's ClusteredDistribution requirement for the
    grouped pandas execs); keyless grouped-agg collapses to a single
    partition.  ``force`` exchanges even single-partition children —
    cogrouped sides must agree on partition COUNT and router, not just
    co-locate groups."""
    from spark_rapids_tpu.exec.partitioning import SinglePartitioning
    nparts = c.exec_node.num_partitions(ExecCtx(backend="host"))
    if not keys:
        if nparts <= 1:
            return c
        exch = ShuffleExchangeExec(SinglePartitioning(), c.exec_node)
        return PlannedNode(exch, [], [c])
    if nparts <= 1 and not force:
        return c
    part = HashPartitioning(list(keys), conf.shuffle_partitions)
    exch = ShuffleExchangeExec(part, c.exec_node)
    return PlannedNode(exch, list(keys), [c])


def _mesh_sort_orders(orders, exec_node: PlanNode, conf: TpuConf):
    """Resolved SortOrders when this sort can run as a mesh broadcast
    sort, else None (non-column sort keys, array payloads, or no mesh
    configured keep the in-process global sort)."""
    if conf.mesh_device_count <= 1 or _schema_has_arrays(exec_node):
        return None
    from spark_rapids_tpu.exec.sortexec import resolve_orders
    try:
        return resolve_orders(orders, exec_node.output_schema)
    # enginelint: disable=RL001 (unresolvable sort key falls back to the in-process global sort)
    except Exception:  # noqa: BLE001 - any unresolvable key falls back
        return None


def _schema_has_arrays(*nodes: PlanNode) -> bool:
    """Mesh programs (shard_map bucketize/canonicalize, shard stacking)
    do not handle array payload columns yet; plans carrying them take
    the in-process path."""
    return any(isinstance(f.data_type, T.ArrayType)
               for n in nodes for f in n.output_schema)


def _aqe_join_exchange(c: PlannedNode, keys, conf: TpuConf) -> PlannedNode:
    """Hash-exchange one join side on its join keys, marked
    ``_aqe_inserted`` so the adaptive layer owns it: the stage-boundary
    pass puts a re-plan barrier above the join, and the re-optimizer may
    coalesce its reduce side, switch it to a broadcast, or drop the
    probe copy entirely.  Gated on the shuffled-hash-join conf (the
    engine's static join needs no co-partitioning) and skipped under
    the mesh (joins ride MeshJoinExec there) or when the side already
    exchanges on these keys (explicit repartition)."""
    from spark_rapids_tpu.exec.exchange import (ADAPTIVE_ENABLED,
                                                ShuffleExchangeExec)
    from spark_rapids_tpu.plan.adaptive import AQE_SHUFFLED_JOIN
    if not keys or not conf.get(AQE_SHUFFLED_JOIN) or \
            not conf.get(ADAPTIVE_ENABLED) or conf.mesh_device_count > 1 \
            or isinstance(c.exec_node, ShuffleExchangeExec):
        return c
    ex = ShuffleExchangeExec(
        HashPartitioning(list(keys), conf.shuffle_partitions), c.exec_node)
    ex._aqe_inserted = True
    return PlannedNode(ex, list(keys), [c])


def _aqe_join_reader(c: PlannedNode, conf: TpuConf) -> PlannedNode:
    """Joins read shuffles through an adaptive reader (Spark's
    OptimizeSkewedJoin scope): join sides have per-row semantics, so
    fanning a skewed hash partition out into several reader groups is
    safe — the stream side probes per batch and a build side is fully
    materialized either way.  Coalescing is allowed ONLY for exchanges
    the adaptive layer itself inserted (``_aqe_join_exchange``): an
    explicit ``repartition(n)`` promises n partitions, never REDUCED
    below the user's request (REPARTITION_BY_NUM contract; a skewed
    partition may still fan out, which preserves the requested
    parallelism floor), while an AQE-inserted exchange carries no user
    promise and small reduce partitions may merge to the advisory
    size."""
    from spark_rapids_tpu.exec.exchange import (ADAPTIVE_ENABLED,
                                                AdaptiveShuffleReaderExec,
                                                ShuffleExchangeExec)
    if not conf.get(ADAPTIVE_ENABLED) or \
            not isinstance(c.exec_node, ShuffleExchangeExec):
        return c
    reader = AdaptiveShuffleReaderExec(
        c.exec_node, allow_skew_split=True,
        allow_coalesce=getattr(c.exec_node, "_aqe_inserted", False))
    return PlannedNode(reader, [], [c])


def _split_window_exprs(exprs):
    """Separate window expressions out of a projection list.

    Handles windows at ANY depth: nested occurrences (e.g.
    ``x * 100 / sum(x).over(spec)``) are hoisted into generated columns
    and replaced by references (round-1 advisor finding: the old code
    only split top-level windows, letting nested ones crash projection
    eval)."""
    plain, windows = [], []
    counter = [0]

    def hoist(node):
        if isinstance(node, WindowExpression):
            name = f"_we{counter[0]}"
            counter[0] += 1
            windows.append(node.alias(name))
            return col(name)
        return node

    for e in exprs:
        inner = e.children[0] if isinstance(e, Alias) else e
        if isinstance(inner, WindowExpression):
            # generated name + re-alias: naming the appended window column
            # after an existing child column would shadow it at bind time
            name = f"_we{counter[0]}"
            counter[0] += 1
            windows.append(inner.alias(name))
            plain.append(col(name).alias(output_name(e)))
        else:
            plain.append(e.transform_up(hoist))
    return plain, windows


def _split_pandas_udfs(exprs):
    """Hoist PandasUDF occurrences (any depth) into generated columns
    evaluated by one ArrowEvalPythonExec (reference: Spark plans
    ArrowEvalPython below the projection)."""
    from spark_rapids_tpu.exec.python_exec import PandasUDF
    udfs, counter = [], [0]

    def fresh(u):
        if any(isinstance(s, PandasUDF) for c in u.children
               for s in c.walk()):
            raise ValueError(
                "nested pandas UDFs are not supported; materialize the "
                "inner UDF in a separate select() first")
        name = f"_pyudf{counter[0]}"   # ALWAYS a generated name: reusing a
        counter[0] += 1                # child column name would shadow it
        udfs.append((name, u))
        return name

    def hoist(n):
        if isinstance(n, PandasUDF):
            return col(fresh(n))
        return n

    plain = []
    for e in exprs:
        inner = e.children[0] if isinstance(e, Alias) else e
        if isinstance(inner, PandasUDF):
            plain.append(col(fresh(inner)).alias(output_name(e)))
        else:
            plain.append(e.transform_up(hoist))
    return plain, udfs


def _window_key_names(keys) -> tuple | None:
    """Canonical column-name tuple for a key list, or None when any key
    is not a plain column reference (structural comparison is then not
    attempted and an exchange is inserted conservatively)."""
    from spark_rapids_tpu.expr.core import UnresolvedAttribute
    names = []
    for k in keys:
        if isinstance(k, Alias):
            k = k.children[0]
        if not isinstance(k, UnresolvedAttribute):
            return None
        names.append(k.name)
    return tuple(names)


def _mesh_window_ok(child_exec: PlanNode, spec, conf: TpuConf,
                    windows) -> bool:
    """True when this spec's window functions lower to MeshWindowExec:
    a mesh is active, the conf gate is on, the spec has partition or
    order keys (a fully global unordered window keeps the in-process
    bounded-memory stream — gathering it would be a regression), the
    child schema is mesh-shardable, and no expression is a pandas
    window UDF (a mixed native+UDF spec falls back entirely so both
    halves see the same distribution)."""
    from spark_rapids_tpu.conf import MESH_WINDOW_ENABLED
    if conf.mesh_device_count <= 1 or not conf.get(MESH_WINDOW_ENABLED):
        return False
    if not (spec.partition_by or spec.order_by):
        return False
    if _schema_has_arrays(child_exec):
        return False
    from spark_rapids_tpu.exec.python_exec import PandasWindowUDF
    for w in windows:
        inner = w.children[0] if isinstance(w, Alias) else w
        if isinstance(inner.function, PandasWindowUDF):
            return False
    return True


def _ensure_window_distribution(cur: PlannedNode, spec,
                                conf: TpuConf) -> tuple[PlannedNode, bool]:
    """Hash-partition on the window partition keys so the window program
    runs per partition instead of collapsing all upstream parallelism
    into one global batch (Spark's EnsureRequirements inserts the same
    exchange for ClusteredDistribution; reference GpuWindowExec.scala:92
    needs one batch per partition GROUP only).  Skips the exchange when
    the child is already hash-partitioned on a subset of the window keys
    — rows equal on the window keys are then already co-located — and
    where the child is a final aggregate grouped by the window's keys
    and more, whose own exchange is then made on the window's keys."""
    if not spec.partition_by:
        return cur, False
    if cur.exec_node.num_partitions(ExecCtx(backend="host")) <= 1:
        return cur, False
    want = _window_key_names(spec.partition_by)
    if want is not None:
        node = cur.exec_node
        # window output preserves its child's distribution: look through
        # WindowExecs stacked by earlier specs of the same projection
        while isinstance(node, WindowExec) and node._keys_partitioned:
            node = node.children[0]
        if isinstance(node, ShuffleExchangeExec) and \
                isinstance(node.partitioning, HashPartitioning):
            have = _window_key_names(node.partitioning._keys)
            if have and set(have) <= set(want):
                return cur, True
        # a final aggregate grouped by the window's keys and more: the
        # exchange it reads clusters its groups just as well on the
        # window's keys alone (rows equal on all the group keys are equal
        # on some of them), and the window needs no exchange of its own
        exch = _aggregate_exchange(node)
        if exch is not None:
            have = _window_key_names(exch.partitioning._keys)
            if have and set(have) <= set(want):
                return cur, True    # the aggregate keeps its distribution
            if have and set(want) <= set(have):
                exch.partitioning = HashPartitioning(
                    [col(n) for n in want], exch.partitioning.num_partitions)
                exch.partitioning.bind(exch.children[0].output_schema)
                return cur, True
    part = HashPartitioning(list(spec.partition_by),
                            conf.shuffle_partitions)
    exch = ShuffleExchangeExec(part, cur.exec_node)
    return PlannedNode(exch, list(spec.partition_by), [cur]), True


def _aggregate_exchange(node: PlanNode) -> ShuffleExchangeExec | None:
    """The hash exchange a final ``HashAggregateExec`` reads its partial
    buffers from (through the adaptive reader), or None."""
    from spark_rapids_tpu.exec.exchange import AdaptiveShuffleReaderExec
    if not (isinstance(node, HashAggregateExec) and node.mode == "final"):
        return None
    child = node.children[0]
    if isinstance(child, AdaptiveShuffleReaderExec):
        child = child.children[0]
    if isinstance(child, ShuffleExchangeExec) \
            and isinstance(child.partitioning, HashPartitioning):
        return child
    return None


def _lower_project(node: L.Project, conf: TpuConf) -> PlannedNode:
    c = lower(node.child, conf)
    from spark_rapids_tpu.udf import maybe_compile_udfs
    exprs = maybe_compile_udfs(node.exprs, conf)
    exprs, pandas_udfs = _split_pandas_udfs(exprs)
    if pandas_udfs:
        from spark_rapids_tpu.exec.python_exec import ArrowEvalPythonExec
        ex = ArrowEvalPythonExec(pandas_udfs, c.exec_node)
        c = PlannedNode(ex, [u for _, u in pandas_udfs], [c])
    plain, windows = _split_window_exprs(exprs)
    if not windows:
        ex = ProjectExec(exprs, c.exec_node)
        return PlannedNode(ex, list(exprs), [c])
    # one WindowExec per distinct spec (Spark's planner does the same),
    # told apart by the specs' content (spec_key), then the final
    # projection over the appended columns
    by_spec: dict = {}
    for w in windows:
        inner = w.children[0] if isinstance(w, Alias) else w
        by_spec.setdefault(spec_key(inner.spec), (inner.spec, []))[1] \
            .append(w)
    cur = c
    for spec, spec_windows in by_spec.values():
        if _mesh_window_ok(cur.exec_node, spec, conf, spec_windows):
            cur = _stack_window_execs(cur, spec_windows, False,
                                      conf=conf, mesh=True)
            continue
        cur, keys_partitioned = _ensure_window_distribution(cur, spec, conf)
        cur = _stack_window_execs(cur, spec_windows, keys_partitioned)
    ex = ProjectExec(plain, cur.exec_node)
    return PlannedNode(ex, list(plain), [cur])


def _stack_window_execs(cur: PlannedNode, spec_windows,
                        keys_partitioned: bool, conf: TpuConf = None,
                        mesh: bool = False) -> PlannedNode:
    """Plan one spec's window expressions, splitting pandas window UDFs
    into WindowInPandasExec (reference GpuWindowInPandasExec) and
    native functions into WindowExec — or MeshWindowExec when the
    caller passed ``mesh=True`` (_mesh_window_ok held, so the list is
    all-native) — stacked over ``cur``."""
    from spark_rapids_tpu.exec.python_exec import (PandasWindowUDF,
                                                   WindowInPandasExec)

    def _is_udf(w):
        inner = w.children[0] if isinstance(w, Alias) else w
        return isinstance(inner.function, PandasWindowUDF)

    native_ws = [w for w in spec_windows if not _is_udf(w)]
    udf_ws = [w for w in spec_windows if _is_udf(w)]
    if native_ws:
        if mesh:
            from spark_rapids_tpu.exec.mesh_region import MeshWindowExec
            ex = MeshWindowExec(native_ws, cur.exec_node,
                                conf.mesh_device_count)
        else:
            ex = WindowExec(native_ws, cur.exec_node,
                            keys_partitioned=keys_partitioned)
        cur = PlannedNode(ex, list(native_ws), [cur])
    if udf_ws:
        ex = WindowInPandasExec(udf_ws, cur.exec_node,
                                keys_partitioned=keys_partitioned)
        cur = PlannedNode(ex, list(udf_ws), [cur])
    return cur


def _lower_aggregate(node: L.Aggregate, conf: TpuConf) -> PlannedNode:
    c = lower(node.child, conf)
    # holistic aggregates (percentile) have no mergeable intermediate:
    # neither the partial/final split nor the mesh program can run
    # them — plan a whole-input complete aggregation
    holistic = any(getattr(sub, "requires_complete", False)
                   for e in node.agg_exprs for sub in e.walk())
    if conf.mesh_device_count > 1 and not holistic \
            and not _schema_has_arrays(c.exec_node):
        # grouped AND grand aggregates both lower to the mesh program
        # (grand: partials merge on device 0 inside the shard_map) — a
        # grand aggregate over a mesh join's per-device outputs must
        # not fall into the single-device complete path (matrix-sweep
        # finding: q96 under mesh8 mixed devices in one jit)
        from spark_rapids_tpu.exec.mesh_exec import MeshAggregateExec
        ex = MeshAggregateExec(node.group_exprs, node.agg_exprs, c.exec_node,
                               conf.mesh_device_count)
        return PlannedNode(ex, list(node.agg_exprs), [c])
    nparts = c.exec_node.num_partitions(ExecCtx(backend="host"))
    if node.group_exprs and nparts > 1 and not holistic:
        partial = HashAggregateExec(node.group_exprs, node.agg_exprs,
                                    c.exec_node, mode="partial")
        pmeta = PlannedNode(partial, list(node.agg_exprs), [c])
        group_cols = [col(n) for n in partial._group_names]
        shuffle = ShuffleExchangeExec(
            HashPartitioning(group_cols, conf.shuffle_partitions), partial)
        smeta = PlannedNode(shuffle, group_cols, [pmeta])
        from spark_rapids_tpu.exec.exchange import (ADAPTIVE_ENABLED,
                                                    AdaptiveShuffleReaderExec)
        agg_child = shuffle
        if conf.get(ADAPTIVE_ENABLED):
            reader = AdaptiveShuffleReaderExec(shuffle)
            smeta = PlannedNode(reader, [], [smeta])
            agg_child = reader
        final = HashAggregateExec.final_from_partial(partial, agg_child)
        return PlannedNode(final, list(node.agg_exprs), [smeta])
    ex = HashAggregateExec(node.group_exprs, node.agg_exprs, c.exec_node,
                           mode="complete")
    return PlannedNode(ex, list(node.agg_exprs), [c])


# ---------------------------------------------------------------------------
# tagging + conversion
# ---------------------------------------------------------------------------

class TpuOverrides:
    """Tag the planned tree and realize backends + transitions."""

    def __init__(self, conf: TpuConf):
        self.conf = conf

    def prepare(self, root: PlannedNode, explain: bool = False) -> PlanNode:
        """The full planning pipeline; ``apply`` and the quiet plan
        builds both run THIS, so every future pass reaches both paths
        (review finding: a hand-duplicated pass list diverged)."""
        verify = self._verifier()
        self._tag(root)
        verify(root, "tag")
        self._insert_coalesce(root)
        verify(root, "coalesce")
        self._insert_transitions(root)
        verify(root, "transitions")
        self._align_mesh_outputs(root)
        verify(root, "mesh_align")
        self._mark_shared_scans(root)
        verify(root, "shared_scans")
        self._stamp_lineage(root)
        verify(root, "stamp_lineage")
        self._lower_cluster(root)
        verify(root, "cluster")
        explain_mode = self.conf.explain
        if explain and explain_mode and explain_mode != "NONE":
            text = self.explain(root, only_fallback=(explain_mode
                                                     == "NOT_ON_TPU"))
            if text:
                print(text)
        if self.conf.test_enabled:
            self._assert_on_tpu(root)
        self._insert_stage_boundaries(root)
        verify(root, "stage_boundaries")
        self._fuse_stages(root)
        verify(root, "fusion")
        self._form_mesh_regions(root)
        verify(root, "mesh_regions")
        return root.exec_node

    def _verifier(self):
        """Invariant verification hook (plan/verify.py).

        Default (``spark.rapids.sql.verify.plan`` on): one walk after
        the FINAL rewrite pass — the interim hooks are no-ops, so the
        steady state pays a single O(nodes) pass per prepare.  With
        ``spark.rapids.sql.verify.plan.everyPass`` (tests)
        every hook verifies, so a violation names the pass that
        introduced it.  A no-op callable when verification is off."""
        from spark_rapids_tpu.plan.verify import (PLAN_VERIFY,
                                                  PLAN_VERIFY_EVERY_PASS,
                                                  verify_plan)
        if not self.conf.get(PLAN_VERIFY):
            return lambda root, pass_name: None
        every_pass = self.conf.get(PLAN_VERIFY_EVERY_PASS)

        def check(root: PlannedNode, pass_name: str) -> None:
            if every_pass or pass_name == "mesh_regions":
                verify_plan(root.exec_node, self.conf, pass_name)

        return check

    def _insert_stage_boundaries(self, root: PlannedNode) -> None:
        """Wrap each join whose build side reads an AQE-inserted shuffle
        in a ``StageBoundaryExec`` (exec/stage_boundary.py): the barrier
        at which plan/adaptive.py re-plans the join from the build
        stage's materialized statistics.

        Runs on the realized exec tree BEFORE fusion: the boundary is a
        pipeline breaker (never fused), and the dynamic-filter targets
        must be resolved while the probe-side scan is still a visible
        leaf — fusion later hides the operators above it inside a
        FusedStageExec, but the scan object itself stays shared, so the
        captured reference remains live."""
        # express lane: the control plane routed this plan below its
        # learned wall threshold — the AQE stage machinery (boundary
        # insertion + runtime re-planning) costs more than re-planning
        # could save on a sub-threshold query.  Raw settings read: the
        # marker is stamped by control/loop.py, but planning must not
        # import the control package (it may be disabled/absent).
        if str(self.conf.settings.get(
                "spark.rapids.control.express", "")).lower() \
                in ("true", "1", "yes"):
            return
        from spark_rapids_tpu.exec.exchange import ADAPTIVE_ENABLED
        if not self.conf.get(ADAPTIVE_ENABLED):
            return
        from spark_rapids_tpu.exec.joins import JoinExec
        from spark_rapids_tpu.exec.stage_boundary import StageBoundaryExec
        from spark_rapids_tpu.plan.adaptive import (dynamic_filter_targets,
                                                    unwrap_exchange)
        done: dict[int, PlanNode] = {}

        def walk(node: PlanNode) -> PlanNode:
            got = done.get(id(node))
            if got is not None:
                return got
            new_children = tuple(walk(c) for c in node.children)
            if any(a is not b for a, b in zip(new_children, node.children)):
                node.children = new_children
            out = node
            if type(node) is JoinExec and len(node.children) == 2:
                ex = unwrap_exchange(node.children[1])
                if ex is not None and getattr(ex, "_aqe_inserted", False):
                    out = StageBoundaryExec(node,
                                            dynamic_filter_targets(node))
            done[id(node)] = out
            return out

        root.exec_node = walk(root.exec_node)

    def _fuse_stages(self, root: PlannedNode) -> None:
        """Collapse runs of adjacent elementwise operators into
        ``FusedStageExec`` nodes — one jit region and one dispatch per
        batch instead of one per operator (exec/fused.py; the
        whole-stage-codegen analog, PAPER.md §L3).

        Runs LAST, on the realized exec tree only: transitions,
        coalesces, and exchanges are already placed, so a fusible run
        can never cross a backend switch or a pipeline breaker — any
        non-fusible node simply terminates the run.  The meta tree is
        left untouched (conversion EXPLAIN shows per-operator nodes;
        EXPLAIN ANALYZE shows the fused stages with what they
        replaced)."""
        from spark_rapids_tpu.exec.compile_cache import (FUSION_ENABLED,
                                                         FUSION_MIN_OPS)
        if not self.conf.get(FUSION_ENABLED):
            return
        from spark_rapids_tpu.exec.fused import FusedStageExec, fusible
        min_ops = max(2, self.conf.get(FUSION_MIN_OPS))
        done: dict[int, PlanNode] = {}

        def walk(node: PlanNode) -> PlanNode:
            got = done.get(id(node))
            if got is not None:
                return got
            if fusible(node):
                run = [node]  # outermost-first
                cur = node.children[0]
                while fusible(cur):
                    run.append(cur)
                    cur = cur.children[0]
                if len(run) >= min_ops:
                    below = walk(cur)
                    ops = list(reversed(run))  # innermost-first
                    if below is not cur:
                        ops[0].children = (below,)
                    fused = FusedStageExec(ops)
                    done[id(node)] = fused
                    return fused
            new_children = tuple(walk(c) for c in node.children)
            if any(a is not b for a, b in zip(new_children, node.children)):
                node.children = new_children
            done[id(node)] = node
            return node

        root.exec_node = walk(root.exec_node)

        # Donation safety: a fused stage may only donate its input batch
        # when that batch is provably exclusive.  Two producers break
        # exclusivity: a plan-shared subtree (CTE scanned once, joined
        # twice — TPC-DS q1) yields the same batch objects to every
        # parent, and a shared-output scan (io/scan.py share_output:
        # several scan NODES over one table share one parked
        # materialization — TPC-DS q49) aliases device buffers across
        # plan-distinct nodes.  Pass-through nodes can forward either
        # upward unchanged, so any such producer anywhere BELOW the
        # stage disables donation (conservative: a materializing node
        # in between would make it safe again, but proving that per
        # node type is not worth a deleted-buffer crash).
        parent_counts: dict[int, int] = {}
        nodes: dict[int, PlanNode] = {}

        def count(node: PlanNode) -> None:
            if id(node) in nodes:
                return
            nodes[id(node)] = node
            for c in node.children:
                parent_counts[id(c)] = parent_counts.get(id(c), 0) + 1
                count(c)

        count(root.exec_node)

        def exclusive(node: PlanNode, seen: set) -> bool:
            if id(node) in seen:
                return True
            seen.add(id(node))
            if parent_counts.get(id(node), 0) > 1 or \
                    getattr(node, "share_output", False):
                return False
            return all(exclusive(c, seen) for c in node.children)

        for node in nodes.values():
            if isinstance(node, FusedStageExec) and \
                    not exclusive(node.children[0], set()):
                node.donate_ok = False

    def _form_mesh_regions(self, root: PlannedNode) -> None:
        """Grow each mesh collective (aggregate / exchange / sort /
        window) downward into a MeshRegionExec absorbing the contiguous
        pipeline below it — whole-stage fusion's elementwise set
        (filter / non-partition-aware project / FusedStageExec) PLUS
        the collective interiors MeshJoinExec and MeshWindowExec, so a
        region can hold scan→filter→join→project→agg as ONE per-device
        program (exec/mesh_region.py).  The run grows through a join's
        STREAM side (children[0]); its build subtree stays a real plan
        edge (the region drains it host-side and stacks it as an extra
        program input) and is walked separately so nested collectives
        below the build form their own regions.

        Runs after fusion on the realized exec tree: transitions and
        coalesces are placed, so an absorbable run can never cross a
        backend switch.  Members keep their original child links
        (lineage recovery and host fallback replay them per batch);
        ``mesh_regions`` counts formed regions at plan time."""
        from spark_rapids_tpu.conf import MESH_REGIONS_ENABLED
        if self.conf.mesh_device_count <= 1 or \
                not self.conf.get(MESH_REGIONS_ENABLED):
            return
        from spark_rapids_tpu.exec.fused import FusedStageExec, fusible
        from spark_rapids_tpu.exec.mesh_exec import (MeshAggregateExec,
                                                     MeshExchangeExec,
                                                     MeshJoinExec)
        from spark_rapids_tpu.exec.mesh_region import (MeshRegionExec,
                                                       MeshSortExec,
                                                       MeshWindowExec)
        from spark_rapids_tpu.obs.registry import get_registry
        terminals = (MeshAggregateExec, MeshExchangeExec, MeshSortExec,
                     MeshWindowExec)
        done: dict[int, PlanNode] = {}

        def absorbable(n: PlanNode) -> bool:
            return fusible(n) or type(n) is FusedStageExec \
                or type(n) in (MeshJoinExec, MeshWindowExec)

        def walk(node: PlanNode) -> PlanNode:
            got = done.get(id(node))
            if got is not None:
                return got
            if type(node) in terminals:
                run = []  # outermost-first members below the terminal
                cur = node.children[0]
                while absorbable(cur):
                    run.append(cur)
                    cur = cur.children[0]  # join: the STREAM side
                if run:
                    below = walk(cur)
                    members = list(reversed(run))  # innermost-first
                    if below is not cur:
                        members[0].children = \
                            (below,) + tuple(members[0].children[1:])
                    # build subtrees walked BEFORE the region is built:
                    # its children list snapshots each join's build edge
                    for m in members:
                        if isinstance(m, MeshJoinExec):
                            nb = walk(m.children[1])
                            if nb is not m.children[1]:
                                m.children = (m.children[0], nb)
                    region = MeshRegionExec(node, members)
                    # the terminal now yields through the region, which
                    # owns the mesh->single-device boundary
                    region.align_output = node.align_output
                    node.align_output = False
                    get_registry().inc("mesh_regions")
                    done[id(node)] = region
                    return region
            new_children = tuple(walk(c) for c in node.children)
            if any(a is not b for a, b in zip(new_children, node.children)):
                node.children = new_children
            done[id(node)] = node
            return node

        root.exec_node = walk(root.exec_node)

    def apply(self, root: PlannedNode) -> PlanNode:
        return self.prepare(root, explain=True)

    def _stamp_lineage(self, root: PlannedNode) -> None:
        """Stamp every exchange with the effective conf's fingerprint.
        Stage recovery (exec/recovery.py) re-executes lost map
        partitions from the exchange's recorded lineage, which is only
        deterministic under the settings the original map ran with —
        the stamp binds the two so a recompute under a drifted conf
        fails loudly instead of producing a silently different
        shuffle."""
        from spark_rapids_tpu.exec.exchange import ShuffleExchangeExec
        from spark_rapids_tpu.exec.recovery import conf_fingerprint
        fp = conf_fingerprint(self.conf)

        def walk(node) -> None:
            if isinstance(node, ShuffleExchangeExec):
                node._conf_fp = fp
            for c in node.children:
                walk(c)

        walk(root.exec_node)

    def _lower_cluster(self, root: PlannedNode) -> None:
        """Tag exchanges the cluster runtime may shard over the worker
        pool (cluster/exec.py reads the tag at materialization time).
        Gated on the RAW setting so ``cluster.mode=off`` — the default —
        never imports the cluster package and the planned tree is
        byte-identical to the single-process engine.

        Only hash and single partitionings are clusterable: their
        partition ids are a pure per-batch function, so independent
        workers computing them agree.  Round-robin and range
        partitionings build global ``prepare()`` state from ALL map
        batches (a running row offset; sampled range bounds) that
        cannot be split across processes without changing results."""
        if self.conf.settings.get("spark.rapids.cluster.mode",
                                  "off") == "off":
            return
        from spark_rapids_tpu.exec.exchange import ShuffleExchangeExec
        from spark_rapids_tpu.exec.partitioning import (HashPartitioning,
                                                        SinglePartitioning)
        seen: set[int] = set()

        def walk(node) -> None:
            if id(node) in seen:
                return
            seen.add(id(node))
            if isinstance(node, ShuffleExchangeExec) and isinstance(
                    node.partitioning,
                    (HashPartitioning, SinglePartitioning)):
                node._cluster_ok = True
            for c in node.children:
                walk(c)

        walk(root.exec_node)

    def _mark_shared_scans(self, root: PlannedNode) -> None:
        """Scans whose (files, columns, pushdown) fingerprint appears
        more than once in the final exec tree share one spillable
        materialization per partition (io/scan.py share_output).
        TPC-DS q28 reads store_sales 12x through its bucket branches —
        without sharing, each instance re-decodes, re-encodes, and
        re-transfers the same table (reference analog: ReuseExchange
        over identical subtrees, here applied at the leaf)."""
        from spark_rapids_tpu.conf import SCAN_REUSE
        from spark_rapids_tpu.io.scan import FileScanExec
        if not self.conf.get(SCAN_REUSE):
            return
        # count CONSUMPTIONS per fingerprint, not instances: a builder
        # reusing one DataFrame makes the exec tree a DAG whose single
        # scan object is pulled once per referencing branch — each pull
        # re-executes without sharing
        groups: dict = {}

        def walk(n: PlanNode):
            if isinstance(n, FileScanExec):
                groups.setdefault(n.scan_fingerprint(), []).append(n)
            for c in n.children:
                walk(c)

        walk(root.exec_node)
        for g in groups.values():
            if len(g) > 1:
                for n in g:
                    n.share_output = True
                    # consumptions of this fingerprint in the tree: the
                    # LAST consumer to drain a partition closes the
                    # parked spillable entries (io/scan.py), so a shared
                    # table doesn't stay registered until catalog close
                    n.share_consumers = len(g)

    def root_backend(self, root: PlannedNode) -> str:
        return root.backend

    def _assert_on_tpu(self, meta: PlannedNode) -> None:
        """Test mode (spark.rapids.sql.test.enabled): the WHOLE plan
        must run on the device, except exec names listed in
        spark.rapids.sql.test.allowedNonTpu (reference
        GpuTransitionOverrides.assertIsOnTheGpu, :322-367)."""
        from spark_rapids_tpu.conf import TEST_ALLOWED_NONTPU
        allowed = {n.strip() for n in
                   self.conf.get(TEST_ALLOWED_NONTPU).split(",")
                   if n.strip()}
        bad = []

        def walk(m: PlannedNode):
            if m.backend != "device" and m.name not in allowed:
                bad.append(f"{m.name}: {'; '.join(m.reasons) or 'host'}")
            for ch in m.children:
                walk(ch)

        walk(meta)
        if bad:
            raise AssertionError(
                "plan is not fully on the TPU (spark.rapids.sql.test."
                "enabled):\n  " + "\n  ".join(bad))

    # -- tagging -------------------------------------------------------
    def _tag(self, meta: PlannedNode) -> None:
        for ch in meta.children:
            self._tag(ch)
        conf = self.conf
        if not conf.sql_enabled:
            meta.will_not_work("spark.rapids.sql.enabled is false")
        key = f"spark.rapids.sql.exec.{meta.name}"
        if not conf.is_op_enabled(key):
            meta.will_not_work(f"{key} is disabled")
        bound = list(getattr(meta.exec_node, "bound_exprs", []))
        for e in list(meta.exprs) + bound:
            if not isinstance(e, Expression):
                continue
            for sub in e.walk():
                cname = type(sub).__name__
                ekey = f"spark.rapids.sql.expression.{cname}"
                if not conf.is_op_enabled(ekey):
                    meta.will_not_work(f"{ekey} is disabled")
                try:
                    ds = sub.device_supported
                except TypeError:
                    # dtype-dependent check on an unbound tree: the bound
                    # copy (exec_node.bound_exprs) carries the decision
                    ds = True
                if ds is False:
                    meta.will_not_work(
                        f"expression {cname} has no device kernel")
        self._tag_special(meta)
        meta.backend = "host" if meta.reasons else "device"

    def _tag_special(self, meta: PlannedNode) -> None:
        ex = meta.exec_node
        # MapType has no device representation (types.MapType): a node
        # whose OWN output carries a map runs on the host, and so does a
        # node whose CHILD outputs one — the host->device transition
        # would otherwise have to upload the map column (review repro:
        # df.select(k) over a map-carrying scan crashed in
        # host_to_device).  The node ABOVE the map-dropping projection
        # returns to the device (reference: unsupported-type tagging,
        # RapidsMeta.willNotWorkOnGpu).
        if any(isinstance(f.data_type, T.MapType)
               for f in ex.output_schema) or \
           any(isinstance(f.data_type, T.MapType)
               for ch in ex.children for f in ch.output_schema):
            meta.will_not_work("map columns are host-only")
        # the write sink consumes its child's batches directly (Arrow
        # encode is host-side either way) — it follows the child's
        # backend so no transition lands between child and sink, and a
        # device child keeps the cluster runtime attached to the job
        from spark_rapids_tpu.exec.write_exec import CreateDataWriteExec
        if isinstance(ex, CreateDataWriteExec) and any(
                ch.backend != "device" for ch in meta.children):
            meta.will_not_work("write sink follows its host child")
        if isinstance(ex, WindowExec):
            from spark_rapids_tpu.expr import aggregates as A
            for w, dt in zip(ex._wexprs, ex._out_dtypes):
                f = w.function
                if isinstance(f, (A.Min, A.Max)) and isinstance(
                        dt, T.StringType):
                    meta.will_not_work(
                        "windowed min/max over strings has no device kernel")
        from spark_rapids_tpu.exec.mesh_exec import MeshAggregateExec
        agg_ex = ex._layout if isinstance(ex, MeshAggregateExec) else \
            ex if isinstance(ex, HashAggregateExec) else None
        if agg_ex is not None and agg_ex._aggs:
            # float-aggregation gates (reference ENABLE_FLOAT_AGG +
            # the incompat machinery, RapidsConf.scala:461-492):
            # variableFloatAgg=false refuses ANY float aggregation
            # (reduction order varies); exactDoubleAggregation=true
            # refuses DOUBLE ones specifically — TPU f64 is a
            # float32-pair emulation and sums can deviate from exact
            # f64 (measured by scripts/verify_exprs_tpu.py).
            # Mesh lowering (MeshAggregateExec) shares the layout, so
            # the gates cover both single-chip and mesh aggregates.
            from spark_rapids_tpu.conf import (ALLOW_FLOAT_AGG,
                                               EXACT_DOUBLE_AGG)
            in_types = [a.input.dtype for a in agg_ex._aggs
                        if a.input is not None]
            if not self.conf.get(ALLOW_FLOAT_AGG) and any(
                    t.fractional for t in in_types):
                meta.will_not_work(
                    "float aggregation disabled "
                    "(spark.rapids.sql.variableFloatAgg.enabled)")
            if self.conf.get(EXACT_DOUBLE_AGG) and any(
                    isinstance(t, T.DoubleType) for t in in_types):
                meta.will_not_work(
                    "double aggregation forced to host for exact f64 "
                    "(spark.rapids.sql.exactDoubleAggregation)")

    # -- mesh output alignment ------------------------------------------
    def _align_mesh_outputs(self, meta: PlannedNode) -> None:
        """Set align_output on mesh execs whose per-device batches flow
        (possibly through per-batch operators, which preserve placement)
        into a non-mesh BATCH-COMBINING consumer — a program jitting
        batches from different devices crashes (q96-under-mesh matrix
        finding).  Per-batch consumers (filter/project/limit) pass
        placement through so the distributed pipeline is not funneled
        through one chip; unconsumed producers at the root stay
        unaligned — collect's per-batch D2H handles any device."""
        from spark_rapids_tpu.exec.mesh_exec import _MeshOutputMixin

        def walk(m: PlannedNode) -> list:
            # returns mesh execs whose (unaligned) per-device output
            # reaches m's own output
            producers = [p for ch in m.children for p in walk(ch)]
            ex = m.exec_node
            if isinstance(ex, _MeshOutputMixin):
                # a mesh exec consumes its children mesh-aware (device
                # affinity in place_shards); only ITS output escapes
                return [ex]
            if producers and ex.combines_batches:
                for p in producers:
                    p.align_output = True
                return []
            return producers

        walk(meta)

    # -- coalesce insertion (reference GpuTransitionOverrides
    # insertCoalesce :224-244 / optimizeCoalesce :96-116) ---------------
    def _insert_coalesce(self, meta: PlannedNode) -> None:
        """Insert CoalesceBatchesExec where an operator's
        children_coalesce_goal demands batching its child does not
        already satisfy.  A declared ``TargetSize(0)`` resolves to
        ``spark.rapids.sql.batchSizeBytes`` (reference: the goal is
        built from conf at planning, GpuExec.scala:71-86 +
        RapidsConf.scala:364)."""
        from spark_rapids_tpu.exec import CoalesceBatchesExec
        from spark_rapids_tpu.exec.core import TargetSize
        for ch in meta.children:
            self._insert_coalesce(ch)
        goals = meta.exec_node.children_coalesce_goal
        if not any(g is not None for g in goals):
            return
        new_children = []
        new_metas = []
        for ch, goal in zip(meta.children, goals):
            if goal is None or ch.exec_node.output_batching is not None \
                    and ch.exec_node.output_batching.satisfies(goal):
                new_children.append(ch.exec_node)
                new_metas.append(ch)
                continue
            if isinstance(goal, TargetSize) and goal.size <= 0:
                goal = TargetSize(self.conf.batch_size_bytes)
            co = CoalesceBatchesExec(goal, ch.exec_node)
            cometa = PlannedNode(co, [], [ch], backend=ch.backend)
            new_children.append(co)
            new_metas.append(cometa)
        assert len(new_children) == len(meta.exec_node.children)
        meta.exec_node.children = tuple(new_children)
        meta.children = new_metas

    # -- transitions ---------------------------------------------------
    def _insert_transitions(self, meta: PlannedNode) -> None:
        for ch in meta.children:
            self._insert_transitions(ch)
        new_children = []
        for ch in meta.children:
            if ch.backend != meta.backend:
                new_children.append(BackendSwitchExec(ch.exec_node,
                                                      ch.backend))
            else:
                new_children.append(ch.exec_node)
        if meta.children:
            kids = list(meta.exec_node.children)
            # planner invariant: the meta tree mirrors the exec tree; a
            # mismatch is a lowering bug and silently skipping it would
            # run a child on the wrong backend (round-1 advisor finding)
            assert len(kids) == len(new_children), (
                f"planner arity mismatch at {meta.name}: exec has "
                f"{len(kids)} children, meta has {len(new_children)}")
            meta.exec_node.children = tuple(new_children)

    # -- explain -------------------------------------------------------
    def explain(self, meta: PlannedNode, only_fallback: bool = False,
                indent: int = 0) -> str:
        marker = "*" if meta.backend == "device" else "!"
        line = "  " * indent + f"{marker} {meta.exec_node.node_desc()}"
        if meta.reasons:
            line += "  <-- " + "; ".join(meta.reasons)
        lines = [] if (only_fallback and not meta.reasons) else [line]
        for ch in meta.children:
            sub = self.explain(ch, only_fallback, indent + 1)
            if sub:
                lines.append(sub)
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE: post-execution plan annotation
# ---------------------------------------------------------------------------

#: metrics shown inline on every node that recorded them, in this order
_CORE_METRICS = ("totalTime", "numOutputBatches", "numOutputRows")


def _fmt_metric(name: str, v: float) -> str:
    if name.endswith(("Time", "_s")) or isinstance(v, float) and v != int(v):
        return f"{name}={v:.3f}s" if name.endswith(("Time", "_s")) \
            else f"{name}={v:.3f}"
    return f"{name}={int(v)}"


def explain_analyze(plan, ctx) -> str:
    """Render the EXECUTED plan tree annotated with runtime metrics —
    the EXPLAIN ANALYZE counterpart of :meth:`TpuOverrides.explain`
    (reference: GpuExec metrics surfaced in the Spark SQL UI per node).

    ``plan`` is the exec-tree root (a PlanNode); metrics come from the
    ExecCtx the plan ran under, keyed by node identity, so repeated
    EXPLAIN ANALYZE calls over one execution are stable.  Nodes carry
    ``[time=.. batches=.. rows=..]`` plus any extra recorded metrics
    (spills, retries, stage recoveries) sorted by name; a footer gives
    the query/trace ids and the process-wide counters so shuffle and
    memory activity not attributable to a single node is still
    visible."""
    lines: list[str] = []

    def walk(node, indent: int) -> None:
        key = f"{type(node).__name__}@{id(node):x}"
        m = ctx.metrics.get(key)
        line = "  " * indent + f"* {node.node_desc()}"
        if m is not None and m.values:
            parts = [_fmt_metric(k, m.values[k]) for k in _CORE_METRICS
                     if k in m.values]
            parts += [_fmt_metric(k, v) for k, v in sorted(m.values.items())
                      if k not in _CORE_METRICS]
            line += "  [" + ", ".join(parts) + "]"
        lines.append(line)
        for c in node.children:
            walk(c, indent + 1)

    walk(plan, 0)
    lines.append("")
    lines.append(f"query_id={ctx.query_id} trace_id={ctx.trace_id}")
    cat = ctx.cache.get("catalog")
    if cat is not None and getattr(cat, "metrics", None):
        parts = [_fmt_metric(k, v) for k, v in sorted(cat.metrics.items())
                 if isinstance(v, (int, float))]
        if parts:
            lines.append("catalog: " + ", ".join(parts))
    gov = getattr(cat, "governor", None) if cat is not None else None
    if gov is not None:
        # this query's slice of the cross-query HBM ledger: live/pinned/
        # peak device bytes as the governor attributed them
        stats = gov.query_stats(ctx.query_id).get(ctx.query_id)
        if stats:
            parts = [_fmt_metric(k, stats[k]) for k in
                     ("device_bytes", "pinned_bytes", "peak_bytes")
                     if k in stats]
            lines.append("governor: " + ", ".join(parts))
    from spark_rapids_tpu.obs.registry import get_registry
    counters = get_registry().snapshot()["counters"]
    if counters:
        parts = [_fmt_metric(k, v) for k, v in sorted(counters.items())]
        lines.append("counters: " + ", ".join(parts))
    return "\n".join(lines)
