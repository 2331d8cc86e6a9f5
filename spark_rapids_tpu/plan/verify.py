"""Physical-plan invariant verifier: mechanically check, after plan
rewrites, the contracts the planner only promises.

The reference plugin re-walks the rewritten physical plan and asserts
transition and distribution legality (`GpuTransitionOverrides`
`assertIsOnTheGpu` / `validateExecsInGpuPlan`, PAPER.md §L3).  This
engine rewrites plans far more aggressively — overrides, whole-stage
fusion, mesh regions, and runtime AQE re-planning all reparent live
exec nodes — so the verifier re-derives the invariants the downstream
machinery depends on:

* **schema/dtype agreement** — pass-through nodes (exchange, reader,
  coalesce, boundary, transition, limit, broadcast) expose exactly
  their child's fields; join key lists agree in arity and dtype.
* **partitioning legality at exchanges** — every bound partitioning
  key resolves inside the child schema; an adaptive reader still
  bottoms out on a ShuffleExchangeExec after all rewrites.
* **lineage stamps** — once ``_stamp_lineage`` has run, every exchange
  carries a conf fingerprint (stage recovery refuses to recompute
  without one, so a stripped stamp means lost-output recovery is dead).
* **donation exclusivity** — ``FusedStageExec.donate_ok`` implies its
  input subtree has a single consumer and no shared scan below
  (donating a shared batch deletes its buffers under the sibling).
* **AQE boundary legality** — a ``StageBoundaryExec`` sits only above
  a join whose build side reads an AQE-inserted exchange (or, after
  runtime re-planning, its broadcast-strategy rewrite).
* **mesh-region closure** — a region's members are exactly the
  absorbable elementwise set; a host transition captured inside the
  region would silently sync per shard inside one jitted program.

Each violation raises a structured :class:`PlanInvariantError` naming
the node path from the root and the pass after which the broken shape
was observed.

Two gates (docs/developer-guide.md):

* ``spark.rapids.sql.verify.plan`` (default ON): ONE full walk after
  the final rewrite pass plus one after runtime AQE re-planning — the
  walk is a single fused tree pass (no per-node string building, no
  per-call imports), so it stays on everywhere including the
  benchmark's path.
* ``spark.rapids.sql.verify.plan.everyPass`` (default off): verify
  after EVERY rewrite pass, so a violation names the pass that
  introduced it rather than the end of the pipeline.
  tests/test_plan_verify.py plans the TPC-H ladder with this on; the
  steady state does not pay the 9 extra walks.
"""
from __future__ import annotations

from spark_rapids_tpu import types as T
from spark_rapids_tpu.conf import bool_conf

__all__ = ["PLAN_VERIFY", "PLAN_VERIFY_EVERY_PASS", "PASS_ORDER",
           "PlanInvariantError", "verify_plan", "verify_governor_ledger"]

PLAN_VERIFY = bool_conf(
    "spark.rapids.sql.verify.plan", True,
    "Run the physical-plan invariant verifier over the final rewritten "
    "plan and after adaptive stage re-planning: parent/child schema and "
    "dtype agreement, partitioning legality at exchanges, lineage "
    "stamps on every exchange, donation exclusivity for fused stages, "
    "StageBoundaryExec placement, and mesh-region closure. A violation "
    "raises PlanInvariantError naming the node path and pass. One fused "
    "O(nodes) walk, so it stays on by default "
    "(docs/developer-guide.md).")

PLAN_VERIFY_EVERY_PASS = bool_conf(
    "spark.rapids.sql.verify.plan.everyPass", False,
    "Verify after EVERY plan rewrite pass (tag, coalesce, transitions, "
    "mesh alignment, shared scans, lineage stamping, cluster lowering, "
    "stage boundaries, fusion, mesh regions) instead of once at the "
    "end, so a violation "
    "names the pass that introduced it. tests/test_plan_verify.py "
    "runs with this on; requires spark.rapids.sql.verify.plan.")

#: rewrite passes in execution order; a check only arms once the pass
#: that establishes its invariant has run (e.g. lineage stamps exist
#: only from ``stamp_lineage`` on)
PASS_ORDER = ("tag", "coalesce", "transitions", "mesh_align",
              "shared_scans", "stamp_lineage", "cluster",
              "stage_boundaries", "fusion", "mesh_regions", "aqe_replan")

_PASS_IDX = {name: i for i, name in enumerate(PASS_ORDER)}


class PlanInvariantError(RuntimeError):
    """One broken plan invariant: which node, after which pass, why."""

    def __init__(self, node_path: str, pass_name: str, message: str):
        self.node_path = node_path
        self.pass_name = pass_name
        self.message = message
        super().__init__(
            f"plan invariant violated after pass '{pass_name}' at "
            f"{node_path}: {message}")


def _schema_sig(schema, _memo) -> list:
    out = []
    for f in schema.fields:
        sig = _memo.get(id(f))
        if sig is None:
            # the field object itself is kept in the memo value so its
            # id cannot be recycled while the memo lives
            sig = (f.name, repr(f.data_type), f)
            _memo[id(f)] = sig
        out.append(sig[:2])
    return out


def _bound_refs(expr, out: list) -> None:
    """Collect (index, dtype) of every BoundReference under ``expr``."""
    idx = getattr(expr, "index", None)
    if idx is not None and type(expr).__name__ == "BoundReference":
        out.append((idx, getattr(expr, "dtype", None)))
    for c in getattr(expr, "children", ()) or ():
        _bound_refs(c, out)


_CLS: dict = {}


def _classes() -> dict:
    """Exec-class table, imported once per process (the verifier runs
    on every prepare — per-call imports would dominate the walk)."""
    if not _CLS:
        from spark_rapids_tpu.exec.basic import GlobalLimitExec
        from spark_rapids_tpu.exec.exchange import (AdaptiveShuffleReaderExec,
                                                    BroadcastExchangeExec,
                                                    ShuffleExchangeExec)
        from spark_rapids_tpu.exec.fused import FusedStageExec, fusible
        from spark_rapids_tpu.exec.joins import JoinExec
        from spark_rapids_tpu.exec.sortexec import CoalesceBatchesExec
        from spark_rapids_tpu.exec.stage_boundary import StageBoundaryExec
        from spark_rapids_tpu.exec.transitions import BackendSwitchExec
        from spark_rapids_tpu.plan.adaptive import unwrap_exchange
        _CLS.update(
            ShuffleExchangeExec=ShuffleExchangeExec,
            AdaptiveShuffleReaderExec=AdaptiveShuffleReaderExec,
            BroadcastExchangeExec=BroadcastExchangeExec,
            StageBoundaryExec=StageBoundaryExec,
            BackendSwitchExec=BackendSwitchExec,
            FusedStageExec=FusedStageExec,
            JoinExec=JoinExec,
            fusible=fusible,
            unwrap_exchange=unwrap_exchange,
            passthrough=(ShuffleExchangeExec, AdaptiveShuffleReaderExec,
                         BroadcastExchangeExec, CoalesceBatchesExec,
                         StageBoundaryExec, BackendSwitchExec,
                         GlobalLimitExec))
    return _CLS


# node-kind codes for the learned dispatch table: one dict lookup per
# node replaces the isinstance chain on the hot walk
_K_NONE, _K_EXCHANGE, _K_READER, _K_JOIN, _K_BOUNDARY, _K_FUSED, \
    _K_REGION = range(7)

#: learned type -> (kind, is_passthrough); grows one entry per exec
#: class ever verified, so it is bounded by the class population
_DISPATCH: dict = {}

#: schema objects proven well-formed, keyed by id with the OBJECT kept
#: as the value so its id cannot be recycled while the memo lives;
#: plans re-prepared from the same logical plan share these objects,
#: so repeat walks skip the per-field validation.  Schemas are treated
#: as immutable engine-wide (a rewrite swaps the schema object, never
#: edits one in place), which is what makes the id-memo sound.  Capped:
#: clearing only costs one re-validation.
_OK_SCHEMAS: dict = {}
_MEMO_CAP = 16384

#: DataType subclasses proven via isinstance once — per-field dtype
#: validation is then one set lookup on the class
_DT_CLASSES: set = set()


def _classify(cls) -> tuple:
    c = _classes()
    if issubclass(cls, c["ShuffleExchangeExec"]):
        kind = _K_EXCHANGE
    elif issubclass(cls, c["AdaptiveShuffleReaderExec"]):
        kind = _K_READER
    elif issubclass(cls, c["JoinExec"]):
        kind = _K_JOIN
    elif issubclass(cls, c["StageBoundaryExec"]):
        kind = _K_BOUNDARY
    elif issubclass(cls, c["FusedStageExec"]):
        kind = _K_FUSED
    elif cls.__name__ == "MeshRegionExec":
        kind = _K_REGION
    else:
        kind = _K_NONE
    entry = (kind, issubclass(cls, c["passthrough"]))
    _DISPATCH[cls] = entry
    return entry


class _Verifier:
    def __init__(self, conf=None, pass_name: str = "mesh_regions"):
        self.c = _classes()
        self._parent_counts: dict[int, int] = {}
        # id(node) -> (parent_node, child_index | -1 for hidden); paths
        # are only rendered on failure, never on the hot path
        self._parents: dict[int, tuple] = {}
        self._sig_memo: dict[int, tuple] = {}
        self.reset(conf, pass_name)

    def reset(self, conf, pass_name: str) -> None:
        self.conf = conf
        self.pass_name = pass_name
        self._pass_idx = _PASS_IDX.get(pass_name, len(PASS_ORDER) - 1)

    def _after(self, pass_name: str) -> bool:
        return self._pass_idx >= _PASS_IDX[pass_name]

    def _path(self, node) -> str:
        """Render the root->node path.  Only ever runs on a failure, so
        the hot walk stores one parent pointer per node and the child
        index / hidden-edge marker is re-derived here."""
        parts = []
        seen = 0
        while node is not None and seen < 256:
            parent = self._parents.get(id(node))
            name = type(node).__name__
            if parent is None:
                parts.append(name)
            else:
                idx = None
                for i, ch in enumerate(parent.children):
                    if ch is node:
                        idx = i
                        break
                parts.append(f"{name}[hidden]" if idx is None
                             else f"{name}[{idx}]")
            node, seen = parent, seen + 1
        return "/".join(reversed(parts))

    def _fail(self, node, message: str):
        raise PlanInvariantError(self._path(node), self.pass_name, message)

    # -- the walk ------------------------------------------------------

    def run(self, root) -> None:
        counts = self._parent_counts
        parents = self._parents
        dispatch = _DISPATCH
        ok_schemas = _OK_SCHEMAS
        armed_boundary = self._pass_idx >= _PASS_IDX["stage_boundaries"]
        armed_fusion = self._pass_idx >= _PASS_IDX["fusion"]
        armed_region = self._pass_idx >= _PASS_IDX["mesh_regions"]
        donate_checks = []
        # the parents map doubles as the visited set (membership =
        # discovered), and schemas fetched while checking a parent's
        # pass-through edge are cached so the child's own visit does
        # not re-run its output_schema property
        parents[id(root)] = None
        schema_cache: dict = {}
        # (node, counting): edges out of hidden-side nodes (fused ops,
        # mesh-region members) MIRROR visible edges — e.g. a fused
        # op's child is also the wrapper's child — so only the visible
        # .children graph contributes to parent counts, exactly like
        # _fuse_stages' own exclusivity scan
        stack = [(root, True)]
        while stack:
            node, counting = stack.pop()
            entry = dispatch.get(node.__class__)
            if entry is None:
                entry = _classify(node.__class__)
            kind, passthrough = entry
            if schema_cache:
                schema = schema_cache.pop(id(node), None)
                if schema is None:
                    schema = node.output_schema
            else:
                schema = node.output_schema
            if ok_schemas.get(id(schema)) is not schema:
                self._validate_schema(node, schema)
            children = node.children
            if passthrough and children:
                child = children[0]
                child_schema = child.output_schema
                schema_cache[id(child)] = child_schema
                if schema is not child_schema:
                    self._check_passthrough(node, schema, child_schema)
            if kind:
                if kind == _K_EXCHANGE:
                    self._check_exchange(node)
                elif kind == _K_READER:
                    self._check_reader(node)
                elif kind == _K_JOIN:
                    self._check_join(node)
                elif kind == _K_BOUNDARY:
                    if armed_boundary:
                        self._check_boundary(node)
                elif kind == _K_FUSED:
                    if armed_fusion and getattr(node, "donate_ok", False):
                        donate_checks.append(node)
                elif armed_region:  # _K_REGION
                    self._check_region(node)
            for ch in children:
                cid = id(ch)
                if counting:
                    counts[cid] = counts.get(cid, 0) + 1
                if cid not in parents:
                    parents[cid] = node
                    stack.append((ch, counting))
            # fused ops and mesh-region members keep their ORIGINAL
            # child links but are not .children of the wrapper — walk
            # them too so a broken node hidden inside a fused body is
            # still caught
            if kind == _K_FUSED:
                hidden = node.fused_ops
            elif kind == _K_REGION:
                hidden = node._members + (node._terminal,)
            else:
                continue
            for ch in hidden:
                cid = id(ch)
                if cid not in parents:
                    parents[cid] = node
                    stack.append((ch, False))
        # donation exclusivity needs the COMPLETE parent counts, so it
        # is deferred until the walk has seen every edge
        for node in donate_checks:
            self._check_donation(node)

    # -- per-node checks -----------------------------------------------

    def _validate_schema(self, node, schema) -> None:
        if not isinstance(schema, T.Schema):
            self._fail(node, f"output_schema is {type(schema).__name__}, "
                             "not a Schema")
        dt_classes = _DT_CLASSES
        for f in schema.fields:
            dt = getattr(f, "data_type", None)
            if dt.__class__ in dt_classes:
                continue
            if not isinstance(dt, T.DataType):
                self._fail(node, f"field {f!r} carries no DataType")
            dt_classes.add(dt.__class__)
        if len(_OK_SCHEMAS) > _MEMO_CAP:
            _OK_SCHEMAS.clear()
        _OK_SCHEMAS[id(schema)] = schema

    def _check_passthrough(self, node, schema, child_schema) -> None:
        memo = self._sig_memo
        if _schema_sig(schema, memo) != _schema_sig(child_schema, memo):
            self._fail(
                node, "pass-through node schema diverges from its "
                f"child: {_schema_sig(schema, memo)} != "
                f"{_schema_sig(child_schema, memo)}")

    def _check_exchange(self, node) -> None:
        part = node.partitioning
        nparts = getattr(part, "num_partitions", 0)
        if not isinstance(nparts, int) or nparts < 1:
            self._fail(node, f"exchange partitioning has num_partitions="
                             f"{nparts!r}")
        bound = getattr(part, "_bound", ()) or ()
        if bound:
            arity = len(node.children[0].output_schema.fields)
            refs: list = []
            for key in bound:
                _bound_refs(key, refs)
            for idx, _dtype in refs:
                if not 0 <= idx < arity:
                    self._fail(
                        node, f"partitioning key references column {idx} "
                        f"outside the child schema (arity {arity})")
        if self._after("stamp_lineage"):
            fp = getattr(node, "_conf_fp", None)
            if not fp or not isinstance(fp, str):
                self._fail(
                    node, "exchange carries no lineage stamp (_conf_fp): "
                    "stage recovery cannot prove a recompute runs under "
                    "the conf the original map ran with")

    def _check_reader(self, node) -> None:
        if self.c["unwrap_exchange"](node) is None:
            self._fail(
                node, "AdaptiveShuffleReaderExec no longer bottoms out "
                f"on a ShuffleExchangeExec (child is "
                f"{type(node.children[0]).__name__})")

    def _check_join(self, node) -> None:
        lkeys = getattr(node, "_lkeys_b", None)
        rkeys = getattr(node, "_rkeys_b", None)
        if lkeys is None or rkeys is None:
            return
        if len(lkeys) != len(rkeys):
            self._fail(node, f"join key arity mismatch: {len(lkeys)} "
                             f"left vs {len(rkeys)} right")
        for i, (lk, rk) in enumerate(zip(lkeys, rkeys)):
            ld, rd = getattr(lk, "dtype", None), getattr(rk, "dtype", None)
            if ld is not None and rd is not None and \
                    type(ld) is not type(rd):
                self._fail(node, f"join key {i} dtype mismatch: "
                                 f"{ld!r} vs {rd!r}")

    def _check_boundary(self, node) -> None:
        child = node.children[0]
        if not isinstance(child, self.c["JoinExec"]) or \
                len(child.children) != 2:
            self._fail(
                node, "StageBoundaryExec must sit directly above a "
                f"two-child join, found {type(child).__name__}")
        build = child.children[1]
        if self.pass_name == "aqe_replan" and \
                isinstance(build, self.c["BroadcastExchangeExec"]):
            return  # broadcast-strategy rewrite: build side re-wrapped
        ex = self.c["unwrap_exchange"](build)
        if ex is None or not getattr(ex, "_aqe_inserted", False):
            self._fail(
                node, "StageBoundaryExec build side does not unwrap to "
                "an AQE-inserted exchange — the barrier would "
                "materialize a stage AQE never planned for re-decision")

    def _check_donation(self, node) -> None:
        bad = self._non_exclusive(node.children[0], set())
        if bad is not None:
            why = "is consumed by multiple parents" \
                if self._parent_counts.get(id(bad), 0) > 1 \
                else "shares a parked scan materialization"
            self._fail(
                node, f"donate_ok fused stage over a non-exclusive "
                f"input: {type(bad).__name__} below it {why}; donating "
                "its batches would delete buffers under the sibling "
                "consumer")

    def _non_exclusive(self, node, seen: set):
        """First node under ``node`` (inclusive) breaking donation
        exclusivity, or None.  Mirrors _fuse_stages' ``exclusive()``."""
        if id(node) in seen:
            return None
        seen.add(id(node))
        if self._parent_counts.get(id(node), 0) > 1 or \
                getattr(node, "share_output", False):
            return node
        for c in node.children:
            bad = self._non_exclusive(c, seen)
            if bad is not None:
                return bad
        return None

    def _check_region(self, node) -> None:
        terminal = node._terminal
        if type(terminal).__name__ not in ("MeshAggregateExec",
                                           "MeshExchangeExec",
                                           "MeshSortExec",
                                           "MeshWindowExec"):
            self._fail(node, f"mesh region terminal is "
                             f"{type(terminal).__name__}, not a mesh "
                             "collective")
        joins = []
        for m in node._members:
            if isinstance(m, self.c["BackendSwitchExec"]):
                self._fail(
                    node, "host transition (BackendSwitchExec) captured "
                    "inside a mesh region: the per-device program would "
                    "sync to host per shard inside one jitted body")
            mname = type(m).__name__
            if mname == "MeshJoinExec":
                joins.append(m)
            elif not (self.c["fusible"](m)
                      or isinstance(m, self.c["FusedStageExec"])
                      or mname == "MeshWindowExec"):
                self._fail(
                    node, f"mesh region member {type(m).__name__} is not "
                    "absorbable (fusible filter/project, FusedStageExec, "
                    "MeshJoinExec, or MeshWindowExec)")
            if isinstance(m, self.c["FusedStageExec"]) and \
                    getattr(m, "donate_ok", False):
                self._fail(
                    node, "fused member inside a mesh region still has "
                    "donate_ok: the slice-lost fallback replays the "
                    "member chain per batch, which a donated (deleted) "
                    "input cannot survive")
            if mname in ("MeshJoinExec", "MeshWindowExec") and \
                    (getattr(m, "mesh_size", None) != node.mesh_size
                     or getattr(m, "axis_name", None) != node.axis_name):
                self._fail(
                    node, f"collective member {mname} runs on mesh "
                    f"{getattr(m, 'mesh_size', None)}/"
                    f"{getattr(m, 'axis_name', None)!r} but the region "
                    f"program is compiled for {node.mesh_size}/"
                    f"{node.axis_name!r}")
        # region closure over the new edges: children must stay exactly
        # [pipeline leaf] + one build subtree per join member, matching
        # the members' OWN links — a rewrite that swapped either side
        # without the other would drain the wrong subtree
        if len(node.children) != 1 + len(joins):
            self._fail(
                node, f"mesh region carries {len(node.children)} children "
                f"for {len(joins)} join member(s); expected the pipeline "
                "leaf plus one build subtree per join")
        if node._members and node._members[0].children[0] \
                is not node.children[0]:
            self._fail(
                node, "mesh region leaf edge diverged: members[0] no "
                "longer consumes the region's child 0 — the program "
                "would shard a different subtree than lineage replays")
        for i, j in enumerate(joins):
            if j.children[1] is not node.children[1 + i]:
                self._fail(
                    node, f"mesh region build edge {i} diverged: the "
                    "absorbed join's build child is not the region's "
                    f"child {1 + i} — the stacked build input would not "
                    "match the join's lineage")
        # chained-region edge: an upstream mesh exchange (bare or a
        # region's exchange terminal) feeding this region must serve
        # the SAME mesh, or the committed shards cannot be consumed
        # in place
        leaf = node.children[0]
        lname = type(leaf).__name__
        up = leaf if lname == "MeshExchangeExec" else \
            (leaf._terminal if lname == "MeshRegionExec"
             and type(leaf._terminal).__name__ == "MeshExchangeExec"
             else None)
        if up is not None and \
                (up.mesh_size != node.mesh_size
                 or up.axis_name != node.axis_name):
            self._fail(
                node, f"chained region edge crosses meshes: upstream "
                f"exchange is mesh {up.mesh_size}/{up.axis_name!r}, "
                f"this region {node.mesh_size}/{node.axis_name!r} — "
                "per-device shards cannot stay committed across the "
                "chain")


def verify_plan(root, conf=None, pass_name: str = "mesh_regions") -> None:
    """Walk the exec tree under ``root`` and raise
    :class:`PlanInvariantError` on the first broken invariant.

    ``pass_name`` is the rewrite pass that just ran (see
    :data:`PASS_ORDER`): checks whose invariant a later pass establishes
    stay disarmed, and the name is carried on the error so a violation
    points at the pass that introduced it.  ``conf`` is optional and
    only consulted by conf-dependent checks."""
    v = _POOL.pop() if _POOL else _Verifier()
    v.reset(conf, pass_name)
    try:
        v.run(root)
    finally:
        # drop plan refs before pooling (error paths included: the
        # failure path string is rendered before the raise)
        v._parent_counts.clear()
        v._parents.clear()
        v._sig_memo.clear()
        if len(_POOL) < 4:
            _POOL.append(v)


def verify_governor_ledger(gov) -> None:
    """Runtime sibling of :func:`verify_plan` for the cross-query memory
    governor (memory/governor.py): check the invariants the arbitration
    logic only promises.  Called by the governor test suites, also
    after ``shutdown(drain=True)``; raises
    :class:`PlanInvariantError` (node path ``<governor>``, pass
    ``governor_ledger``) on the first violation:

    * no negative ledger entries — a double-release or mis-attributed
      free would drive ``device_bytes``/``pinned_bytes`` below zero;
    * ``pinned_bytes <= device_bytes`` per query — pinned is a subset
      of the live working set, never more than what is resident;
    * ``peak_bytes >= device_bytes`` — the high-water mark is monotone;
    * zero outstanding reservations once no grant wait is in flight —
      a leaked reservation permanently shrinks every peer's headroom.
    """
    if gov is None:
        return

    def _fail(msg: str):
        raise PlanInvariantError("<governor>", "governor_ledger", msg)

    stats = gov.query_stats()
    for qid, s in stats.items():
        if s["device_bytes"] < 0 or s["pinned_bytes"] < 0:
            _fail(f"query {qid}: negative ledger "
                  f"(device={s['device_bytes']} pinned={s['pinned_bytes']})")
        if s["pinned_bytes"] > s["device_bytes"]:
            _fail(f"query {qid}: pinned_bytes {s['pinned_bytes']} exceeds "
                  f"device_bytes {s['device_bytes']}")
        if s["peak_bytes"] < s["device_bytes"]:
            _fail(f"query {qid}: peak_bytes {s['peak_bytes']} below live "
                  f"device_bytes {s['device_bytes']}")
    reserved = gov.reserved_bytes()
    if reserved:
        _fail(f"leaked grant reservation: {reserved} bytes still "
              "reserved with no waiter in flight")


#: small reuse pool: one walk per prepare means the same dicts serve
#: every verification instead of reallocating four maps per call
_POOL: list = []
