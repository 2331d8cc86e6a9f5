"""Whole-stage fusion: run an adjacent filter/project pipeline as ONE
jitted program — one dispatch and one kernel launch per batch instead of
one per operator.

This is the engine's analog of whole-stage codegen, the reference
plugin's biggest small-query lever (PAPER.md §L3, GpuTransitionOverrides):
per-operator dispatch is most of a small query's wait (PERF.md §5,
``tpcds-sf1-chip1.q6``).  The
planner (plan/overrides.py ``_fuse_stages``) collapses runs of
elementwise operators into a ``FusedStageExec`` whose body chains the
member programs inside a single ``jax.jit`` region, letting XLA fuse the
predicate, the compaction, and the projections into one kernel schedule
and elide every intermediate batch materialization.

Fusion changes the EXEC tree only — member ops keep their original child
links, so schema / ordering / batching delegation walks the unfused
chain unchanged, and ``node_desc`` renders the replaced pipeline for
EXPLAIN ANALYZE.

Fused stages stay citizens of the existing planes:

- the body is dispatched under ``ExecCtx.dispatch_retry`` → cooperative
  cancellation is checked per batch and OOM split-and-retry replays the
  whole fused program on each half (every member is elementwise, so
  split pieces produce identical rows in order);
- the jitted program comes from ``exec/compile_cache.py`` → identical
  stages across plans, queries, and sessions share one compiled
  executable, and compile/hit counters feed EXPLAIN ANALYZE;
- with ``spark.rapids.sql.fusion.donateInputs`` (default on) the input
  batch's buffers are donated to the region (SNIPPETS.md [1]–[2]
  ``donate_argnums``) so XLA reuses them for outputs.  Injected OOM
  faults fire BEFORE the program runs, so chaos split-and-retry is
  unaffected; a REAL device OOM after donation cannot replay the
  consumed batch and surfaces an actionable error naming the conf.
"""
from __future__ import annotations

import warnings
from typing import Iterator, Sequence

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch, round_capacity
from spark_rapids_tpu.exec.basic import (FilterExec, ProjectExec,
                                          count_string_matches,
                                          matched_columns)
from spark_rapids_tpu.exec.core import ExecCtx, PlanNode
from spark_rapids_tpu.expr.core import eval_device, eval_host
from spark_rapids_tpu.host.batch import HostBatch
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.ops import host_kernels as hk
from spark_rapids_tpu.ops import kernels as dk

__all__ = ["FusedStageExec", "fusible", "stage_body", "stage_key_parts",
           "filters_merged", "has_filter"]

# donation is best-effort by design: a dtype-changing projection leaves
# some input buffers unreusable and jax warns per compile — expected here
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


def fusible(node: PlanNode) -> bool:
    """Exactly FilterExec, or ProjectExec without partition-aware
    expressions (those read (pid, offset) outside the jit region and are
    fusion barriers).  Subclasses are excluded: they may override
    ``partition_iter`` semantics the fused body would bypass."""
    if type(node) is FilterExec:
        return True
    return type(node) is ProjectExec and not node._paware


def _is_donated_reuse_error(e: BaseException) -> bool:
    msg = str(e).lower()
    return "donat" in msg or "deleted" in msg


def stage_body(ops):
    """The single traced body chaining ``ops`` (innermost-first) — ONE
    program whether jitted standalone per batch (FusedStageExec) or
    spliced into a mesh region's per-device shard_map program
    (exec/mesh_region.py), where the same filter/projection chain runs
    shard-resident with no extra dispatch."""
    def body(b):
        # rows are front-packed once, at the end: a filter ands its
        # condition into ``keep`` and later members evaluate on the
        # uncompacted rows (members are elementwise and total, so a row
        # an earlier filter dropped costs a wasted lane, never an error,
        # and ``keep`` masks it out whatever a later condition says)
        keep = None
        for op in ops:
            if type(op) is FilterExec:
                c = eval_device(op._cond, b)
                k = c.data & c.validity
                keep = k if keep is None else keep & k
            else:
                cols = [eval_device(e, b) for e in op._bound]
                b = ColumnBatch(cols, b.num_rows, op._schema)
        return b if keep is None else dk.compact(b, keep)
    return body


def has_filter(ops) -> bool:
    """Whether ``stage_body`` over ``ops`` ends in a compaction."""
    return any(type(op) is FilterExec for op in ops)


def filters_merged(ops) -> int:
    """Filters of a chain beyond its first: the compactions
    ``stage_body`` saves a launch (counter ``fused.filters_merged``)."""
    return max(sum(type(op) is FilterExec for op in ops) - 1, 0)


def stage_key_parts(ops) -> list:
    """Fragment-key material for a filter/project chain: what
    ``stage_body``'s trace closes over, per member."""
    parts = []
    for op in ops:
        if type(op) is FilterExec:
            parts.append(("filter", op._cond))
        else:
            parts.append(("project", tuple(op._bound), op._schema))
    return parts


class FusedStageExec(PlanNode):
    """N adjacent elementwise operators executed as one jitted program.

    ``ops`` is innermost-first (ops[0] consumes the stage input,
    ops[-1] produces the stage output); each op keeps its ORIGINAL child
    link so property delegation traverses the unfused chain."""

    combines_batches = False

    def __init__(self, ops: Sequence[PlanNode]):
        assert len(ops) >= 2 and all(fusible(op) for op in ops)
        super().__init__([ops[0].children[0]])
        self._ops = tuple(ops)
        self._merged = filters_merged(self._ops)
        self._compacts = has_filter(self._ops)
        self._matched = matched_columns(self._ops)
        # cleared by the fusion pass when the stage input is shared by
        # another consumer: donating a shared batch deletes the buffers
        # under the sibling (e.g. a CTE scanned once, consumed twice)
        self.donate_ok = True

    @property
    def output_schema(self) -> T.Schema:
        return self._ops[-1].output_schema

    @property
    def output_ordering(self):
        # every member preserves row order; ProjectExec's rename-aware
        # ordering walk still works because child links are intact
        return self._ops[-1].output_ordering

    @property
    def output_batching(self):
        return self._ops[-1].output_batching

    @property
    def bound_exprs(self):
        return [e for op in self._ops for e in op.bound_exprs]

    @property
    def fused_ops(self) -> tuple:
        return self._ops

    def _stage_key(self, donate: bool) -> str:
        from spark_rapids_tpu.exec import compile_cache as cc
        return cc.fragment_key("fused_stage", stage_key_parts(self._ops),
                               self.children[0].output_schema, donate)

    def _jit_fn(self, donate: bool):
        if not hasattr(self, "_fused_jits"):
            self._fused_jits = {}
        if donate not in self._fused_jits:
            from spark_rapids_tpu.exec import compile_cache as cc
            kw = {"donate_argnums": 0} if donate else {}
            self._fused_jits[donate] = cc.shared_jit(
                self._stage_key(donate), stage_body(self._ops),
                name="string_match_stage" if self._matched[0]
                else "fused_stage_body", **kw)
        return self._fused_jits[donate]

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        child_it = self.children[0].partition_iter(ctx, pid)
        if pid == 0:
            ctx.metrics_for(self).add("fusedOperators", len(self._ops))
        if not ctx.is_device:
            # host fallback mirrors the members' host paths sequentially
            # (the bench verifier runs the SAME plan on both backends)
            for b in child_it:
                for op in self._ops:
                    if type(op) is FilterExec:
                        c = eval_host(op._cond, b)
                        keep = c.data.astype(np.bool_) & c.validity
                        b = hk.host_filter(b, keep)
                    else:
                        cols = [eval_host(e, b) for e in op._bound]
                        b = HostBatch(cols, op._schema)
                yield b
            return
        from spark_rapids_tpu.exec.compile_cache import FUSION_DONATE
        donate = FUSION_DONATE.get(ctx.conf.settings) and self.donate_ok
        fn = self._jit_fn(donate)
        for b in child_it:
            # canonical pow2 entry capacity: shape polymorphism must not
            # fragment the shared executable cache
            cap = round_capacity(b.capacity)
            if cap != b.capacity:
                b = ctx.dispatch(dk.pad_capacity, b, cap)
            if self._merged:
                get_registry().inc("fused.filters_merged", self._merged)
            if self._compacts:
                dk.count_compaction(cap)
            count_string_matches(b, *self._matched)
            try:
                yield from ctx.dispatch_retry(fn, b, op="fused_stage")
            except Exception as e:
                if donate and _is_donated_reuse_error(e):
                    raise RuntimeError(
                        "OOM retry inside a fused stage needed an input "
                        "batch whose buffers were already donated to the "
                        "fused jit region; set "
                        "spark.rapids.sql.fusion.donateInputs=false to "
                        "trade buffer reuse for full split-and-retry "
                        "coverage") from e
                raise

    def node_desc(self) -> str:
        inner = " -> ".join(op.node_desc() for op in self._ops)
        return f"FusedStageExec[{len(self._ops)} ops: {inner}]"
