"""Expand exec: N projections per input batch (rollup / cube / grouping
sets).

Reference: GpuExpandExec (GpuExpandExec.scala:67) — evaluates a list of
projection lists against every input batch, emitting each input row once
per projection (Spark uses this to implement ROLLUP/CUBE/GROUPING SETS,
with nulled-out grouping columns plus a ``spark_grouping_id`` literal per
projection).  TPU design: one jitted program per projection, each
emitted as its own output batch (same capacity, static shapes) so
downstream aggregation keeps canonical capacities and peak device memory
stays at one projection's worth regardless of grouping-set count.
"""
from __future__ import annotations

from typing import Iterator, Sequence

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.exec.core import ExecCtx, PlanNode
from spark_rapids_tpu.expr.core import (Expression, bind, eval_device,
                                        eval_host, output_name)
from spark_rapids_tpu.host.batch import HostBatch

__all__ = ["ExpandExec"]


class ExpandExec(PlanNode):
    """Evaluate ``projections`` (a list of same-arity expression lists)
    per input batch; output = one batch per (input batch, projection)."""

    def __init__(self, projections: Sequence[Sequence[Expression]],
                 child: PlanNode):
        super().__init__([child])
        assert projections, "expand with no projections"
        arity = len(projections[0])
        assert all(len(p) == arity for p in projections), \
            "expand projections must have equal arity"
        cs = child.output_schema
        self._bound = [[bind(e, cs) for e in proj] for proj in projections]
        names = [output_name(e) for e in projections[0]]
        fields = []
        for i, name in enumerate(names):
            dts = {type(p[i].dtype) for p in self._bound}
            assert len(dts) == 1, \
                f"expand column {name} has mixed types across projections"
            fields.append(T.StructField(name, self._bound[0][i].dtype, True))
        self._schema = T.Schema(fields)

    @property
    def output_schema(self) -> T.Schema:
        return self._schema

    @property
    def bound_exprs(self):
        return [e for proj in self._bound for e in proj]

    def _jit_fns(self):
        # one program PER projection, emitted one at a time (reference
        # GpuExpandExec emits per projection) so peak device memory is one
        # output batch, not len(projections) of them — a 4-key cube has 16
        if not hasattr(self, "_expand_jits"):
            from spark_rapids_tpu.exec import compile_cache as cc

            def make(proj):
                def one(b):
                    cols = [eval_device(e, b) for e in proj]
                    return ColumnBatch(cols, b.num_rows, self._schema)
                return cc.shared_jit(
                    cc.fragment_key("expand", tuple(proj), self._schema,
                                    self.children[0].output_schema),
                    one, name="expand_project")

            self._expand_jits = [make(p) for p in self._bound]
        return self._expand_jits

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        child_it = self.children[0].partition_iter(ctx, pid)
        if ctx.is_device:
            fns = self._jit_fns()
            for b in child_it:
                for fn in fns:
                    yield ctx.dispatch(fn, b)
        else:
            for b in child_it:
                for proj in self._bound:
                    cols = [eval_host(e, b) for e in proj]
                    yield HostBatch(cols, self._schema)

    def node_desc(self) -> str:
        return (f"ExpandExec[{len(self._bound)} projections, "
                f"{self._schema.names}]")
