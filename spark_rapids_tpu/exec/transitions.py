"""Backend transition exec (reference GpuTransitionOverrides inserts
GpuRowToColumnarExec / GpuColumnarToRowExec / HostColumnarToGpu,
GpuTransitionOverrides.scala:249-266).

In this engine both backends are columnar, so a transition is a
host<->device batch conversion around a subtree executing on the other
backend.
"""
from __future__ import annotations

from dataclasses import replace
from typing import Iterator

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exec.core import (ExecCtx, PlanNode, device_to_host,
                                        host_to_device)

__all__ = ["BackendSwitchExec"]


class BackendSwitchExec(PlanNode):
    """Run the child subtree on ``inner_backend``; convert its output
    batches to the enclosing context's backend."""

    combines_batches = False

    def __init__(self, child: PlanNode, inner_backend: str):
        super().__init__([child])
        assert inner_backend in ("device", "host")
        self.inner_backend = inner_backend

    @property
    def output_schema(self) -> T.Schema:
        return self.children[0].output_schema

    def num_partitions(self, ctx: ExecCtx) -> int:
        return self.children[0].num_partitions(self._inner(ctx))

    def _inner(self, ctx: ExecCtx) -> ExecCtx:
        if ctx.backend == self.inner_backend:
            return ctx
        return replace(ctx, backend=self.inner_backend)

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        inner = self._inner(ctx)
        for b in self.children[0].partition_iter(inner, pid):
            if inner.backend == ctx.backend:
                yield b
            elif ctx.backend == "host":
                yield device_to_host(b, "fetch@BackendSwitchExec")
            else:
                yield host_to_device(b)

    def node_desc(self) -> str:
        return f"BackendSwitchExec[->{self.inner_backend}]"
