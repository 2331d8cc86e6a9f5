"""Generate exec: explode / posexplode of split-string arrays.

Reference: GpuGenerateExec (GpuGenerateExec.scala:101) — per input row a
generator emits 0..n output rows; the child columns are repeated per
generated row, optionally with a position column, and ``outer`` keeps
rows whose generator yields nothing (null-extended).

Two generators: ``Explode`` over real ArrayType columns (padded element
matrix + lengths, columnar/column.py) and the fused ``SplitExplode`` =
explode(split(string, delimiter)) in one device program.  TPU design:
per-row counts (array lengths / delimiter cumulative-sums over the
padded byte matrix), output row -> (source row, element index) via the
same offsets/searchsorted plan as the join gather — all static shapes,
one host sync for the output total.
"""
from __future__ import annotations

from functools import partial
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch, round_capacity
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.exec.core import ExecCtx, PlanNode, fetch_to_host
from spark_rapids_tpu.exec.compile_cache import guarded_jit
from spark_rapids_tpu.expr.core import Expression, bind, eval_device, \
    eval_host
from spark_rapids_tpu.host.batch import HostBatch, HostColumn

__all__ = ["GenerateExec", "SplitExplode"]

#: span of this operator's blocking total fetches (exec/core.fetch_to_host)
_FETCH = "fetch@GenerateExec"


class SplitExplode(Expression):
    """Generator: explode(split(child, delimiter)) (single-byte delim)."""

    sql_name = "SplitExplode"

    def __init__(self, child: Expression, delimiter: str):
        assert len(delimiter.encode("utf-8")) == 1, \
            "SplitExplode supports single-byte delimiters"
        self.children = [child]
        self.delimiter = delimiter

    @property
    def dtype(self):
        return T.StringType()

    @property
    def nullable(self):
        return True

    def with_new_children(self, children):
        return SplitExplode(children[0], self.delimiter)

    def __repr__(self):
        return f"SplitExplode({self.children[0]!r}, {self.delimiter!r})"


class Explode(Expression):
    """Generator: explode(array_col) (reference GpuGenerateExec explode
    over LIST columns)."""

    sql_name = "Explode"

    def __init__(self, child: Expression):
        self.children = [child]

    @property
    def dtype(self):
        at = self.children[0].dtype
        assert isinstance(at, T.ArrayType), at
        return at.element_type

    @property
    def nullable(self):
        return True

    def with_new_children(self, children):
        return Explode(children[0])

    def __repr__(self):
        return f"Explode({self.children[0]!r})"


@guarded_jit("generate_array",
             static_argnames=("out_cap", "pos_col", "outer"))
def _jit_generate_array(batch: ColumnBatch, col: DeviceColumn,
                        out_cap: int, pos_col: bool, outer: bool):
    """Explode an array column: one output row per element, child
    columns gathered per output row + [pos] + element column."""
    cap = batch.capacity
    w = col.max_len
    real = batch.row_mask()
    counts = jnp.where(col.validity & real, col.lengths, 0)
    emit = jnp.maximum(counts, 1) if outer else counts
    emit = jnp.where(real, emit, 0)
    offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(emit)[:-1].astype(jnp.int32)])
    total = jnp.sum(emit, dtype=jnp.int32)

    j = jnp.arange(out_cap, dtype=jnp.int32)
    in_range = j < total
    src = (jnp.searchsorted(offsets, j, side="right") - 1).astype(jnp.int32)
    src = jnp.clip(src, 0, cap - 1)
    k = j - offsets[src]
    has_elem = in_range & (k < counts[src])

    kc = jnp.clip(k, 0, w - 1)
    # fused single-element gather: col.data[src, kc] avoids
    # materializing the [out_cap, w] row-gather intermediate
    elem = col.data[src, kc]
    elem = jnp.where(has_elem, elem, jnp.zeros((), col.data.dtype))
    elem_col = DeviceColumn(elem, has_elem, col.dtype.element_type)

    out_cols = []
    for c in batch.columns:
        v = c.validity[src] & in_range
        if c.is_var_width:
            out_cols.append(DeviceColumn(
                jnp.where(v[:, None], c.data[src], 0), v, c.dtype,
                jnp.where(v, c.lengths[src], 0)))
        else:
            out_cols.append(DeviceColumn(
                jnp.where(v, c.data[src], jnp.zeros((), c.data.dtype)),
                v, c.dtype))
    if pos_col:
        out_cols.append(DeviceColumn(
            jnp.where(has_elem, k.astype(jnp.int32), 0), has_elem,
            T.IntegerType()))
    out_cols.append(elem_col)
    return out_cols, total


@guarded_jit("generate_counts", static_argnames=())
def _jit_counts(col: DeviceColumn, real: jax.Array, delim: int):
    """Per-row piece counts (0 for null/padding rows) + total."""
    w = col.max_len
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    is_d = (col.data == jnp.uint8(delim)) & (pos < col.lengths[:, None])
    counts = jnp.where(col.validity & real,
                       jnp.sum(is_d, axis=1, dtype=jnp.int32) + 1, 0)
    return counts, jnp.sum(counts, dtype=jnp.int64)


@guarded_jit("generate_split",
             static_argnames=("out_cap", "pos_col", "outer"))
def _jit_generate(batch: ColumnBatch, col: DeviceColumn, counts, delim: int,
                  out_cap: int, pos_col: bool, outer: bool):
    """Build the generated batch: child columns gathered per output row +
    [pos] + piece string column."""
    cap = batch.capacity
    w = col.max_len
    real = batch.row_mask()
    emit = jnp.maximum(counts, 1) if outer else counts
    emit = jnp.where(real, emit, 0)
    offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(emit)[:-1].astype(jnp.int32)])
    total = jnp.sum(emit, dtype=jnp.int32)

    j = jnp.arange(out_cap, dtype=jnp.int32)
    in_range = j < total
    src = (jnp.searchsorted(offsets, j, side="right") - 1).astype(jnp.int32)
    src = jnp.clip(src, 0, cap - 1)
    k = j - offsets[src]                       # piece index within the row
    has_piece = in_range & (k < counts[src])   # outer null-extension rows

    # delimiter cumulative counts per source row
    posw = jnp.arange(w, dtype=jnp.int32)[None, :]
    is_d = (col.data == jnp.uint8(delim)) & (posw < col.lengths[:, None])
    cum = jnp.cumsum(is_d, axis=1)             # [cap, w]
    src_cum = cum[src]                         # [out_cap, w]
    # k-th delimiter position = first index with cum == k
    start = jnp.where(k > 0,
                      _first_ge(src_cum, k) + 1, 0)
    end = _first_ge(src_cum, k + 1)
    end = jnp.minimum(end, col.lengths[src])
    start = jnp.minimum(start, end)
    plen = (end - start).astype(jnp.int32)

    take = start[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    take = jnp.clip(take, 0, w - 1)
    bytes_out = jnp.take_along_axis(col.data[src], take, axis=1)
    mask = jnp.arange(w, dtype=jnp.int32)[None, :] < plen[:, None]
    validity = has_piece
    bytes_out = jnp.where(mask & validity[:, None], bytes_out, 0)
    piece = DeviceColumn(bytes_out, validity, T.StringType(),
                         jnp.where(validity, plen, 0))

    out_cols = []
    for c in batch.columns:
        v = c.validity[src] & in_range
        if c.is_string:
            out_cols.append(DeviceColumn(
                jnp.where(v[:, None], c.data[src], 0), v, c.dtype,
                jnp.where(v, c.lengths[src], 0)))
        else:
            out_cols.append(DeviceColumn(
                jnp.where(v, c.data[src], jnp.zeros((), c.data.dtype)),
                v, c.dtype))
    if pos_col:
        pv = in_range & has_piece
        out_cols.append(DeviceColumn(
            jnp.where(pv, k.astype(jnp.int32), 0), pv, T.IntegerType()))
    out_cols.append(piece)
    return out_cols, total


def _first_ge(cum: jax.Array, k) -> jax.Array:
    """Per output row: first column index where cum >= k (w if none)."""
    w = cum.shape[1]
    kk = k[:, None] if jnp.ndim(k) == 1 else k
    hit = cum >= kk
    idx = jnp.where(hit, jnp.arange(w, dtype=jnp.int32)[None, :], w)
    return jnp.min(idx, axis=1).astype(jnp.int32)


class GenerateExec(PlanNode):
    """explode/posexplode of a SplitExplode generator, child columns
    repeated per generated row (reference GpuGenerateExec.scala:101)."""

    def __init__(self, generator: Expression, child: PlanNode,
                 outer: bool = False, pos: bool = False,
                 output_names=("col",)):
        super().__init__([child])
        assert isinstance(generator, (SplitExplode, Explode)), \
            "only SplitExplode/Explode generators are supported"
        self.generator = generator
        self.outer = outer
        self.pos = pos
        self._gen_bound = bind(generator.children[0], child.output_schema)
        if isinstance(generator, SplitExplode):
            assert isinstance(self._gen_bound.dtype, T.StringType), \
                "SplitExplode input must be a string"
            out_dtype = T.StringType()
        else:
            assert isinstance(self._gen_bound.dtype, T.ArrayType), \
                "Explode input must be an array"
            out_dtype = self._gen_bound.dtype.element_type
        names = list(output_names)
        fields = list(child.output_schema.fields)
        if pos:
            fields.append(T.StructField(
                names[0] if len(names) > 1 else "pos", T.IntegerType(), True))
        fields.append(T.StructField(names[-1], out_dtype, True))
        self._schema = T.Schema(fields)

    @property
    def output_schema(self) -> T.Schema:
        return self._schema

    @property
    def bound_exprs(self):
        return [self._gen_bound]

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        child_it = self.children[0].partition_iter(ctx, pid)
        if not ctx.is_device:
            for b in child_it:
                yield self._host_generate(b)
            return
        if isinstance(self.generator, Explode):
            for b in child_it:
                gcol = self._eval_jit()(b)
                real = b.row_mask()
                counts = jnp.where(gcol.validity & real, gcol.lengths, 0)
                if self.outer:
                    counts = jnp.where(real, jnp.maximum(counts, 1), 0)
                # enginelint: disable=RL003 (total gates output allocation; single scalar sync per batch)
                total = int(fetch_to_host(
                    jnp.sum(counts, dtype=jnp.int64), _FETCH))
                if total == 0:
                    continue
                out_cap = round_capacity(total)
                cols, tot = ctx.dispatch(
                    _jit_generate_array, b, gcol, out_cap, self.pos,
                    self.outer)
                yield ColumnBatch(cols, tot, self._schema)
            return
        delim = self.generator.delimiter.encode("utf-8")[0]
        for b in child_it:
            gcol = self._eval_jit()(b)
            real = b.row_mask()
            counts, total_d = _jit_counts(gcol, real, delim)
            if self.outer:
                # enginelint: disable=RL003 (outer rows need a host total to size the output; single scalar sync)
                total = int(fetch_to_host(
                    jnp.sum(jnp.where(real, jnp.maximum(counts, 1), 0),
                            dtype=jnp.int64), _FETCH))
            else:
                # enginelint: disable=RL003 (total gates output allocation; single scalar sync per batch)
                total = int(fetch_to_host(total_d, _FETCH))
            if total == 0:
                continue
            out_cap = round_capacity(total)
            cols, tot = ctx.dispatch(
                _jit_generate, b, gcol, counts, delim, out_cap,
                self.pos, self.outer)
            yield ColumnBatch(cols, tot, self._schema)

    def _eval_jit(self):
        if not hasattr(self, "_gen_jit"):
            from spark_rapids_tpu.exec import compile_cache as cc
            self._gen_jit = cc.shared_jit(
                cc.fragment_key("generate", self._gen_bound),
                lambda b: eval_device(self._gen_bound, b),
                name="generate_eval")
        return self._gen_jit

    def _host_generate(self, b: HostBatch) -> HostBatch:
        gv = eval_host(self._gen_bound, b)
        is_array = isinstance(self.generator, Explode)
        src_idx, poss, pieces = [], [], []
        for i in range(b.num_rows):
            if not gv.validity[i]:
                if self.outer:
                    src_idx.append(i)
                    poss.append(None)
                    pieces.append(None)
                continue
            if is_array:
                parts = list(gv.data[i])
                if not parts and self.outer:
                    src_idx.append(i)
                    poss.append(None)
                    pieces.append(None)
                    continue
            else:
                parts = str(gv.data[i]).split(self.generator.delimiter)
            for k, p in enumerate(parts):
                src_idx.append(i)
                poss.append(k)
                pieces.append(p)
        cols = []
        idx = np.asarray(src_idx, dtype=np.int64)
        for c in b.columns:
            cols.append(HostColumn(c.data[idx] if len(idx) else
                                   c.data[:0], c.validity[idx] if len(idx)
                                   else c.validity[:0], c.dtype))
        if self.pos:
            pv = np.asarray([p is not None for p in poss], np.bool_)
            pd = np.asarray([0 if p is None else p for p in poss], np.int32)
            cols.append(HostColumn(pd, pv, T.IntegerType()))
        sv = np.asarray([p is not None for p in pieces], np.bool_)
        out_dtype = self._schema.fields[-1].data_type
        if isinstance(out_dtype, T.StringType):
            sd = np.empty(len(pieces), dtype=object)
            for i, p in enumerate(pieces):
                sd[i] = p
        else:
            sd = np.zeros(len(pieces), dtype=out_dtype.np_dtype)
            for i, p in enumerate(pieces):
                if p is not None:
                    sd[i] = p
        cols.append(HostColumn(sd, sv, out_dtype))
        return HostBatch(cols, self._schema)

    def node_desc(self) -> str:
        kind = "posexplode" if self.pos else "explode"
        return f"GenerateExec[{kind}{'_outer' if self.outer else ''}]"
