"""Compile the main path's programs for a DESCRIBED v5e (no chip attached).

The TPU compiler is installed alongside JAX and compiles for a topology
that is described, not present — so what the chip's compiler would
refuse (64-bit bitcasts, unsupported sorts, programs that do not fit)
is refused here, at no chip time.  Nothing runs: a passing compile says
nothing about results or times.

This is the only file that describes the chip.  The topology is touched
only inside the module-scoped fixture below (never at import, in a
``skipif``/``parametrize`` argument or in conftest): only one process
may load the TPU library, and every xdist worker imports every file.
Capacity 2^14 keeps each compile to seconds; the 2^20 programs of a real
q6 are sized by hand before a chip call (PERF.md).
"""
import re

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import batch as B
from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.obs.registry import get_registry

CAP = 1 << 14


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile can be written to the persistent cache
    # but never read back without a chip: keep it out of the way
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    compiled = jitted.lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled


def _capacity_row(hlo: str, op: str) -> list[str]:
    """Names of the compiled module's ``op`` instructions that take or
    give an array of ``CAP`` rows."""
    types = dict(re.findall(r"(%[^\s=]+) = (.+?) [\w-]+\(", hlo))
    big = re.compile(rf"\[{CAP}[\],]")
    return [name for name, rtype, args in re.findall(
        rf"(%[^\s=]+) = (.+?) {op}\(([^)]*)\)", hlo)
        if big.search(rtype) or any(
            big.search(types.get(a.strip(), "")) for a in args.split(","))]


def _sales_batch(n: int = 900, cap: int = CAP) -> ColumnBatch:
    import __graft_entry__ as g
    return g._make_batch(n, cap)


def test_query_step_compiles_for_v5e(one_chip):
    """filter -> compact -> sorted_group_by: the q6-shaped step."""
    import __graft_entry__ as g
    _compile(g.query_step, _shapes(_sales_batch(), one_chip))


def test_unpack_decode_compiles_for_v5e(one_chip, monkeypatch):
    """The packed H2D unpack + wire-codec decode program of a scanned
    batch (nullable int32 keys, an s64, a dictionary string, a float64
    of whole cents and one of whole numbers: both rebuilt into the
    host's doubles from integers, ``wirecodec.rebuild_double``, by
    shifts, float32 products and three widenings — no gather, no
    64-bit division, nothing the chip's compiler refuses)."""
    import pyarrow as pa
    rng = np.random.default_rng(7)
    n = CAP - 100
    nulls = rng.random(n) < 0.05
    rb = pa.record_batch({
        "ss_sold_date_sk": pa.array(
            rng.integers(2450000, 2453000, n).astype(np.int32), mask=nulls),
        "ss_item_sk": pa.array(rng.integers(1, 18000, n).astype(np.int32)),
        "ss_ticket_number": pa.array(
            rng.integers(1, 1 << 40, n).astype(np.int64)),
        "ss_sales_price": pa.array(np.round(rng.uniform(0, 200, n), 2)),
        "ss_quantity": pa.array(
            rng.integers(1, 101, n).astype(np.float64)),
        "ca_state": pa.array(rng.choice(["CA", "TX", "NY", "WA"], n)),
    })
    seen = {}
    real = B._packed_unpack_cached

    def spy(spec):
        program = real(spec)

        def call(bufs):
            seen["program"], seen["bufs"] = program, bufs
            return program(bufs)
        return call
    monkeypatch.setattr(B, "_packed_unpack_cached", spy)
    before = get_registry().counters()
    ColumnBatch.from_arrow(rb, capacity=CAP, codec=True)
    moved = {k: v for k, v in get_registry().counters_since(before).items()
             if k.startswith("wire.double.")}
    assert moved == {"wire.double.scaled": 2,
                     "wire.double.bytes": CAP * (16 + 8) // 8}
    hlo = _compile(seen["program"].fn,
                   _shapes(seen["bufs"], one_chip)).as_text()
    assert " gather(" not in hlo and " divide(" not in hlo


def _keyed_batch(n: int, cap: int, seed: int) -> ColumnBatch:
    from spark_rapids_tpu.host.batch import HostBatch
    rng = np.random.default_rng(seed)
    schema = T.Schema([
        T.StructField("k", T.LongType(), True),
        T.StructField("state", T.StringType(), True),
        T.StructField("v", T.DoubleType(), True)])
    return HostBatch.from_pydict({
        "k": rng.integers(1, 5000, n).astype(np.int64),
        "state": [("CA", "TX", "NY", "WA")[i % 4] for i in range(n)],
        "v": rng.uniform(0, 100, n),
    }, schema).to_device(capacity=cap)


def test_join_build_and_probe_compiles_for_v5e(one_chip):
    """Sort-based build + merge probe + gather on s64 keys."""
    from spark_rapids_tpu.ops.join import (build_prepare_fast,
                                           gather_join_output,
                                           join_indices_from_probe,
                                           probe_fast)
    lb, rb = _keyed_batch(900, CAP, 1), _keyed_batch(300, CAP >> 2, 2)
    schema = T.Schema(list(lb.schema) + list(rb.schema))

    def join_step(left, right):
        prep = build_prepare_fast(right, 0)
        probe, total = probe_fast(left, 0, *prep, "inner")
        plan = join_indices_from_probe(left.capacity, probe, "inner", CAP)
        return gather_join_output(left, right, *plan, schema, True), total
    _compile(join_step, _shapes(lb, one_chip), _shapes(rb, one_chip))


@pytest.mark.parametrize("out_cap", [CAP, CAP >> 1])
def test_aligned_join_gather_compiles_for_v5e(one_chip, out_cap):
    """``join_gather``'s two plans (exec/joins._jit_gather's body) over a
    stream batch with an s64, an f64 and a string and such a build: the
    aligned plan holds no scatter, no running maximum's scan over the
    slots and only the build's gathers -- ``perm[start]`` and one a dtype
    of the build's leaves -- where the expanding plan holds the stream's
    as well."""
    from spark_rapids_tpu.ops.join import (build_prepare_fast,
                                           gather_join_output,
                                           join_indices_from_probe,
                                           probe_fast)
    lb, rb = _keyed_batch(900, CAP, 1), _keyed_batch(300, CAP >> 2, 2)
    schema = T.Schema(list(lb.schema) + list(rb.schema))
    prep = build_prepare_fast(rb, 0)
    probe, _total = probe_fast(lb, 0, *prep, "left")

    def gathers(aligned):
        def gather(left, right, probe):
            plan = join_indices_from_probe(
                left.capacity, probe, "left", out_cap, aligned=aligned)
            return gather_join_output(left, right, *plan, schema, True)
        hlo = _compile(gather, *(_shapes(x, one_chip)
                                 for x in (lb, rb, probe))).as_text()
        big = re.compile(rf"\[{out_cap}[\],]")
        found = re.findall(r"= (.+?) (gather|scatter)\(", hlo)
        return [op for rtype, op in found if big.search(rtype)]
    expanding, aligned = gathers(False), gathers(True)
    assert "scatter" in expanding and "scatter" not in aligned
    # perm[start] and the build's stacks: flags, string lengths, bytes,
    # and two 32-bit halves each for the s64 and the f64 (64-bit words
    # are emulated); the expanding plan moves the stream's seven as well,
    # behind a gather of per-row numbers
    assert 1 <= aligned.count("gather") <= 8
    assert expanding.count("gather") >= aligned.count("gather") + 7


@pytest.mark.parametrize("table", [32, 1 << 19])
def test_direct_probe_compiles_for_v5e(one_chip, table):
    """The probe by address at q6's sizes: a 2^20-row stream batch against
    a 32-entry table (one month of ``date_dim`` in its 2^17 slots) and a
    2^19-entry one (``customer`` at SF10), and the table's own build from
    the sorted keys.  s64 keys: the key offset is taken in 64 bits.  The
    build's sort and the gather behind the probe are the case above's."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.join import build_direct_table, probe_direct
    build_cap = max(table, 1 << 17)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    prep = (on_chip((build_cap,), jnp.int64), on_chip((build_cap,), jnp.int32),
            on_chip((), jnp.int32))
    _compile(lambda k, p, n: build_direct_table(k, p, n, table), *prep)
    build = jax.tree.map(
        lambda a: on_chip(a.shape, a.dtype),
        jax.eval_shape(lambda k, p, n: build_direct_table(k, p, n, table),
                       *prep))
    assert build.table.shape == (table, 2)
    lb = _keyed_batch(900, 1 << 20, 1)
    for jt in ("inner", "left"):
        _compile(lambda left, b: probe_direct(left, 0, b, jt)[0][:-1],
                 _shapes(lb, one_chip), build)


def test_packed_two_key_build_and_search_probe_compile_for_v5e(one_chip):
    """TPC-DS q93's join at SF10: ``store_returns`` (2.88M rows in 2^22
    slots) prepared from (item int32, ticket number int64) packed into
    one int64 key (ranges, packing and the sort by it in one program,
    with the stats of its one fetch), and the ``left`` search probe of a
    2^20-row ``store_sales`` batch that packs its own two keys first:
    at these shapes the probe's merge (two sorts of 5.2M rows with a
    64-bit key, no stepping loop)."""
    from spark_rapids_tpu.host.batch import HostBatch
    from spark_rapids_tpu.ops.join import (build_key_stats,
                                           build_prepare_packed, probe_fast)
    it, lt = T.IntegerType(), T.LongType()

    def batch(prefix, extra, cap):
        schema = T.Schema(
            [T.StructField(prefix + "item_sk", it, True),
             T.StructField(prefix + "ticket_number", lt, True)]
            + [T.StructField(prefix + n, t, True) for n, t in extra])
        return HostBatch.from_pydict(
            {f.name: np.arange(9).astype(f.data_type.np_dtype)
             for f in schema}, schema).to_device(capacity=cap)
    sales = batch("ss_", [("customer_sk", it), ("quantity", it),
                          ("sales_price", T.DoubleType())], 1 << 20)
    returns = batch("sr_", [("reason_sk", it), ("return_quantity", it)],
                    1 << 22)

    def build(right):
        prep, packing = build_prepare_packed(right, (0, 1))
        return prep, packing, build_key_stats(prep[0], prep[2], packing)
    _compile(build, _shapes(returns, one_chip))
    prep, packing, stats = jax.eval_shape(build, returns)
    assert prep[0].shape == (1 << 22,) and str(prep[0].dtype) == "int64"
    assert stats.shape == (5,)
    probe = jax.jit(lambda left, p, k: probe_fast(left, (0, 1), *p, "left",
                                                  k)[0][:-1])
    args = (_shapes(sales, one_chip), _shapes(prep, one_chip),
            _shapes(packing, one_chip))
    text = probe.lower(*args).as_text()
    assert "stablehlo.sort" in text and "stablehlo.while" not in text
    _compile(probe, *args)


def test_search_probe_of_a_small_batch_steps_and_compiles_for_v5e(one_chip):
    """A 2^10-row remnant against the same 2^22-entry int64 build keeps
    ``probe_fast``'s stepping branch (one ``searchsorted``: a loop of
    gathers, no sort of 4M rows): both branches stay compilable."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.join import probe_fast, probe_merges
    cl, cr = 1 << 10, 1 << 22
    assert not probe_merges(cl, cr) and probe_merges(1 << 20, cr)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    prep = (on_chip((cr,), jnp.int64), on_chip((cr,), jnp.int32),
            on_chip((), jnp.int32), on_chip((cr,), jnp.int32))
    probe = jax.jit(lambda left, p: probe_fast(left, 0, *p, "left")[0][:-1])
    args = (_shapes(_keyed_batch(900, cl, 1), one_chip), prep)
    text = probe.lower(*args).as_text()
    assert "stablehlo.while" in text and "stablehlo.sort" not in text
    _compile(probe, *args)


def test_sparse_build_and_semi_probe_compile_for_v5e(one_chip):
    """TPC-H Q18's semi-join at SF1: a few thousand int32 keys spread
    over 1.5M values, in the 2^21 slots the aggregate above it left them
    in (a 2^21-entry table for under 2^13 rows: the key range and the
    build's capacity size it, not its row count; shrunk to 2^13 slots
    the same build would be searched) and the ``semi`` probe of a
    2^22-row stream batch by address."""
    import jax.numpy as jnp
    from spark_rapids_tpu.host.batch import HostBatch
    from spark_rapids_tpu.ops.join import (build_direct_table,
                                           direct_table_size, probe_direct)
    build_cap = table = 1 << 21
    assert direct_table_size(4401, 7, 1_499_990, build_cap) == table
    assert direct_table_size(4401, 7, 1_499_990, 1 << 13) is None

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    prep = (on_chip((build_cap,), jnp.int32), on_chip((build_cap,), jnp.int32),
            on_chip((), jnp.int32))
    _compile(lambda k, p, n: build_direct_table(k, p, n, table), *prep)
    build = jax.tree.map(
        lambda a: on_chip(a.shape, a.dtype),
        jax.eval_shape(lambda k, p, n: build_direct_table(k, p, n, table),
                       *prep))
    assert build.table.shape == (table, 2)
    schema = T.Schema([T.StructField("l_orderkey", T.IntegerType(), True),
                       T.StructField("l_quantity", T.DoubleType(), True)])
    lb = HostBatch.from_pydict(
        {"l_orderkey": np.arange(900, dtype=np.int32),
         "l_quantity": np.ones(900)}, schema).to_device(capacity=1 << 22)
    _compile(lambda left, b: probe_direct(left, 0, b, "semi")[0][:-1],
             _shapes(lb, one_chip), build)


def test_concat_batches_compiles_for_v5e(one_chip):
    """Three packed batches (s64, string, f64) of 2^20, 2^19 and 2^20
    slots placed into a 2^22 bucket: offsets are traced scalars, so the
    chip's program is dynamic-update-slices and one elementwise pass —
    no sort and no gather (f64 is stored, never computed on)."""
    from spark_rapids_tpu.ops import kernels as dk
    parts = [_shapes(_keyed_batch(900, cap, 5), one_chip)
             for cap in (1 << 20, 1 << 19, 1 << 20)]
    hlo = _compile(lambda bs: dk.concat_batches(bs), parts).as_text()
    assert " dynamic-update-slice(" in hlo
    assert " sort(" not in hlo and " gather(" not in hlo


def test_two_filter_stage_compiles_for_v5e(one_chip):
    """q44's fused Filter -> Filter at its batch size (2^20 rows, an s64,
    a string and an f64 column): both conditions and one compaction --
    a ``cond`` over two moves, each one index scatter and row gathers;
    the chip's compiler turns none of it into a sort."""
    from spark_rapids_tpu import TpuSession
    from spark_rapids_tpu.exec.fused import FusedStageExec, stage_body
    from spark_rapids_tpu.expr.core import col
    b = _keyed_batch(900, 1 << 20, 6)
    q = TpuSession({}).from_pydict(
        {"k": [1], "state": ["CA"], "v": [1.0]}, b.schema) \
        .filter(col("k") == 4).filter(col("v").is_null())
    ov, meta = q._overridden(quiet=True)
    assert isinstance(meta.exec_node, FusedStageExec), meta.exec_node
    hlo = _compile(stage_body(meta.exec_node.fused_ops),
                   _shapes(b, one_chip)).as_text()
    assert hlo.count(" conditional(") == 1
    assert 1 <= hlo.count(" scatter(") <= 2       # at most one a branch
    assert " gather(" in hlo and " sort(" not in hlo


def test_q1_shaped_compaction_compiles_for_v5e(one_chip):
    """``compact`` at q1's first batch (2^22 slots: four f64, two
    one-character strings, a date): the stacks of a dtype stay a few
    hundred MB of temporaries, and the 2^22-slot index scatter of the full
    branch is the one the chip's compiler still sorts first (the bucket
    no longer fits its fast memory; the small branch's does)."""
    import pyarrow as pa

    from spark_rapids_tpu.ops import kernels as dk
    n, cap = 900, 1 << 22
    rng = np.random.default_rng(3)
    b = ColumnBatch.from_arrow(pa.record_batch({
        **{name: pa.array(np.round(rng.uniform(0, 1e4, n), 2))
           for name in ("l_quantity", "l_extendedprice", "l_discount",
                        "l_tax")},
        "l_returnflag": pa.array(rng.choice(["A", "N", "R"], n)),
        "l_linestatus": pa.array(rng.choice(["F", "O"], n)),
        "l_shipdate": pa.array(rng.integers(8000, 10600, n).astype(np.int32),
                               type=pa.date32()),
    }), capacity=cap)
    compiled = _compile(lambda x: dk.compact(x, x.columns[6].data <= 10471),
                        _shapes(b, one_chip))
    hlo = compiled.as_text()
    assert hlo.count(" conditional(") == 1
    assert hlo.count(" scatter(") == 2 and hlo.count(" sort(") <= 1
    # one gather a stack a branch: flags, f64 as two f32 halves, bytes, s32
    assert hlo.count(" gather(") == 2 * 5
    args = compiled.memory_analysis().argument_size_in_bytes
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * args


def test_string_key_sort_compiles_for_v5e(one_chip):
    """lax.sort keyed on a string column (padded byte matrix + length),
    s64 and f64 payload gathered behind it."""
    from spark_rapids_tpu.ops.sort import SortOrder, sort_batch
    b = _keyed_batch(900, CAP, 3)
    orders = [SortOrder(1, True)]
    _compile(lambda x: sort_batch(x, orders), _shapes(b, one_chip))


def test_group_by_update_compiles_for_v5e(one_chip):
    """The aggregate update with both branches of its ``lax.cond``: key
    discovery (a ``while_loop`` of equality passes over an s64 key),
    masked f64/s64 reductions per group, the 64-row table sort, and
    ``sorted_group_by`` as the fall-back (a string key costs what
    ``test_string_key_sort_compiles_for_v5e`` already pays)."""
    from spark_rapids_tpu.ops.segmented import AggSpec, group_by_update
    b = _keyed_batch(900, CAP, 4)
    specs = [AggSpec("sum", 2), AggSpec("min", 2), AggSpec("first", 0),
             AggSpec("count_star", 0)]
    compiled = _compile(lambda x: group_by_update(x, [0], specs),
                        _shapes(b, one_chip))
    hlo = compiled.as_text()
    assert " conditional(" in hlo and " while(" in hlo
    # the sort branch reads its sorted rows through scans and gathers:
    # beside the key sort at most one more capacity-row sort (the segment
    # starts), and at most one scatter of capacity-many updates -- what
    # the TPU compiler makes of a scatter that large is a sort of its own
    sorts, scatters = _capacity_row(hlo, "sort"), _capacity_row(hlo, "scatter")
    assert len(sorts) <= 2 and len(scatters) <= 1, (sorts, scatters)


def _q51_window(which: str):
    """``(exec, batch)``: q51's running sum over the daily sums, or its
    two running maxes over the full join's rows, at ``CAP``."""
    from spark_rapids_tpu.exec import LocalScanExec
    from spark_rapids_tpu.exec.window import WindowExec
    from spark_rapids_tpu.expr.aggregates import Max, Sum
    from spark_rapids_tpu.expr.core import col
    from spark_rapids_tpu.expr.window import (CURRENT_ROW, UNBOUNDED,
                                              WindowExpression, WindowFrame,
                                              WindowSpec)
    from spark_rapids_tpu.host.batch import HostBatch
    money = ["day_sales"] if which == "sum" else ["web_sales", "store_sales"]
    schema = T.Schema([T.StructField("item_sk", T.IntegerType(), True),
                       T.StructField("d_date", T.DateType(), True)]
                      + [T.StructField(m, T.DoubleType(), True)
                         for m in money])
    rng = np.random.default_rng(51)
    n = 900
    data = {"item_sk": rng.integers(1, 40, n).astype(np.int32),
            "d_date": rng.integers(10957, 11323, n).astype(np.int32)}
    data.update({m: np.round(rng.uniform(0, 300, n), 2) for m in money})
    hb = HostBatch.from_pydict(data, schema)

    def over(fn):       # a spec built anew for every expression, as q51's
        return WindowExpression(fn, WindowSpec(
            partition_by=(col("item_sk"),),
            order_by=((col("d_date"), True),),
            frame=WindowFrame("rows", UNBOUNDED, CURRENT_ROW)))
    agg = Sum if which == "sum" else Max
    ex = WindowExec([over(agg(col(m))).alias("c_" + m) for m in money],
                    LocalScanExec.from_pydict(data, schema))
    return ex, hb.to_device(capacity=CAP)


@pytest.mark.parametrize("which", ["sum", "two_max"])
def test_q51_window_body_compiles_for_v5e(one_chip, which):
    """The window program with q51's columns: the sort by (item, day),
    one row gather a dtype, the segment arrays and the frames.  A
    running frame is a scan of shifted passes: the program holds the key
    sort (five operands: three stable passes of two, the later two
    gathering their keys: 3 sorts, 4 gathers), the row gather (bool,
    int32, float64 leaves, stacked) and no other capacity-row sort,
    scatter or gather (the parent's frames took 23 levels of a sparse
    table and two gathers a level, its row sort one gather a leaf: 16
    and 22 gathers before a single frame)."""
    from spark_rapids_tpu.exec.window import _window_body
    ex, batch = _q51_window(which)
    aug, orders, part_idx, order_idx, input_idx, nbase = \
        ex._window_args(batch)
    assert len(ex._fns) == (1 if which == "sum" else 2)     # one exec
    assert aug.num_columns == nbase     # plain columns are not appended
    hlo = _compile(lambda b: _window_body(
        b, orders, part_idx, order_idx, input_idx, ex._fns, nbase,
        ex.output_schema), _shapes(aug, one_chip)).as_text()
    assert not _capacity_row(hlo, "scatter")
    assert len(_capacity_row(hlo, "sort")) <= 3
    assert len(_capacity_row(hlo, "gather")) <= 8


def test_distributed_groupby_compiles_for_2x2_mesh(topo):
    """partial group-by -> all-to-all -> merge as ONE shard_map program
    over the four described devices: the engine's own, a bare
    ``MeshAggregateExec`` built by the launcher every mesh program is."""
    import __graft_entry__ as g
    from jax.sharding import Mesh
    from spark_rapids_tpu.exec import compile_cache as cc
    from spark_rapids_tpu.exec.basic import LocalScanExec
    from spark_rapids_tpu.exec.mesh_exec import MeshAggregateExec
    from spark_rapids_tpu.expr.aggregates import (Count, CountStar, Max,
                                                  Min, Sum)
    from spark_rapids_tpu.expr.core import col
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    price, qty = col("ss_sales_price"), col("ss_quantity")
    agg = MeshAggregateExec(
        [col("ss_customer_sk")],
        [col("ss_customer_sk"), Sum(price).alias("s"),
         Count(price).alias("c"), Min(qty).alias("lo"),
         Max(qty).alias("hi"), CountStar().alias("n")],
        LocalScanExec([], g.SCHEMA), 4)
    local = _sales_batch(cap=CAP >> 2)   # x4 shards: the same rows in all
    stacked = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            (4,) + a.shape, a.dtype,
            sharding=NamedSharding(mesh, P("data"))), local)
    # the virtual CPU devices and the described ones share their ids, so
    # the process-wide cache could hand back a CPU mesh's program
    cc.reset_cache()
    try:
        compiled = _compile(agg._launcher._program(mesh).fn, stacked)
    finally:
        cc.reset_cache()
    assert "all-to-all" in compiled.as_text()


def test_region_join_body_compiles_for_2x2_mesh(topo, monkeypatch):
    """The region program of a two-join pipeline with dense ``int32`` keys
    (fact x a month of days x a filtered dimension, then an exchange),
    lowered against the four described devices with each prepared build
    replicated: a join's body -- the ops under its ``join<i>`` scope --
    holds NO sort and NO collective (the build arrives prepared: its
    all-gather and its sorts left the program), ONE scatter (the
    expanding plan's offsets) and a handful of gathers (the table read,
    the per-row numbers, ``perm[pos]``, one a dtype of each side's
    stacked leaves).  The parent's body at this shape (its module scoped
    the same way, PR 44): 2 sorts (stream + build ranked together, the
    build's ranks again), 2 scatters, 20-22 gathers of which 6 sit in
    ``while`` loops (three ``searchsorted`` of 12 steps each here, 20 at
    2^20 slots) and the builds' all-gathers; at 2^20 slots and four joins
    the new bodies hold 0 / 1 / 3-6 each."""
    from jax.sharding import Mesh
    from spark_rapids_tpu.exec import compile_cache as cc
    from spark_rapids_tpu.exec.mesh_exec import MeshLauncher
    from spark_rapids_tpu.expr.core import col
    from spark_rapids_tpu.session import TpuSession
    rng = np.random.default_rng(3)
    n, cap = 3000, CAP >> 2
    ints = lambda *names: T.Schema(
        [T.StructField(c, T.IntegerType(), True) for c in names])
    s = TpuSession({"spark.rapids.tpu.mesh.deviceCount": 4,
                    "spark.rapids.sql.resultCache.enabled": False})
    fact = s.from_pydict({
        "d": rng.integers(2451000, 2451100, n).astype(np.int32),
        "i": rng.integers(1, 18000, n).astype(np.int32),
        "c": rng.integers(1, 100000, n).astype(np.int32)},
        ints("d", "i", "c"), partitions=3)
    days = s.from_pydict(
        {"dk": np.arange(2451020, 2451051, dtype=np.int32)}, ints("dk"))
    items = s.from_pydict({"ik": np.sort(rng.choice(
        np.arange(1, 18000, dtype=np.int32), 3000, replace=False))},
        ints("ik"))
    df = fact.join(days, [("d", "dk")]).join(items, [("i", "ik")]) \
        .repartition(4, col("c"))

    # one collect on the virtual CPU mesh hands over what the region
    # launched with; the same body is then lowered for the described chips
    seen = {}
    real = MeshLauncher._launch

    def spy(self, ctx, mesh, stacked, builds, leaf_cap, modes, probes):
        if self._joins:
            seen.update(launcher=self, stacked=stacked, builds=builds,
                        leaf_cap=leaf_cap, modes=modes, probes=probes)
        return real(self, ctx, mesh, stacked, builds, leaf_cap, modes,
                    probes)
    monkeypatch.setattr(MeshLauncher, "_launch", spy)
    assert df.collect()
    launcher = seen["launcher"]
    assert [p[0] for p in seen["probes"]] == ["direct", "direct"]
    assert seen["modes"] == ("replicated", "replicated")

    mesh = Mesh(np.asarray(topo.devices), ("data",))

    def described(spec, rows=None):
        def shape(a):
            dims = a.shape if rows is None or a.ndim == 1 \
                else a.shape[:1] + (rows,) + a.shape[2:]
            return jax.ShapeDtypeStruct(
                dims, a.dtype, sharding=NamedSharding(mesh, spec))
        return shape
    stacked = jax.tree.map(described(P("data"), cap), seen["stacked"])
    builds = [jax.tree.map(described(P()), b) for b in seen["builds"]]
    # the virtual CPU devices and the described ones share their ids, so
    # the process-wide cache would hand back the CPU mesh's program
    cc.reset_cache()
    try:
        caps = launcher._caps(cap, seen["modes"], None)
        program = launcher._program(mesh, None, seen["modes"], caps,
                                    seen["probes"])
        hlo = _compile(program.fn, stacked, *builds).as_text()
    finally:
        cc.reset_cache()
    assert "all-gather" not in hlo
    for j in range(2):
        ops = re.findall(rf"= .+? ([\w-]+)\([^\n]*/join{j}/", hlo)
        assert ops, "the join's scope is in the ops' metadata"
        assert "sort" not in ops and "while" not in ops
        assert not [op for op in ops if op.startswith("all-")]
        assert ops.count("scatter") == 1
        assert 1 <= ops.count("gather") <= 8
