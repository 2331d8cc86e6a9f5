"""Window function differential tests (reference WindowFunctionSuite +
integration_tests window_function_test.py coverage)."""
import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exec import LocalScanExec, collect_host
from spark_rapids_tpu.exec.window import WindowExec
from spark_rapids_tpu.expr.aggregates import Average, Count, CountStar, \
    Max, Min, Sum
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.expr.window import (CURRENT_ROW, UNBOUNDED, DenseRank,
                                          Lag, Lead, Rank, RowNumber,
                                          WindowExpression, WindowFrame,
                                          WindowSpec)
from spark_rapids_tpu.testing import assert_tpu_and_cpu_equal

SCHEMA = T.Schema([
    T.StructField("g", T.IntegerType(), True),
    T.StructField("o", T.IntegerType(), True),
    T.StructField("v", T.LongType(), True),
    T.StructField("f", T.DoubleType(), True),
])


def _scan(rng, n=200, ngroups=8):
    return LocalScanExec.from_pydict({
        "g": [None if rng.random() < 0.05 else int(x)
              for x in rng.integers(0, ngroups, n)],
        "o": [int(x) for x in rng.integers(0, 50, n)],
        "v": [None if rng.random() < 0.1 else int(x)
              for x in rng.integers(-100, 100, n)],
        "f": [float("nan") if rng.random() < 0.05 else float(np.round(x, 3))
              for x in rng.normal(size=n)],
    }, SCHEMA, rows_per_batch=64)


SPEC = WindowSpec(partition_by=(col("g"),), order_by=((col("o"), True),))


def test_ranking_functions(rng):
    plan = WindowExec([
        WindowExpression(RowNumber(), SPEC).alias("rn"),
        WindowExpression(Rank(), SPEC).alias("rk"),
        WindowExpression(DenseRank(), SPEC).alias("dr"),
    ], _scan(rng))
    rows = assert_tpu_and_cpu_equal(plan)
    assert rows


def test_running_aggregates_default_frame(rng):
    # default frame with order: RANGE unbounded preceding .. current row
    plan = WindowExec([
        WindowExpression(Sum(col("v")), SPEC).alias("rs"),
        WindowExpression(Count(col("v")), SPEC).alias("rc"),
        WindowExpression(CountStar(), SPEC).alias("rcs"),
        WindowExpression(Average(col("v")), SPEC).alias("ra"),
    ], _scan(rng))
    assert_tpu_and_cpu_equal(plan)


def test_whole_partition_aggregates(rng):
    spec = WindowSpec(partition_by=(col("g"),))
    plan = WindowExec([
        WindowExpression(Sum(col("v")), spec).alias("ts"),
        WindowExpression(Min(col("v")), spec).alias("tmin"),
        WindowExpression(Max(col("f")), spec).alias("tmax"),
    ], _scan(rng))
    assert_tpu_and_cpu_equal(plan)


def test_bounded_rows_frames(rng):
    spec = WindowSpec(partition_by=(col("g"),),
                      order_by=((col("o"), True),),
                      frame=WindowFrame("rows", -2, 1))
    plan = WindowExec([
        WindowExpression(Sum(col("v")), spec).alias("ws"),
        WindowExpression(Min(col("v")), spec).alias("wmin"),
        WindowExpression(Max(col("v")), spec).alias("wmax"),
        WindowExpression(Average(col("v")), spec).alias("wavg"),
        WindowExpression(Max(col("f")), spec).alias("wfmax"),
    ], _scan(rng))
    assert_tpu_and_cpu_equal(plan)


def test_lead_lag(rng):
    plan = WindowExec([
        WindowExpression(Lead(col("v"), 1), SPEC).alias("ld"),
        WindowExpression(Lag(col("v"), 2), SPEC).alias("lg"),
        WindowExpression(Lead(col("v"), 1, lit(-999)), SPEC).alias("ldd"),
    ], _scan(rng))
    assert_tpu_and_cpu_equal(plan)


def test_desc_order_and_row_number(rng):
    spec = WindowSpec(partition_by=(col("g"),),
                      order_by=((col("o"), False),))
    plan = WindowExec([
        WindowExpression(RowNumber(), spec).alias("rn"),
        WindowExpression(Sum(col("v")), spec).alias("rs"),
    ], _scan(rng))
    assert_tpu_and_cpu_equal(plan)


def test_mixed_specs_rejected(rng):
    other = WindowSpec(partition_by=(col("o"),))
    with pytest.raises(ValueError):
        WindowExec([
            WindowExpression(RowNumber(), SPEC).alias("a"),
            WindowExpression(RowNumber(), other).alias("b"),
        ], _scan(rng))


def test_empty_input(rng):
    empty = LocalScanExec.from_pydict(
        {"g": [], "o": [], "v": [], "f": []}, SCHEMA)
    plan = WindowExec([
        WindowExpression(RowNumber(), SPEC).alias("rn"),
    ], empty)
    assert assert_tpu_and_cpu_equal(plan) == []


def test_bounded_following_only_frame(rng):
    # ROWS BETWEEN 2 FOLLOWING AND 5 FOLLOWING: empty frames at partition
    # tails must produce count 0 (regression: negative cross-partition diff)
    spec = WindowSpec(partition_by=(col("g"),),
                      order_by=((col("o"), True),),
                      frame=WindowFrame("rows", 2, 5))
    plan = WindowExec([
        WindowExpression(CountStar(), spec).alias("c"),
        WindowExpression(Count(col("v")), spec).alias("cv"),
        WindowExpression(Sum(col("v")), spec).alias("s"),
    ], _scan(rng, n=60, ngroups=4))
    rows = assert_tpu_and_cpu_equal(plan)
    assert all(r[4] >= 0 for r in rows)


def test_multi_partition_window_keeps_parallelism(rng):
    """The planner hash-partitions on window partition keys so the window
    program runs per partition instead of collapsing the world into one
    batch (round-3 scaling cliff; reference GpuWindowExec.scala:92 needs
    one batch per partition GROUP only)."""
    from spark_rapids_tpu import TpuSession
    from spark_rapids_tpu.exec.core import ExecCtx
    from spark_rapids_tpu.exec.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.expr.aggregates import Sum as _Sum

    s = TpuSession({"spark.sql.shuffle.partitions": 4})
    n = 300
    df = s.from_pydict({
        "g": [None if rng.random() < 0.05 else int(x)
              for x in rng.integers(0, 16, n)],
        "o": [int(x) for x in rng.integers(0, 40, n)],
        "v": [None if rng.random() < 0.1 else int(x)
              for x in rng.integers(-50, 50, n)],
    }, T.Schema([T.StructField("g", T.IntegerType(), True),
                 T.StructField("o", T.IntegerType(), True),
                 T.StructField("v", T.LongType(), True)]),
        partitions=3, rows_per_batch=64)
    spec = WindowSpec(partition_by=(col("g"),), order_by=((col("o"), True),))
    out = df.select(
        col("g"), col("o"), col("v"),
        WindowExpression(RowNumber(), spec).alias("rn"),
        WindowExpression(_Sum(col("v")), spec).alias("rs"))

    _, meta = out._overridden(quiet=True)
    ctx = ExecCtx(backend="host")
    wins = [nd for nd in _walk(meta.exec_node) if isinstance(nd, WindowExec)]
    assert wins, "plan lost its WindowExec"
    assert all(w.num_partitions(ctx) > 1 for w in wins), \
        "window collapsed to a single partition"
    assert any(isinstance(nd, ShuffleExchangeExec)
               for w in wins for nd in _walk(w)), \
        "planner did not insert the hash exchange under the window"

    # differential: device result == host oracle through the full planner
    from spark_rapids_tpu.exec.core import collect_host
    dev_rows = sorted(out.collect(), key=_row_key)
    host_rows = sorted(collect_host(meta.exec_node, s.conf), key=_row_key)
    assert len(host_rows) == len(dev_rows) == n
    assert host_rows == dev_rows


def _walk(node):
    yield node
    for c in node.children:
        yield from _walk(c)


def _row_key(r):
    return tuple((x is None, 0 if x is None else x)
                 if x is None or isinstance(x, (int, float))
                 else (False, str(x)) for x in r)


def test_global_window_streams_bounded_memory(rng):
    """Empty-partition-by plain-aggregate windows run as a two-pass
    stream: one running state + spillable parked batches, emitting one
    output batch PER input batch instead of one world-sized batch
    (VERDICT r4 item 10; reference contract is single batch per GROUP,
    GpuWindowExec.scala:92)."""
    from spark_rapids_tpu.exec.core import ExecCtx, device_to_host
    scan = _scan(rng, n=300)
    gspec = WindowSpec()
    plan = WindowExec([
        WindowExpression(Sum(col("v")), gspec).alias("sv"),
        WindowExpression(CountStar(), gspec).alias("c"),
        WindowExpression(Count(col("v")), gspec).alias("cv"),
        WindowExpression(Min(col("v")), gspec).alias("mn"),
        WindowExpression(Max(col("f")), gspec).alias("mx"),
        WindowExpression(Average(col("v")), gspec).alias("av"),
    ], scan)
    assert plan._global_streamable()
    assert plan.output_batching is None
    rows = assert_tpu_and_cpu_equal(plan)
    assert len(rows) == 300
    # the device path must emit MULTIPLE batches (bounded memory), not
    # one world batch
    with ExecCtx(backend="device") as ctx:
        batches = list(plan.partition_iter(ctx, 0))
        assert len(batches) > 1
        got = [r for b in batches for r in device_to_host(b).to_rows()]
    assert len(got) == 300


def test_global_window_streaming_exact_int64(rng):
    """int64 extremes/sums past 2^53 stay exact through the streaming
    accumulator (an f64 fold would round them)."""
    big = (1 << 60) + 12345
    scan = LocalScanExec.from_pydict(
        {"v": [big, big + 7, None, -big]},
        T.Schema([T.StructField("v", T.LongType(), True)]),
        rows_per_batch=2)
    gspec = WindowSpec()
    plan = WindowExec([
        WindowExpression(Max(col("v")), gspec).alias("mx"),
        WindowExpression(Min(col("v")), gspec).alias("mn"),
        WindowExpression(Sum(col("v")), gspec).alias("s"),
    ], scan)
    rows = assert_tpu_and_cpu_equal(plan)
    # sum over [big, big+7, None, -big] = big + 7, exactly
    assert rows[0][1:] == (big + 7, -big, big + 7)


def test_global_window_with_order_keeps_single_batch(rng):
    """An ordered global window (running frame) is NOT streamable — it
    must keep the sorted single-batch path."""
    plan = WindowExec([
        WindowExpression(Sum(col("v")),
                         WindowSpec(order_by=((col("o"), True),)))
        .alias("rs")], _scan(rng, n=100))
    assert not plan._global_streamable()
    assert plan.output_batching is not None
    assert_tpu_and_cpu_equal(plan)


# ---------------------------------------------------------------------------
# PR 38: exact sums of money over frames, running frames by a scan, one
# WindowExec a distinct spec
# ---------------------------------------------------------------------------

MONEY = T.Schema([
    T.StructField("g", T.IntegerType(), True),
    T.StructField("o", T.IntegerType(), True),
    T.StructField("m", T.DoubleType(), True),
])
RUNNING_ROWS = WindowFrame("rows", UNBOUNDED, CURRENT_ROW)


def _money_rows(seed):
    """Partitions of whole-cent amounts in input order: partitions 1 and
    2 come to the same 0.60 by different addends (0.1 + 0.2 + 0.3 is not
    0.3 + 0.3 in doubles), after a partition of large amounts whose
    prefix a difference of prefix sums would carry into them."""
    rng = np.random.default_rng(seed)
    rows = [(0, i, float(c) / 100.0)
            for i, c in enumerate(rng.integers(10 ** 8, 10 ** 10, 40))]
    rows += [(1, 0, 0.1), (1, 1, 0.2), (1, 2, 0.3),
             (2, 0, 0.3), (2, 1, None), (2, 2, 0.3)]
    rows += [(3 + int(g), int(o), int(c) / 100.0) for g, o, c in
             zip(rng.integers(0, 5, 60), rng.permutation(60),
                 rng.integers(-30000, 30000, 60))]
    order = rng.permutation(len(rows))
    return [rows[i] for i in order]


@pytest.mark.parametrize("seed,rows_per_batch", [(1, 16), (2, 64), (3, 200)])
def test_running_sums_of_cents_are_the_integer_answer(seed, rows_per_batch):
    from spark_rapids_tpu.exec.core import collect_device
    from spark_rapids_tpu.ops import cents
    rows = _money_rows(seed)
    scan = LocalScanExec.from_pydict(
        {"g": [r[0] for r in rows], "o": [r[1] for r in rows],
         "m": [r[2] for r in rows]}, MONEY, rows_per_batch=rows_per_batch)
    spec = WindowSpec(partition_by=(col("g"),),
                      order_by=((col("o"), True),), frame=RUNNING_ROWS)
    plan = WindowExec([WindowExpression(Sum(col("m")), spec).alias("rs"),
                       WindowExpression(Average(col("m")), spec).alias("ra")],
                      scan)
    got = {(g, o): (rs, ra) for g, o, _, rs, ra in collect_device(plan)}
    # the integer answer: cents cumulated as Python ints, rounded once
    want, total, count = {}, {}, {}
    for g, o, m in sorted(rows, key=lambda r: (r[0], r[1])):
        if m is not None:
            total[g] = total.get(g, 0) + round(m * 100)
            count[g] = count.get(g, 0) + 1
        want[g, o] = (
            float(cents.from_cents(np, np.int64(total[g]))),
            float(cents.mean(np, cents.from_cents(np, np.int64(total[g])),
                             np.int64(count[g]))))
    assert got == want          # bit for bit, whatever the input order
    # the constructed tie: equal amounts are equal doubles
    assert got[1, 2][0] == got[2, 2][0] == 0.6
    # and the host oracle takes its sums the same way
    assert_tpu_and_cpu_equal(plan, approximate_float=False)


FRAMES = {"running_rows": RUNNING_ROWS,
          "running_range": WindowFrame("range", UNBOUNDED, CURRENT_ROW),
          "whole": WindowFrame("range", UNBOUNDED, UNBOUNDED),
          "whole_rows": WindowFrame("rows", UNBOUNDED, UNBOUNDED)}


def _sorted_batch(seed, n, capacity):
    """A batch sorted by (g, o) as ``_window_body`` hands it to the
    frames: NULL and NaN values, a partition with no value at all, peer
    groups, and ``capacity - n`` padding rows."""
    from spark_rapids_tpu.exec.core import host_to_device
    from spark_rapids_tpu.host.batch import HostBatch
    from spark_rapids_tpu.ops import kernels as dk
    rng = np.random.default_rng(seed)
    g = np.sort(rng.integers(0, 6, n))
    o = np.concatenate([np.sort(rng.integers(0, 4, (g == k).sum()))
                        for k in range(6)]) if n else g
    v = [None if (gg == 2 or rng.random() < 0.2) else int(x)
         for gg, x in zip(g, rng.integers(-50, 50, n))]
    f = [None if gg == 2 or rng.random() < 0.1 else
         float("nan") if rng.random() < 0.15 else float(np.round(x, 2))
         for gg, x in zip(g, rng.normal(size=n) * 10)]
    hb = HostBatch.from_pydict({"g": [int(x) for x in g],
                                "o": [int(x) for x in o], "v": v, "f": f},
                               SCHEMA)
    return dk._pad_jit(host_to_device(hb), capacity)


@pytest.mark.parametrize("op", ["count_star", "count", "sum", "avg", "min",
                                "max"])
@pytest.mark.parametrize("frame", sorted(FRAMES))
def test_scanned_frames_equal_the_table_frames(op, frame):
    """A frame that starts at its partition's first row is answered by
    scans; the prefix differences and the sparse table (what every frame
    took before, and frames bounded by offsets still take) give the
    same rows, padding and all."""
    from spark_rapids_tpu.ops import window as W
    from spark_rapids_tpu.ops.segmented import AggSpec, _compute_agg
    fr = FRAMES[frame]
    assert W.frame_scans(fr)
    for seed, n, cap in ((1, 90, 128), (2, 64, 64), (3, 0, 16), (4, 1, 16)):
        sb = _sorted_batch(seed, n, cap)
        seg = W.sorted_segments(sb, [0], [1])
        for ci in (2, 3):
            col_ = None if op == "count_star" else sb.columns[ci]
            data, validity, rtype = W.running_or_bounded_agg(
                op, col_, seg, fr)
            red = W._TableFrames(seg, fr)
            rows = red.sum(seg.real.astype(np.int32)).astype(np.int64)
            want = _compute_agg(AggSpec(op, 0), col_, red, seg.real,
                                seg.real, rows)
            assert rtype == want.dtype
            np.testing.assert_array_equal(np.asarray(validity),
                                          np.asarray(want.validity))
            keep = np.asarray(validity)
            np.testing.assert_allclose(np.asarray(data)[keep],
                                       np.asarray(want.data)[keep],
                                       rtol=1e-12, atol=1e-12)
            assert not np.asarray(validity)[n:].any()


@pytest.mark.parametrize("frame", sorted(FRAMES))
def test_scanned_frames_equal_the_host_oracle(rng, frame):
    spec = WindowSpec(partition_by=(col("g"),), order_by=((col("o"), True),),
                      frame=FRAMES[frame])
    plan = WindowExec([
        WindowExpression(Sum(col("v")), spec).alias("s"),
        WindowExpression(Count(col("f")), spec).alias("c"),
        WindowExpression(CountStar(), spec).alias("cs"),
        WindowExpression(Average(col("f")), spec).alias("a"),
        WindowExpression(Min(col("f")), spec).alias("mn"),
        WindowExpression(Max(col("f")), spec).alias("mx"),
        WindowExpression(Max(col("v")), spec).alias("mxv"),
    ], _scan(rng, n=150, ngroups=5))
    assert_tpu_and_cpu_equal(plan)


def _window_execs(df):
    _, meta = df._overridden(quiet=True)
    return [nd for nd in _walk(meta.exec_node) if isinstance(nd, WindowExec)]


@pytest.mark.parametrize("second,execs", [
    ("same", 1), ("other_partition", 2), ("other_order", 2),
    ("descending", 2), ("other_frame", 2)])
def test_one_window_exec_a_distinct_spec(second, execs):
    """Specs built from separate ``col()`` calls are one spec where
    their content is equal (an Expression hashes by identity and its
    ``==`` builds a truthy EqualTo, so neither may decide)."""
    from spark_rapids_tpu import TpuSession
    from spark_rapids_tpu.exec.window import spec_key

    def spec(part="g", order="o", asc=True, frame=RUNNING_ROWS):
        return WindowSpec(partition_by=(col(part),),
                          order_by=((col(order), asc),), frame=frame)
    other = {"same": spec(), "other_partition": spec(part="o"),
             "other_order": spec(order="v"), "descending": spec(asc=False),
             "other_frame": spec(frame=FRAMES["whole_rows"])}[second]
    assert (spec_key(spec()) == spec_key(other)) == (execs == 1)
    s = TpuSession({})
    df = s.from_pydict({"g": [1, 1, 2], "o": [1, 2, 3], "v": [5, 6, 7]},
                       T.Schema([T.StructField("g", T.IntegerType(), True),
                                 T.StructField("o", T.IntegerType(), True),
                                 T.StructField("v", T.LongType(), True)]))
    out = df.select(col("g"), col("o"),
                    WindowExpression(Max(col("v")), spec()).alias("a"),
                    WindowExpression(Sum(col("v")), other).alias("b"))
    assert len(_window_execs(out)) == execs
    assert len(out.collect()) == 3
    if execs == 2:
        # the guard compares the same key, so it can fire
        with pytest.raises(ValueError):
            WindowExec([WindowExpression(Max(col("v")), spec()).alias("a"),
                        WindowExpression(Sum(col("v")), other).alias("b")],
                       _window_execs(out)[0].children[0])


def test_global_window_streams_exact_money():
    """The two-pass global stream sums whole cents as integers, batch
    after batch: the total is the same double whatever the batches."""
    from spark_rapids_tpu.exec.core import collect_device
    vals = [0.1, 0.2, 0.3, 1e7 + 0.01, 0.3, 0.3, None, -1e7 - 0.01]
    got = set()
    for rows_per_batch in (1, 3, 8):
        scan = LocalScanExec.from_pydict(
            {"g": [1] * 8, "o": list(range(8)), "m": vals}, MONEY,
            rows_per_batch=rows_per_batch)
        plan = WindowExec([
            WindowExpression(Sum(col("m")), WindowSpec()).alias("s"),
            WindowExpression(Average(col("m")), WindowSpec()).alias("a")],
            scan)
        assert plan._global_streamable()
        got |= {r[3:] for r in collect_device(plan)}
        assert_tpu_and_cpu_equal(plan, approximate_float=False)
    assert got == {(1.2, 1.2 / 7)}


@pytest.mark.parametrize("window_keys,agg_keys", [
    (("g",), ("g", "o")), (("g", "o"), ("g", "o")), (("g", "x"), ("g",)),
    (("v",), ("g", "v"))])
def test_window_over_an_aggregate_needs_one_exchange(rng, window_keys,
                                                     agg_keys):
    """A window partitioned by some of the group keys of the aggregate
    below it (q51: daily sums by (item, day), running totals by item)
    runs after ONE exchange, made on the window's keys: groups are
    clustered on those just as well.  An aggregate grouped by fewer keys
    than the window partitions by keeps its own exchange."""
    from spark_rapids_tpu import TpuSession
    from spark_rapids_tpu.exec.core import ExecCtx, collect_host
    from spark_rapids_tpu.exec.exchange import ShuffleExchangeExec
    s = TpuSession({"spark.sql.shuffle.partitions": 4,
                    "spark.sql.adaptive.enabled": "false"})
    n = 400
    df = s.from_pydict({
        "g": [None if rng.random() < 0.05 else int(x)
              for x in rng.integers(0, 12, n)],
        "o": [int(x) for x in rng.integers(0, 9, n)],
        "v": [int(x) for x in rng.integers(0, 3, n)],
        "m": [int(x) / 100.0 for x in rng.integers(0, 10000, n)],
    }, T.Schema([T.StructField("g", T.IntegerType(), True),
                 T.StructField("o", T.IntegerType(), True),
                 T.StructField("v", T.IntegerType(), True),
                 T.StructField("m", T.DoubleType(), True)]),
        partitions=3, rows_per_batch=64)
    other = [k for k in ("g", "o", "v") if k not in agg_keys][0]
    agg = df.group_by(*agg_keys).agg(Sum(col("m")).alias("day"),
                                     Max(col(other)).alias("x"))
    order = [k for k in agg_keys if k not in window_keys] or ["x"]
    spec = WindowSpec(partition_by=tuple(col(k) for k in window_keys),
                      order_by=tuple((col(k), True) for k in order),
                      frame=RUNNING_ROWS)
    out = agg.select(*[col(k) for k in agg_keys],
                     WindowExpression(Sum(col("day")), spec).alias("cume"))
    _, meta = out._overridden(quiet=True)
    window, = [nd for nd in _walk(meta.exec_node)
               if isinstance(nd, WindowExec)]
    exchanges = [nd for nd in _walk(window)
                 if isinstance(nd, ShuffleExchangeExec)]
    assert len(exchanges) == 1 and window._keys_partitioned
    assert window.num_partitions(ExecCtx(backend="host")) == 4
    keyed = [k.name for k in exchanges[0].partitioning._keys]
    assert set(keyed) == set(window_keys) & set(agg_keys)
    dev_rows = sorted(out.collect(), key=_row_key)
    host_rows = sorted(collect_host(meta.exec_node, s.conf), key=_row_key)
    assert dev_rows == host_rows and dev_rows
