"""TPC-DS q51 as the benchmark runs it (benchmark/queries/tpcds_q51.py
and tpcds_q51_all.py, their references, benchmark/datagen/tpcds.py,
loaded by path as the harness does) on XLA:CPU at SF0.01: the engine's
device path against the plain reference with the limit on and lifted,
hand-made rows that hold an exact tie of the two cumulatives and every
NULL the full outer join and the running ``max`` can make, and what the
windows and the full join leave in the per-query record."""
import datetime
import os

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark.harness.cell import ROOT, load_module
from benchmark.harness.compare import rows_match
from spark_rapids_tpu import TpuSession
from spark_rapids_tpu.exec.window import WindowExec
from spark_rapids_tpu.obs.registry import get_registry

CONF = {"spark.rapids.sql.resultCache.enabled": "false",
        "spark.rapids.sql.test.enabled": "true"}


def _bench(kind, name):
    return load_module(ROOT, kind, name)


@pytest.fixture(scope="module")
def session():
    s = TpuSession(dict(CONF))
    yield s
    s.shutdown(drain=False)


@pytest.fixture(scope="module")
def generated(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("q51") / "sf001_seed42")
    _bench("datagen", "tpcds").generate(
        path, 0.01, 42, sorted(_bench("queries", "tpcds_q51").TABLES))
    return path


def _in_order(rows):
    return [(r[0], str(r[1])) for r in rows]


@pytest.mark.parametrize("query", ["tpcds_q51", "tpcds_q51_all"])
def test_device_path_against_the_reference(session, generated, query):
    ref = _bench("reference", "tpcds_q51")
    y = ref.cumulatives(generated)
    every = ref.as_rows(ref.qualifying(y))
    want = _bench("reference", query).rows(generated)
    assert want == (every[:100] if query == "tpcds_q51" else every)
    assert len(every) > 300 and len(y) > 5 * len(every)
    got = _bench("queries", query).build(session, generated).collect()
    assert isinstance(got[0][1], datetime.date)
    assert rows_match(got, want)
    # the harness compares without order; the order is held here
    assert _in_order(got) == _in_order(want)
    # not vacuous: a row lost, a row of y that does not qualify let in
    assert not rows_match(got[1:], want)
    out = ref.as_rows(y[~y.index.isin(ref.qualifying(y).index)].head(1))
    assert not rows_match(got[:-1] + out, want)


def test_q51_record_follows_the_plan(session, generated):
    ref = _bench("reference", "tpcds_q51")
    y = ref.cumulatives(generated)
    df = _bench("queries", "tpcds_q51_all").build(session, generated)
    _, meta = df._overridden(quiet=True)

    def walk(node):
        yield node
        for c in node.children:
            yield from walk(c)
    windows = [n for n in walk(meta.exec_node) if isinstance(n, WindowExec)]
    # a running sum a channel, and ONE exec for the two running maxes
    assert sorted(len(w._fns) for w in windows) == [1, 1, 2]
    df.collect()
    c = get_registry().recent_queries(1)[0]["counters"]
    assert c["window.launches"] == c["program.window_frame.launches"] == 3
    assert c["window.frames.scanned"] == 4      # no sparse table
    assert c["window.sum.cents"] == 2
    web = int(y.web_sales_has.sum())
    store = int(y.store_sales_has.sum())
    assert c["window.rows"] == web + store + len(y)
    assert c["window.rows@int,date,double>double"] == web + store
    assert c["window.rows@int,date,double,double>double,double"] == len(y)
    # the store side is the build: its rows no web row matched
    assert c["join.full.unmatched_rows"] == len(y) - web
    assert c["join.keys.packed"] == 1 and "join.probe.sorted" not in c
    # (item, day) is unique on both sides: every web row comes out of the
    # full join's stream phase once, so each of its batches keeps its own
    # columns in place (PR 43; the joins to date_dim drop rows and expand)
    assert c["join.gather.aligned"] == 1


def _write(path, web, store):
    """Hand-made q51 tables: ``web`` / ``store`` = (item, day of the
    year 2000 or None, price), and the year's date_dim."""
    first = datetime.date(2000, 1, 1)
    epoch = 2415022 + (first - datetime.date(1900, 1, 1)).days
    days = list(range(-3, 370))
    tables = {"date_dim": pa.table({
        "d_date_sk": pa.array([epoch + d for d in days], pa.int32()),
        "d_date": pa.array([first + datetime.timedelta(d) for d in days],
                           pa.date32()),
        "d_month_seq": pa.array(
            [(lambda x: (x.year - 1900) * 12 + x.month - 1)(
                first + datetime.timedelta(d)) for d in days], pa.int32())})}
    for name, p, rows in (("web_sales", "ws", web), ("store_sales", "ss",
                                                     store)):
        tables[name] = pa.table({
            f"{p}_item_sk": pa.array([r[0] for r in rows], pa.int32()),
            f"{p}_sold_date_sk": pa.array(
                [None if r[1] is None else epoch + r[1] for r in rows],
                pa.int32()),
            f"{p}_sales_price": pa.array([r[2] for r in rows],
                                         pa.float64())})
    for name, t in tables.items():
        os.makedirs(os.path.join(path, name))
        pq.write_table(t, os.path.join(path, name, "part-00000.parquet"))
    return path


def test_ties_and_nulls(session, tmp_path):
    big = [(1, d, 99999.99) for d in range(200)]    # a prefix to carry
    web = big + [
        (2, 0, 0.1), (2, 1, 0.25), (2, 2, 0.25),    # 0.60 by three addends
        (3, 5, 7.00),                           # web alone: store NULL
        (4, 1, 2.50), (4, 3, None),             # a day with no price
        (5, 0, 1.00), (5, 2, 1.00), (5, 9, 0.01),
        (8, 0, None), (8, 1, 1.00),
        (None, 1, 5.00), (6, None, 5.00), (6, -2, 5.00)]    # all dropped
    store = [(1, d, 99999.98) for d in range(200)] + [
        (2, 0, 0.3), (2, 2, 0.3),               # 0.60 by two: a tie
        (4, 2, 2.49),
        (5, 1, 2.00), (5, 9, 0.01),             # ties on day 2, then again
        (7, 4, 3.00),                           # store alone: web NULL
        (8, 0, 0.50)]
    # forty more ties, each behind another prefix of the rows before it:
    # a sum that carries that prefix's rounding breaks some of them
    for i in range(10, 50):
        web += [(i, 0, 0.1), (i, 1, 0.25), (i, 2, 0.25), (i, 3, 33.33)]
        store += [(i, 0, 0.3), (i, 2, 0.3), (i, 3, 33.33)]
    path = _write(str(tmp_path / "hand"), web, store)
    ref = _bench("reference", "tpcds_q51")
    y = ref.cumulatives(path)
    tied = {(r[0], r[1]) for r in ref.as_rows(ref.ties(y))}
    assert tied == {(2, "2000-01-03"), (5, "2000-01-03"), (5, "2000-01-10")} \
        | {(i, d) for i in range(10, 50) for d in ("2000-01-03", "2000-01-04")}
    want = _bench("reference", "tpcds_q51_all").rows(path)
    keys = {(r[0], r[1]) for r in want}
    assert not keys & tied and not {3, 6, 7} & {r[0] for r in want}
    # item 1: web a cent a day ahead; item 2: ahead until the tie; item
    # 4: 2.50 > 2.49 from the day the store sold; the day with no price
    # adds nothing to the running sum
    assert len([r for r in want if r[0] == 1]) == 200
    assert [r[1] for r in want if r[0] == 2] == ["2000-01-02"]
    assert [r[1:] for r in want if r[0] == 4] == [
        ("2000-01-03", None, 2.49, 2.5, 2.49),
        ("2000-01-04", 2.5, None, 2.5, 2.49)]
    # item 8: no price yet on its first day, so no cumulative to compare
    assert [r[1:] for r in want if r[0] == 8] == [
        ("2000-01-02", 1.0, None, 1.0, 0.5)]
    assert [r[1] for r in want if r[0] == 5] == []
    assert [r[1] for r in want if r[0] >= 10] == ["2000-01-02"] * 40
    got = _bench("queries", "tpcds_q51_all").build(session, path).collect()
    assert rows_match(got, want) and _in_order(got) == _in_order(want)
    # the tied cumulatives are the same doubles, not merely close
    assert all(r[4] != r[5] for r in got)
