"""TPC-H Q18 as the benchmark runs it (benchmark/queries/tpch_q18.py,
benchmark/reference/tpch_q18.py, benchmark/datagen/tpch.py, loaded by
path as the harness does) on XLA:CPU: the engine's device path against
the plain reference at SF0.1 and on hand-made edge cases, and the
counters the query's mechanisms leave in the per-query record (a
high-cardinality sort-branch update, a semi-join against the
aggregate's result, fact-sized builds, a sort and a limit)."""
import datetime
import os

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark.harness.cell import ROOT, load_module
from benchmark.harness.compare import rows_match
from spark_rapids_tpu import TpuSession
from spark_rapids_tpu.obs.registry import get_registry

SF = 0.1
SEEDS = {"seed_42": 42, "seed_7": 7, "seed_2p31": 2**31 + 295}
CONF = {"spark.rapids.sql.resultCache.enabled": "false",
        "spark.rapids.sql.test.enabled": "true"}
#: counters that PR 31 added for what Q18 runs
NEW = ("join.semi.batches", "join.build.rows", "join.probe.sorted",
       "agg.update.rows", "sort.launches", "limit.rows_out")


def _bench(kind, name):
    return load_module(ROOT, kind, name)


@pytest.fixture(scope="module")
def session():
    s = TpuSession(dict(CONF))
    yield s
    s.shutdown(drain=False)


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    """``data(seed)``: that seed's q18 tables (q1's lineitem is among
    them), generated once."""
    base = tmp_path_factory.mktemp("q18")

    def of(seed: int) -> str:
        path = str(base / f"seed{seed}")
        _bench("datagen", "tpch").generate(
            path, SF, seed, sorted(_bench("queries", "tpch_q18").TABLES))
        return path
    return of


def _device_rows(session, data_dir):
    return _bench("queries", "tpch_q18").build(session, data_dir).collect()


def _reference_rows(data_dir):
    return _bench("reference", "tpch_q18").rows(data_dir)


def _in_order(rows):
    """Rows as the harness would print them, in the order they came."""
    return [tuple(str(x) for x in r) for r in rows]


def _write(path, lines, orders, customers=None):
    """Hand-made q18 tables under ``path``: ``lines`` = (orderkey,
    quantity), ``orders`` = (orderkey, custkey, date, price);
    customers default to every custkey the orders name."""
    if customers is None:
        customers = sorted({c for _, c, _, _ in orders if c is not None})
    tables = {
        "lineitem": pa.table({
            "l_orderkey": pa.array([k for k, _ in lines], pa.int32()),
            "l_quantity": pa.array([float(q) for _, q in lines],
                                   pa.float64())}),
        "orders": pa.table({
            "o_orderkey": pa.array([o[0] for o in orders], pa.int32()),
            "o_custkey": pa.array([o[1] for o in orders], pa.int32()),
            "o_orderdate": pa.array([o[2] for o in orders], pa.date32()),
            "o_totalprice": pa.array([o[3] for o in orders],
                                     pa.float64())}),
        "customer": pa.table({
            "c_custkey": pa.array(customers, pa.int32()),
            "c_name": pa.array([f"Customer#{c:09d}" for c in customers],
                               pa.string())}),
    }
    for name, table in tables.items():
        os.makedirs(os.path.join(path, name))
        pq.write_table(table, os.path.join(path, name, "part-0.parquet"))
    return path


def _lines(total, key, n=7):
    """``n`` lines of order ``key`` whose whole quantities sum to
    ``total``, none over 50."""
    q, r = divmod(total, n)
    assert q < 50
    return [(key, q + (i < r)) for i in range(n)]


D = datetime.date


def _edge_tables(case):
    """(lines, orders, customers or None, order keys expected in order)."""
    if case == "boundary":
        # 300 exactly is out, 301 is in; 299 is out; an order over 300
        # whose customer key is NULL, or names no customer, joins nothing
        lines = (_lines(300, 1) + _lines(301, 2) + _lines(299, 3)
                 + _lines(340, 4) + _lines(345, 5) + _lines(302, 6)
                 + [(7, 50), (8, 1)])
        orders = [(1, 11, D(1995, 1, 1), 900000.0),
                  (2, 12, D(1995, 1, 2), 1000.5),
                  (3, 13, D(1995, 1, 3), 800000.0),
                  (4, None, D(1995, 1, 4), 700000.0),
                  (5, 99, D(1995, 1, 5), 600000.0),
                  (6, 11, D(1995, 1, 6), 2000.25),
                  (7, 12, D(1995, 1, 7), 10.0),
                  (8, 13, D(1995, 1, 8), 20.0)]
        return lines, orders, [11, 12, 13], [6, 2]
    if case == "none_qualifies":
        lines = _lines(300, 1) + _lines(120, 2) + [(3, 50)]
        orders = [(1, 11, D(1995, 1, 1), 9.0), (2, 12, D(1995, 1, 2), 8.0),
                  (3, 13, D(1995, 1, 3), 7.0)]
        return lines, orders, None, []
    if case == "tied_on_price":
        # three orders at one price: the date decides; then a cheaper one
        lines = (_lines(310, 1) + _lines(320, 2) + _lines(330, 3)
                 + _lines(305, 4))
        orders = [(1, 11, D(1996, 5, 2), 5000.75),
                  (2, 12, D(1994, 3, 1), 5000.75),
                  (3, 13, D(1995, 9, 9), 5000.75),
                  (4, 14, D(1992, 1, 1), 4000.0)]
        return lines, orders, None, [2, 3, 1, 4]
    raise AssertionError(case)


@pytest.mark.parametrize("case", list(SEEDS) + [
    "boundary", "none_qualifies", "tied_on_price", "tie_at_the_limit"])
def test_device_path_against_the_reference(session, data, tmp_path, case):
    if case in SEEDS:
        path = data(SEEDS[case])
        want = _reference_rows(path)
        assert len(want) == 100
        assert all(isinstance(r[0], str) and isinstance(r[3], str)
                   and isinstance(r[5], float) and r[5] > 300
                   for r in want)
        assert [r[4] for r in want] == sorted((r[4] for r in want),
                                              reverse=True)
        got = _device_rows(session, path)
        assert rows_match(got, want)
        # the harness compares without order; the order is held here
        assert _in_order(r[:4] for r in got) == _in_order(
            r[:4] for r in want)
        # not vacuous: one order dropped for its neighbour is caught
        assert not rows_match(got[1:] + [got[1]], want)
    elif case == "tie_at_the_limit":
        # 101 qualifying orders, the 100th and 101st equal on price and
        # date: the text does not say which is kept, the reference
        # refuses the data
        lines = [ln for k in range(1, 102) for ln in _lines(301, k)]
        orders = [(k, 1, D(1995, 1, 1), 5000.0 + max(0, 99 - k))
                  for k in range(1, 102)]
        path = _write(str(tmp_path), lines, orders)
        with pytest.raises(AssertionError, match="tie"):
            _reference_rows(path)
    else:
        lines, orders, customers, keys = _edge_tables(case)
        path = _write(str(tmp_path), lines, orders, customers)
        want = _reference_rows(path)
        assert [r[2] for r in want] == keys
        got = _device_rows(session, path)
        assert len(got) == len(want) < 100
        # compared IN ORDER, floats at the harness's tolerance
        assert _in_order(r[:4] for r in got) == _in_order(
            r[:4] for r in want)
        assert all(rows_match([g], [w]) for g, w in zip(got, want))
        totals = {k: sum(q for kk, q in lines if kk == k) for k in keys}
        assert [r[5] for r in got] == [float(totals[k]) for k in keys]


# ---------------------------------------------------------- the record

def _record(df):
    df.collect()                                    # compiles
    df.collect()
    return get_registry().recent_queries(1)[0]["counters"]


def test_q18_record_follows_the_plan(session, data):
    import pandas as pd
    path = data(42)
    c = _record(_bench("queries", "tpch_q18").build(session, path))
    li = pd.read_parquet(os.path.join(path, "lineitem"),
                         columns=["l_orderkey", "l_quantity"])
    total = li.groupby("l_orderkey").l_quantity.sum()
    big = int((total > 300).sum())
    kept = int(li.l_orderkey.isin(total[total > 300].index).sum())
    n_orders = pq.read_metadata(
        os.path.join(path, "orders", "part-0.parquet")).num_rows
    n_cust = pq.read_metadata(
        os.path.join(path, "customer", "part-0.parquet")).num_rows
    # two updates, both over far more than 64 keys: the sort branch
    assert c["agg.update.sorted"] == 2 and "agg.update.dense" not in c
    assert c["agg.update.groups"] == len(total) + big
    assert c["agg.update.rows"] == len(li) + kept
    # one lineitem batch at this scale goes through the semi-join
    assert c["join.semi.batches"] == 1
    assert c["join.probe.direct"] == 3 and "join.probe.sorted" not in c
    assert "join.probe.search" not in c
    assert c["join.build.rows"] == big + n_orders + n_cust
    assert c["program.join_build_table.launches"] == 3
    assert c["sort.launches"] == 1
    assert c["limit.rows_out"] == min(big, 100) == 100
    # lineitem's two columns are staged once and handed to both readers
    assert c["scan.shared.handed_batches"] \
        == c["scan.shared.staged_batches"] + 1


def test_q1_record_leaves_the_q18_counters_alone(session, data):
    import pandas as pd
    path = data(42)
    c = _record(_bench("queries", "tpch_q1").build(session, path))
    assert c["agg.update.dense"] >= 1
    # q1 sorts its four groups, and the coalesce under its aggregate has
    # fetched the filter's row count already; nothing else of Q18's runs
    assert c["sort.launches"] == 1
    shipdate = pd.read_parquet(os.path.join(path, "lineitem"),
                               columns=["l_shipdate"]).l_shipdate
    assert c["agg.update.rows"] == int((shipdate <= D(1998, 9, 2)).sum())
    assert not [k for k in NEW if k.startswith(("join.", "limit."))
                and c.get(k, 0)]
