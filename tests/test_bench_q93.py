"""TPC-DS q93 as the benchmark runs it (benchmark/queries/tpcds_q93.py
and tpcds_q93_all.py, their references, benchmark/datagen/tpcds.py,
loaded by path as the harness does) on XLA:CPU: the engine's device path
against the plain reference at SF0.01 and SF0.1, the query with its
limit and with the limit lifted (the cell collects both in turn), on
hand-made edge cases, and what the two-key join leaves in the per-query
record (keys packed, batches searched, none sorted)."""
import os

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark.harness.cell import ROOT, load_module
from benchmark.harness.compare import rows_match
from spark_rapids_tpu import TpuSession
from spark_rapids_tpu.obs.registry import get_registry

CASES = {"sf001_seed_42": (0.01, 42), "sf01_seed_7": (0.1, 7),
         "sf01_seed_2p31": (0.1, 2**31 + 331)}
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
def data(tmp_path_factory):
    """``data(sf, seed)``: that seed's q93 tables, generated once."""
    base = tmp_path_factory.mktemp("q93")

    def of(sf: float, seed: int) -> str:
        path = str(base / f"sf{sf:g}_seed{seed}")
        _bench("datagen", "tpcds").generate(
            path, sf, seed, sorted(_bench("queries", "tpcds_q93").TABLES))
        return path
    return of


def _limited(session, data_dir):
    return _bench("queries", "tpcds_q93").build(session, data_dir)


def _unlimited(session, data_dir):
    """q93 with its limit lifted, the cell's second query: every group."""
    return _bench("queries", "tpcds_q93_all").build(session, data_dir)


def _in_order(rows):
    return [tuple(str(x) for x in r) for r in rows]


@pytest.mark.parametrize("case", list(CASES))
def test_device_path_against_the_reference(session, data, case):
    sf, seed = CASES[case]
    path = data(sf, seed)
    ref = _bench("reference", "tpcds_q93")
    want = ref.rows(path)
    every = ref.as_rows(ref.aggregate(path))
    assert want == every[:100] and len(every) > (50 if sf < 0.1 else 700)
    assert any(c is None for c, _ in every)          # the NULL customer
    assert [s for _, s in want] == sorted(s for _, s in want)
    got = _limited(session, path).collect()
    assert rows_match(got, want)
    # the harness compares without order; the order is held here
    assert _in_order(r[:1] for r in got) == _in_order(r[:1] for r in want)
    # not vacuous: one customer dropped for the next is caught
    if len(every) > len(got):
        assert not rows_match(got[1:] + [every[len(got)]], want)
    # every group, in the text's order
    assert _bench("reference", "tpcds_q93_all").rows(path) == every
    all_got = _unlimited(session, path).collect()
    assert rows_match(all_got, every)
    assert _in_order(r[:1] for r in all_got) == _in_order(r[:1] for r in every)
    # what the hundred rows cannot show and these do: a match the join
    # missed (one returned sale's customer gone), a sum without its price
    lost = next(i for i, (_, s) in enumerate(every) if s)
    assert not rows_match(all_got[:lost] + all_got[lost + 1:], every)
    assert not rows_match(
        [(c, s and s * (1 + 1e-4)) if i == lost else (c, s)
         for i, (c, s) in enumerate(all_got)], every)


def test_q93_record_follows_the_plan(session, data):
    import pandas as pd
    path = data(0.1, 7)
    df = _limited(session, path)
    df.collect()                                    # compiles
    df.collect()
    c = get_registry().recent_queries(1)[0]["counters"]
    sr = pd.read_parquet(os.path.join(path, "store_returns"),
                         columns=["sr_item_sk", "sr_ticket_number"])
    n_ss = pq.read_metadata(os.path.join(
        path, "store_sales", "part-00000.parquet")).num_rows
    assert c["join.keys.packed"] == 1 and "join.keys.unpackable" not in c
    assert "join.probe.sorted" not in c
    assert c["join.probe.search"] == 1      # one stream batch at SF0.1
    assert c["join.probe.direct"] == 1      # the one-row reason semi-join
    assert c["join.semi.batches"] == 1
    # the returns (every key there) and the one kept reason
    assert c["join.build.rows"] == len(sr.dropna()) + 1
    assert c["program.join_build_prep.launches"] == 2
    assert c["program.join_probe_fast.launches"] == 1
    assert "program.join_probe.launches" not in c
    # the left join hands on its whole stream; the semi-join 1 in 35 of
    # the returned sales
    left_out = c["join.probe.rows_out"] - c["agg.update.rows"]
    assert n_ss <= left_out <= n_ss + 10
    # (item, ticket) is unique: every sale comes out once and the left
    # join's batch keeps its own columns in place (PR 43); the
    # semi-join's batch drops rows and takes the expanding plan
    assert left_out == n_ss and c["join.gather.aligned"] == 1
    assert 0 < c["agg.update.rows"] < len(sr) / 20
    assert c["limit.rows_out"] == 100
    # one fetch a build, one a flush of each join
    assert c["span.fetch@JoinExec.count"] == 4


def test_q93_search_batches_take_the_merge(session, data):
    """Every stream batch q93 searches has the shapes of the merge (a
    2^19-slot batch against 2^15 build slots at SF0.1; 2^20 against 2^22
    at SF10): ``join.probe.search.merged`` moves with
    ``join.probe.search``, under the program's one name."""
    from spark_rapids_tpu.ops.join import probe_merges
    df = _limited(session, data(0.1, 7))
    df.collect()
    c = get_registry().recent_queries(1)[0]["counters"]
    assert c["join.probe.search.merged"] == c["join.probe.search"] == 1
    assert c["program.join_probe_fast.launches"] == 1
    assert probe_merges(1 << 19, 1 << 15) and probe_merges(1 << 20, 1 << 22)


# ------------------------------------------------------ hand-made cases

def _write(path, sales, returns, reasons=((28, "reason 28"),
                                          (29, "reason 29"))):
    """Hand-made q93 tables: ``sales`` = (item, ticket, customer,
    quantity, price), ``returns`` = (item, ticket, reason, quantity)."""
    def column(rows, i, kind):
        return pa.array([r[i] for r in rows], kind)
    tables = {
        "store_sales": pa.table({
            "ss_item_sk": column(sales, 0, pa.int32()),
            "ss_ticket_number": column(sales, 1, pa.int64()),
            "ss_customer_sk": column(sales, 2, pa.int32()),
            "ss_quantity": column(sales, 3, pa.int32()),
            "ss_sales_price": column(sales, 4, pa.float64())}),
        "store_returns": pa.table({
            "sr_item_sk": column(returns, 0, pa.int32()),
            "sr_ticket_number": column(returns, 1, pa.int64()),
            "sr_reason_sk": column(returns, 2, pa.int32()),
            "sr_return_quantity": column(returns, 3, pa.int32())}),
        "reason": pa.table({
            "r_reason_sk": column(reasons, 0, pa.int32()),
            "r_reason_desc": column(reasons, 1, pa.string())}),
    }
    for name, table in tables.items():
        os.makedirs(os.path.join(path, name))
        pq.write_table(table, os.path.join(path, name, "part-0.parquet"))
    return path


T40 = 1 << 40       # a ticket number past 32 bits


def test_null_rules_and_the_two_keys(session, tmp_path):
    sales = [
        (1, 10, 7, 5, 2.50),        # returned in full for reason 28: 0.0
        (1, 11, 7, 3, 1.00),        # same item, other ticket: not returned
        (2, 10, 8, 4, 10.00),       # returned 1 of 4: 30.0
        (2, T40, 8, 2, 0.25),       # returned twice: 2 rows, 0.25 + 0.0
        (3, 10, None, 6, 1.50),     # NULL customer, quantity NULL: 9.0
        (3, 11, None, 1, 4.00),     # NULL customer, returned 1: 0.0
        (4, 10, 9, 9, 3.00),        # returned for another reason
        (4, 11, 9, 9, 3.00),        # returned, reason NULL
        (None, 12, 5, 1, 1.00),     # NULL item: matches nothing
        (5, None, 5, 1, 1.00),      # NULL ticket: matches nothing
        (6, 13, 6, None, 2.00),     # quantity NULL: the sum is NULL
        (7, 14, 4, 2, None),        # price NULL, and a real row beside it
        (7, 15, 4, 3, 1.25),        # returned 1: 2.5
        (8, 16, 3, 100, 300.00),    # ticket in range, item matches wrongly
    ]
    returns = [
        (1, 10, 28, 5), (2, 10, 28, 1), (2, T40, 28, 1), (2, T40, 28, 2),
        (3, 10, 28, None), (3, 11, 28, 1), (4, 10, 29, 1), (4, 11, None, 1),
        (None, 12, 28, 1), (5, None, 28, 1), (6, 13, 28, 1), (7, 14, 28, 1),
        (7, 15, 28, 1),
        (9, 16, 28, 1), (8, 17, 28, 1),     # a key of each, the pair of none
    ]
    path = _write(str(tmp_path), sales, returns)
    want = _bench("reference", "tpcds_q93").rows(path)
    assert want == [(6, None), (7, 0.0), (4, 2.5), (None, 9.0), (8, 30.25)]
    got = _limited(session, path).collect()
    assert _in_order(got) == _in_order(want)
    c = get_registry().recent_queries(1)[0]["counters"]
    assert c["join.keys.packed"] == 1 and "join.probe.sorted" not in c
    # 14 sales, one of them returned twice
    assert c["join.probe.rows_out"] - c["agg.update.rows"] == 15


def test_reference_refuses_a_near_tie_at_the_limit(tmp_path):
    # 101 customers with one fully priced sale each; the 100th and the
    # 101st differ by a cent in eleven million dollars: under 1e-9
    def sale(k, cents):
        return [(k, 1000 * k + i, k, 100, 300.0) for i in range(366)] + \
            [(k, 1000 * k + 999, k, 1, cents / 100.0)]
    sales, returns = [], []
    for k in range(1, 102):
        rows = sale(k, 100 * k if k < 100 else 2000_00 + (k - 100))
        sales += rows
        returns += [(it, tk, 28, None) for it, tk, *_ in rows]
    path = _write(str(tmp_path / "near"), sales, returns)
    ref = _bench("reference", "tpcds_q93")
    assert len(ref.aggregate(path)) == 101
    with pytest.raises(AssertionError, match="near tie"):
        ref.rows(path)
    # a whole dollar apart is no tie
    sales[-1] = sales[-1][:4] + (2100.0,)
    path = _write(str(tmp_path / "apart"), sales, returns)
    assert len(ref.rows(path)) == 100
