"""TPC-H Q13 as the benchmark runs it (benchmark/queries/tpch_q13.py,
its reference, benchmark/datagen/tpch_orders.py, loaded by path as the
harness does) on XLA:CPU: the engine's device path against the plain
reference at SF0.01 on three seeds, every row equal; hand-made edge
cases (a customer without orders counts 0, a NULL comment, a NULL key);
the package's own q13 through the same device path; and what the
collect leaves in the per-query record."""
import os

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark.harness.cell import ROOT, load_module
from spark_rapids_tpu import TpuSession
from spark_rapids_tpu.obs.registry import get_registry

SEEDS = [42, 7, 2**31 + 331]
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
    """``data(seed)``: that seed's Q13 tables at SF0.01, generated once."""
    base = tmp_path_factory.mktemp("q13")

    def of(seed: int) -> str:
        path = str(base / f"seed{seed}")
        _bench("datagen", "tpch_orders").generate(
            path, 0.01, seed, sorted(_bench("queries", "tpch_q13").TABLES))
        return path
    return of


def _collect(session, data_dir):
    df = _bench("queries", "tpch_q13").build(session, data_dir)
    return df, [tuple(r) for r in df.collect()]


@pytest.mark.parametrize("seed", SEEDS)
def test_device_path_against_the_reference(session, data, seed):
    path = data(seed)
    want = _bench("reference", "tpch_q13").rows(path)
    df, rows = _collect(session, path)
    # the whole plan on the device: test mode would have raised, and the
    # rendering marks every node
    assert all(ln.lstrip().startswith("*")
               for ln in df.explain().splitlines())
    assert rows == want
    assert len(rows) > 20 and rows[0][0] == 0     # the zero-order third
    assert sum(d for _, d in rows) == 1500        # every customer once
    record = get_registry().recent_queries(1)[0]["counters"]
    # the predicate met every scanned order on the device, and the
    # orders it kept are the join's build
    assert record["like.device.rows"] == 15_000
    assert record["like.device.bytes"] == 15_000 * 128
    assert record["scan.stage.string_bytes"] == (1 << 14) * 128
    kept = record["join.build.rows"]
    assert 14_700 < kept < 14_950
    assert record["join.probe.rows_out"] == kept + rows[0][1]
    assert record["program.string_match_stage.launches"] == 1
    assert "program.fused_stage_body.launches" not in record


def test_the_packages_q13_takes_the_same_device_path(session, data):
    from spark_rapids_tpu.bench.tpch_queries import q13
    path = data(SEEDS[0])
    df = q13(session, path)
    assert all(ln.lstrip().startswith("*")
               for ln in df.explain().splitlines())
    assert [tuple(r) for r in df.collect()] \
        == _bench("reference", "tpch_q13").rows(path)


def _write(path, table, **columns):
    os.makedirs(os.path.join(path, table))
    pq.write_table(pa.table(columns),
                   os.path.join(path, table, "part-0.parquet"))


def test_a_customer_without_orders_counts_zero(session, tmp_path):
    """Customers 1..6; 1 has two kept orders and one excluded, 2 has
    only an excluded one, 3 one whose comment is NULL (``not like`` of
    NULL is NULL: not kept), 4 one kept order with a NULL key (joined,
    not counted), 5 and 6 none; an order of no customer and one with a
    NULL customer key join nothing."""
    path = str(tmp_path)
    _write(path, "customer",
           c_custkey=pa.array([1, 2, 3, 4, 5, 6], pa.int32()))
    _write(path, "orders",
           o_orderkey=pa.array([10, 11, 12, 13, 14, None, 16, 17],
                               pa.int32()),
           o_custkey=pa.array([1, 1, 1, 2, 3, 4, 99, None], pa.int32()),
           o_comment=pa.array([
               "quickly final packages", "requests are special",
               "special pending requests", "specialrequests", None,
               "even deposits", "nobody's order", "no customer key"]))
    want = _bench("reference", "tpch_q13").rows(path)
    assert want == [(0, 5), (2, 1)]
    _, rows = _collect(session, path)
    assert rows == want


def test_a_lone_filter_that_matches_strings_runs_under_its_own_name(
        session, data):
    """Fused with a projection the match is ``string_match_stage``
    (above); alone it is ``string_match_filter``, and a filter that
    matches no string keeps ``filter_batch``."""
    from spark_rapids_tpu.expr.core import col, lit
    path = os.path.join(data(SEEDS[0]), "orders")
    cols = ["o_orderkey", "o_comment"]
    reg = get_registry()
    want = pq.read_table(path, columns=cols).to_pandas()
    rows = session.read_parquet(path, columns=cols) \
        .where(col("o_comment").like("%special%requests%")).collect()
    hit = want[want.o_comment.str.contains("special.*requests")]
    assert sorted(r[0] for r in rows) == sorted(hit.o_orderkey)
    record = reg.recent_queries(1)[0]["counters"]
    assert record["program.string_match_filter.launches"] == 1
    assert record["like.device.rows"] == 15_000
    assert "program.filter_batch.launches" not in record
    rows = session.read_parquet(path, columns=cols) \
        .where(col("o_orderkey") < lit(10)).collect()
    record = reg.recent_queries(1)[0]["counters"]
    assert len(rows) == 9 and record["program.filter_batch.launches"] == 1
    assert "like.device.rows" not in record
