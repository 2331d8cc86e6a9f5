"""TPC-DS q44 as the benchmark runs it (benchmark/queries/tpcds_q44.py,
benchmark/reference/tpcds_q44.py, benchmark/datagen/tpcds.py, loaded by
path as the harness does) at SF0.1 on XLA:CPU: the engine's device path
against the plain reference, and the counters the query's new
mechanisms leave in the per-query record (shared scan replay, sort
branch of the aggregate, cross join, global window)."""
import os
import shutil

import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from benchmark.harness.cell import ROOT, load_module
from benchmark.harness.compare import rows_match
from spark_rapids_tpu import TpuSession
from spark_rapids_tpu.exec.core import ExecCtx
from spark_rapids_tpu.obs.registry import get_registry

SF = 0.1
SEEDS = {"seed_42": 42, "seed_7": 7, "seed_2p31": 2**31 + 5}
CONF = {"spark.rapids.sql.resultCache.enabled": "false",
        "spark.rapids.sql.test.enabled": "true"}
Q6_DIMS = ["date_dim", "item", "customer", "customer_address"]


def _bench(kind, name):
    return load_module(ROOT, kind, name)


@pytest.fixture(scope="module")
def session():
    s = TpuSession(dict(CONF))
    yield s
    s.shutdown(drain=False)


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    """``data(seed)``: that seed's q44 and q6 tables, generated once."""
    base = tmp_path_factory.mktemp("q44")
    tables = sorted(set(_bench("queries", "tpcds_q44").TABLES)
                    | set(_bench("queries", "tpcds_q6").TABLES))

    def of(seed: int) -> str:
        path = str(base / f"seed{seed}")
        _bench("datagen", "tpcds").generate(path, SF, seed, tables)
        return path
    return of


def _rewrite_store_sales(src: str, dst: str, change) -> str:
    """``src``'s q44 tables under ``dst``, store_sales put through
    ``change(table) -> table``."""
    shutil.copytree(os.path.join(src, "item"), os.path.join(dst, "item"))
    os.makedirs(os.path.join(dst, "store_sales"))
    cols = _bench("queries", "tpcds_q44").TABLES["store_sales"]
    t = pq.read_table(os.path.join(src, "store_sales"), columns=cols)
    pq.write_table(change(t), os.path.join(dst, "store_sales",
                                           "part-00000.parquet"))
    return dst


def _device_rows(session, data_dir):
    return _bench("queries", "tpcds_q44").build(session, data_dir).collect()


def _reference_rows(data_dir):
    return _bench("reference", "tpcds_q44").rows(data_dir)


@pytest.mark.parametrize("case", list(SEEDS) + [
    "swapped_name", "no_null_address", "near_tie", "exact_tie"])
def test_device_path_against_the_reference(session, data, tmp_path, case):
    if case in SEEDS:
        path = data(SEEDS[case])
        want = _reference_rows(path)
        assert len(want) == 10 and [r[0] for r in want] == list(range(1, 11))
        assert all(isinstance(b, str) and isinstance(w, str)
                   for _, b, w in want)
        assert rows_match(_device_rows(session, path), want)
    elif case == "swapped_name":
        # the comparison is not vacuous: two names changing places (what
        # a slip in the order of two averages would do) is caught
        path = data(42)
        want = _reference_rows(path)
        broken = [(want[0][0], want[1][1], want[0][2]),
                  (want[1][0], want[0][1], want[1][2])] + want[2:]
        got = _device_rows(session, path)
        assert rows_match(got, want) and not rows_match(got, broken)
    elif case == "no_null_address":
        # an empty subquery is NULL and the having keeps nothing: the
        # package's Coalesce(_base, 0.0) would answer ten rows here
        def fill(t):
            i = t.schema.get_field_index("ss_addr_sk")
            return t.set_column(i, "ss_addr_sk", pc.fill_null(
                t.column("ss_addr_sk"), pa.scalar(1, pa.int32())))
        path = _rewrite_store_sales(data(42), str(tmp_path), fill)
        assert _reference_rows(path) == []
        assert _device_rows(session, path) == []
    elif case == "exact_tie":
        # averages that are equal as numbers and unequal as sums of
        # doubles, (0.1 + 0.2) / 2 against 0.15 / 1, among the smallest
        # and among the largest: SQL gives each pair one rank, the join
        # on the rank pairs both, and the rank after them is skipped
        def ties(t):
            profit = {1: [0.0], 2: [0.15], 3: [0.1, 0.2],
                      4: [0.07, 0.11, 0.27],
                      41: [1000.15], 42: [1000.07, 1000.23]}
            profit.update({k: [float(k)] for k in range(5, 41)})
            rows = [(k, p) for k, ps in profit.items() for p in ps]
            return pa.table({
                "ss_item_sk": pa.array([k for k, _ in rows], pa.int32()),
                "ss_store_sk": pa.array([4] * len(rows), pa.int32()),
                "ss_addr_sk": pa.array(
                    [None] + [1] * (len(rows) - 1), pa.int32()),
                "ss_net_profit": pa.array([p for _, p in rows],
                                          pa.float64())})
        assert (0.1 + 0.2) / 2 != 0.15 and (1000.07 + 1000.23) / 2 != 1000.15
        path = _rewrite_store_sales(data(42), str(tmp_path), ties)
        want = _reference_rows(path)
        # ascending 1, 1, 1, 4..10 against descending 1, 1, 3..10
        assert [r[0] for r in want] == [1] * 6 + list(range(4, 11))
        assert rows_match(_device_rows(session, path), want)
    else:
        # two of the largest averages within 1e-12 relative: the
        # reference refuses the data instead of tossing a coin
        def tie(t):
            n = 40
            profit = [float(k) for k in range(n)]
            profit[-1] = profit[-2] * (1 + 1e-12)
            return pa.table({
                "ss_item_sk": pa.array(range(1, n + 1), pa.int32()),
                "ss_store_sk": pa.array([4] * n, pa.int32()),
                "ss_addr_sk": pa.array([None] + [1] * (n - 1), pa.int32()),
                "ss_net_profit": pa.array(profit, pa.float64())})
        path = _rewrite_store_sales(data(42), str(tmp_path), tie)
        with pytest.raises(AssertionError, match="near-tie"):
            _reference_rows(path)


# ---------------------------------------------------------- the record

def _collect_record(df, monkeypatch):
    """One warm collect: its record's counters, and what the execution
    context still held of shared scans when it closed."""
    left = []
    close = ExecCtx.close

    def spy(self):
        with self._lock:
            left.extend(k for k in self.cache if isinstance(k, tuple)
                        and k and k[0] == "scan_share")
            catalog = self.cache.get("catalog")
        if catalog is not None:
            left.extend((tier, t["buffers"]) for tier, t in
                        catalog.tier_occupancy().items()
                        if tier != "_totals")
        close(self)
    monkeypatch.setattr(ExecCtx, "close", spy)
    df.collect()
    return get_registry().recent_queries(1)[0]["counters"], left


def _batches(data_dir, table):
    """Device batches a scan of ``table`` stages at this scale: one a
    file (every file is under the reader's batch size)."""
    return len([f for f in os.listdir(os.path.join(data_dir, table))
                if f.endswith(".parquet")])


def test_q44_record_carries_the_new_counters(session, data, monkeypatch):
    path = data(42)
    df = _bench("queries", "tpcds_q44").build(session, path)
    df.collect()                                    # compiles
    c, left = _collect_record(df, monkeypatch)
    sales, item = _batches(path, "store_sales"), _batches(path, "item")
    # four logical scans of store_sales and two of item: each staged
    # once and handed to all four and to both, so replayed to the other
    # three and the other one
    assert c["scan.shared.staged_batches"] == sales + item
    assert c["scan.shared.handed_batches"] == 4 * sales + 2 * item
    assert c["scan.shared.parked_bytes"] > 0
    assert c["span.stage@ParquetScanExec.count"] == sales + item
    assert c["agg.update.sorted"] >= 1 and c["agg.update.groups"] > 64
    assert c["window.global"] == 2 and c["window.batches_in"] >= 2
    assert c["join.cross.launches"] >= 2
    # closed on the last consumer: nothing parked outlives the collect
    assert left == []


def test_q6_record_leaves_the_q44_counters_alone(session, data, monkeypatch):
    path = data(42)
    df = _bench("queries", "tpcds_q6").build(session, path)
    df.collect()
    c, left = _collect_record(df, monkeypatch)
    assert not [k for k in c if k.startswith(("window.", "join.cross."))]
    # q6 shares its item scan between two consumers: one batch staged,
    # the same batch handed to both
    assert c["scan.shared.staged_batches"] == _batches(path, "item")
    assert c["scan.shared.handed_batches"] == 2 * _batches(path, "item")
    # each of q6's four streaming joins is on a surrogate key: every
    # stream batch is probed by address, none by search
    assert c["join.probe.direct"] \
        == c["program.join_probe_direct.launches"] >= 4
    assert c["program.join_build_table.launches"] == 4
    assert "join.probe.search" not in c
    assert left == []
