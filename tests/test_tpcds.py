"""TPC-DS slice: generator sanity + all queries verify vs host oracle.

Reference test pattern: tpcds_test.py wraps TpcdsLikeSpark queries as
assertions (integration_tests/src/main/python/tpcds_test.py).
"""
import os

import pytest

from spark_rapids_tpu.bench.tpcds_gen import generate_tpcds, table_row_counts
from spark_rapids_tpu.bench.runner import run_benchmark
from spark_rapids_tpu.bench.tpcds_queries import QUERIES


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("tpcds") / "sf001")
    generate_tpcds(d, sf=0.01)
    return d


def test_row_counts_scale():
    c1 = table_row_counts(1.0)
    c10 = table_row_counts(10.0)
    assert c1["store_sales"] == 2_880_000
    assert c10["store_sales"] == 28_800_000
    assert c1["date_dim"] == c10["date_dim"] == 73049
    assert c10["customer"] > c1["customer"]


def test_generator_is_deterministic(tmp_path):
    import pyarrow.parquet as pq
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    generate_tpcds(d1, sf=0.001, tables=["item"])
    generate_tpcds(d2, sf=0.001, tables=["item"])
    t1 = pq.read_table(os.path.join(d1, "item"))
    t2 = pq.read_table(os.path.join(d2, "item"))
    assert t1.equals(t2)


def test_date_dim_keys(data_dir):
    import pyarrow.parquet as pq
    dd = pq.read_table(os.path.join(data_dir, "date_dim"))
    rows = dd.to_pydict()
    i = rows["d_date_sk"].index(2450816)  # 1998-01-02 per dsdgen convention
    assert rows["d_year"][i] == 1998
    assert rows["d_moy"][i] == 1


# Default (tier-1) runs a representative cross-section of plan
# shapes; TPCDS_FULL=1 sweeps all 99 (the nightly tier — the committed
# artifact artifacts/tpcds_99_sf001_verify.txt records a full pass).
# Mirrors the reference's per-change-vs-nightly split (jenkins/).
_SMOKE = ["q1", "q6", "q14", "q23", "q36", "q47", "q49", "q51", "q64",
          "q67", "q72", "q77", "q87", "q95"]
_SUITE = sorted(QUERIES) if os.environ.get("TPCDS_FULL") == "1" else _SMOKE


@pytest.mark.parametrize("query", _SUITE)
def test_query_device_matches_oracle(data_dir, query):
    reports = run_benchmark(data_dir, 0.01, [query], verify=True,
                            generate=False)
    r = reports[0]
    assert "error" not in r, r
    assert r["ok"], r


def test_all_99_queries_registered():
    assert len(QUERIES) == 99
    assert all(f"q{i}" in QUERIES for i in range(1, 100))


def test_q6_returns_states_at_larger_sf(tmp_path):
    d = str(tmp_path / "sf01")
    generate_tpcds(d, sf=0.1)
    reports = run_benchmark(d, 0.1, ["q6"], verify=True, generate=False)
    r = reports[0]
    assert r["ok"], r
    assert r["rows"] > 0, "q6 should produce state groups at SF0.1"
