"""The three plain references (pandas, written from the TPC query
texts) against the engine's host oracle, once, at SF0.1 on the CPU: two
independent routes to the same rows."""
import pytest

from benchmark.harness.cell import ROOT, load_module
from benchmark.harness.compare import rows_match


@pytest.fixture(scope="module")
def session():
    from spark_rapids_tpu import TpuSession
    s = TpuSession({"spark.rapids.sql.resultCache.enabled": "false"})
    yield s
    s.shutdown(drain=False)


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    base = tmp_path_factory.mktemp("sf0.1")
    dirs = {}
    for suite in ("tpcds", "tpch"):
        dirs[suite] = str(base / suite)
    return dirs


@pytest.mark.parametrize("suite,q,n_rows", [
    ("tpcds", "q6", None), ("tpch", "q1", 4), ("tpch", "q6", 1)])
def test_reference_equals_host_oracle(session, data, suite, q, n_rows):
    from spark_rapids_tpu.bench.runner import _collect_rows
    query = load_module(ROOT, "queries", f"{suite}_{q}")
    load_module(ROOT, "datagen", suite).generate(
        data[suite], 0.1, 42, sorted(query.TABLES))
    want = load_module(ROOT, "reference", f"{suite}_{q}").rows(data[suite])
    got = _collect_rows(query.build(session, data[suite]), "host")
    assert len(want) > 0 and (n_rows is None or len(want) == n_rows)
    assert rows_match(got, want)
    # and the comparison is not vacuous: a changed cell is caught
    broken = [tuple(want[0][:-1]) + (want[0][-1] * 1.001,)] + want[1:]
    assert not rows_match(got, broken)
