"""TPCx-BB suite: the 19 reference-runnable BigBench queries verify vs
the host oracle; the 11 the reference refuses raise the same reasons
(reference TpcxbbLikeSpark.scala:808-2130)."""
import os

import pytest

from spark_rapids_tpu.bench.runner import run_benchmark
from spark_rapids_tpu.bench.tpcxbb_gen import generate_tpcxbb
from spark_rapids_tpu.bench.tpcxbb_queries import (TPCXBB_QUERIES,
                                                   UNSUPPORTED,
                                                   build_tpcxbb_query)


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("tpcxbb") / "sf001")
    generate_tpcxbb(d, sf=0.01)
    return d


def test_query_registry_matches_reference():
    assert len(TPCXBB_QUERIES) == 19
    assert len(UNSUPPORTED) == 11
    assert set(TPCXBB_QUERIES) | set(UNSUPPORTED) == {
        f"q{i}" for i in range(1, 31)}


def test_unsupported_refused_like_reference():
    with pytest.raises(NotImplementedError, match="UDTF"):
        build_tpcxbb_query("q1", None, "")
    with pytest.raises(NotImplementedError, match="python"):
        build_tpcxbb_query("q3", None, "")
    with pytest.raises(NotImplementedError, match="UDF"):
        build_tpcxbb_query("q10", None, "")


# default (tier-1) smoke runs the cross-section with non-empty
# results at SF0.01; TPCXBB_FULL=1 sweeps all 19
_SMOKE = ["q5", "q6", "q11", "q12", "q14", "q24", "q25", "q28"]
_SUITE = sorted(set(TPCXBB_QUERIES) - {"q20"}) \
    if os.environ.get("TPCXBB_FULL") == "1" else _SMOKE


@pytest.mark.parametrize("query", _SUITE)
def test_query_device_matches_oracle(data_dir, query):
    r = run_benchmark(data_dir, 0.01, [query], verify=True,
                      generate=False, suite="tpcxbb")[0]
    assert "error" not in r, r
    assert r["ok"], r


def test_smoke_queries_return_rows(data_dir):
    """The smoke subset must produce data at SF0.01 — a 0-row
    verification verifies nothing (round-2 verdict's q6 lesson)."""
    from spark_rapids_tpu.session import TpuSession
    for name in ("q5", "q6", "q12", "q20", "q25", "q28"):
        s = TpuSession({})
        assert len(TPCXBB_QUERIES[name](s, data_dir).collect()) > 0, name


def test_q20_device_matches_oracle_with_round_tolerance(data_dir):
    """q20's ratios are money quotients rounded HALF_UP at 7 decimals:
    values land EXACTLY on the rounding boundary, and 1-ulp summation-
    order noise between the device and the oracle legally flips the
    7th decimal — so q20 verifies with a one-unit-in-the-7th-decimal
    tolerance instead of the runner's 6-significant-digit normalizer."""
    import math
    from spark_rapids_tpu.exec.core import collect_host
    from spark_rapids_tpu.session import TpuSession

    s = TpuSession({})
    q = TPCXBB_QUERIES["q20"](s, data_dir)
    dev = sorted(q.collect())
    ov, meta = q._overridden(quiet=True)
    host = sorted(collect_host(meta.exec_node, s.conf))
    assert len(dev) == len(host) > 0
    for a, b in zip(dev, host):
        assert a[0] == b[0]
        for x, y in zip(a[1:], b[1:]):
            if x is None or y is None:
                assert x == y
            elif isinstance(x, float):
                assert math.isclose(x, y, rel_tol=0, abs_tol=1.01e-7), \
                    (a, b)
            else:
                assert x == y
