"""OOM chaos suite: TPC-H under a deterministic HBM-exhaustion storm.

The ``memory.oom.until_rows`` fault point makes every retry-scoped
dispatch above the row threshold fail exactly like an XLA
RESOURCE_EXHAUSTED, so split-and-retry (memory/retry.py) must halve
batches until they fit — queries still return EXACT oracle results,
with nonzero split counts in the BufferCatalog metrics.  Reference
intent: the plugin's retry framework keeps queries correct under
memory pressure (RmmRapidsRetryIterator + the *_retry suites); here the
pressure is seeded and conf-driven, CPU-only, no mocks.

The sync-point tests cover the async-dispatch gap: with
``_SYNC_DISPATCH`` off (tpu behavior) an OOM surfaces at the
chunk-flush ``device_get`` in aggregate/join — ``retry_sync`` must
spill, redo the poisoned dispatches from retained inputs, and sync
again instead of propagating.
"""
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.bench.runner import run_benchmark
from spark_rapids_tpu.bench.tpch_gen import generate_tpch

# storm threshold: any dispatch above this row count OOMs.  TPC-H
# sf0.01 lineitem is ~60k rows per scan batch, so hot operators must
# split 2+ levels before work fits.  The 32-row minSplitRows default
# floor is far below the threshold, so splits always converge.
_STORM = "memory.oom.until_rows:oom,until_rows=16384"
_CHAOS_CONF = {
    "spark.rapids.test.faults": _STORM,
    # small host arena: chaos catalogs spill often and a 1GB mapping
    # per query is pure setup cost here
    "spark.rapids.memory.host.spillStorageSize": 64 << 20,
}

_QUERIES = ["q1", "q3", "q6", "q12", "q18"]


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("tpch_chaos") / "sf001")
    generate_tpch(d, sf=0.01)
    return d


@pytest.mark.parametrize("query", _QUERIES)
def test_tpch_exact_under_oom_storm(data_dir, query):
    r = run_benchmark(data_dir, 0.01, [query], verify=True,
                      generate=False, suite="tpch",
                      session_conf=_CHAOS_CONF)[0]
    assert "error" not in r, r
    assert r["ok"], r
    cat = r["metrics"].get("BufferCatalog", {})
    # the storm must actually have forced split-and-retry
    assert cat.get("oom_splits", 0) > 0, cat
    assert cat.get("oom_retries", 0) >= cat["oom_splits"], cat
    assert cat.get("device_bytes_peak", 0) > 0, cat


def test_storm_inert_with_retry_disabled(data_dir):
    """Control: with oomRetry.enabled=false the legacy spill hook has
    no row context, so until_rows rules cannot fire there BY DESIGN
    (plain ctx.dispatch inside retry scopes must not storm).  The
    query runs clean with zero splits — proving the splits above are
    the retry framework's, not ambient fault noise."""
    conf = dict(_CHAOS_CONF)
    conf["spark.rapids.memory.tpu.oomRetry.enabled"] = "false"
    r = run_benchmark(data_dir, 0.01, ["q6"], verify=True,
                      generate=False, suite="tpch",
                      session_conf=conf)[0]
    assert "error" not in r and r["ok"], r
    cat = r["metrics"].get("BufferCatalog", {})
    assert cat.get("oom_splits", 0) == 0, cat


# ---------------------------------------------------------------------------
# async sync-point recovery (_SYNC_DISPATCH gap)
# ---------------------------------------------------------------------------

@pytest.fixture
def async_dispatch(monkeypatch):
    """Force the async-dispatch mode (tpu behavior on CPU): OOMs
    surface at sync points, not at dispatch."""
    from spark_rapids_tpu.memory import catalog as cat_mod
    monkeypatch.setattr(cat_mod, "_SYNC_DISPATCH", False)
    yield
    # monkeypatch restores the cached value on teardown


def _session(faults: str):
    from spark_rapids_tpu.session import TpuSession
    return TpuSession({"spark.rapids.test.faults": faults})


def _oracle(df):
    from spark_rapids_tpu.exec.core import collect_host
    ov, meta = df._overridden(quiet=True)
    return sorted(collect_host(meta.exec_node, df._s.conf))


@pytest.mark.parametrize("op", ["agg_flush", "join_flush"])
def test_sync_point_oom_recovered(async_dispatch, op):
    """An OOM injected at the aggregate/join chunk-flush sync point is
    recovered by retry_sync (spill + redo + re-sync), not propagated
    (the pre-retry engine died here on async backends).  The run drives
    an explicit ExecCtx so the fault's fired count is checkable — a
    vacuous pass (injection site never reached) fails the test."""
    from spark_rapids_tpu.exec.core import (ExecCtx, _rows_from_host,
                                            device_to_host)
    from spark_rapids_tpu.expr.aggregates import CountStar, Sum
    from spark_rapids_tpu.expr.core import col

    s = _session(f"memory.oom:oom,op={op},times=1")
    schema = T.Schema([
        T.StructField("k", T.IntegerType(), True),
        T.StructField("v", T.LongType(), True),
    ])
    data = {"k": [i % 13 for i in range(500)],
            "v": list(range(500))}
    left = s.from_pydict(data, schema, partitions=2)
    if op == "agg_flush":
        df = left.group_by("k").agg(Sum(col("v")), CountStar())
    else:
        rschema = T.Schema([
            T.StructField("k", T.IntegerType(), True),
            T.StructField("w", T.LongType(), True),
        ])
        right = s.from_pydict(
            {"k": list(range(13)), "w": [i * 10 for i in range(13)]},
            rschema)
        df = left.join(right, on="k").group_by("k").agg(Sum(col("w")))
    ov, meta = df._overridden(quiet=True)
    with ExecCtx(backend="device", conf=s.conf) as ctx:
        rows = []
        for b in meta.exec_node.execute(ctx):
            rows.extend(_rows_from_host(device_to_host(b)))
        fired = ctx.catalog.faults.fired_count()
        retries = ctx.catalog.metrics["oom_retries"]
    assert sorted(rows) == _oracle(df)
    assert fired == 1 and retries == 1, (fired, retries)


def test_sync_point_fault_fires(async_dispatch):
    """The injected flush-point fault is consumed (fired), proving the
    recovery above exercised the redo path rather than never hitting
    the injection site."""
    from spark_rapids_tpu.exec.core import ExecCtx
    from spark_rapids_tpu.conf import TpuConf

    conf = TpuConf({"spark.rapids.test.faults":
                    "memory.oom:oom,op=agg_flush,times=1"})
    with ExecCtx(backend="device", conf=conf) as ctx:
        redone = []
        out = ctx.retry_sync(lambda: 41, redo=lambda: redone.append(1),
                             op="agg_flush")
        assert out == 41 and redone == [1]
        assert ctx.catalog.faults.fired_count() == 1
        assert ctx.catalog.metrics["oom_retries"] == 1
