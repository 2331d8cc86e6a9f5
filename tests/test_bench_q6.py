"""TPC-H Q6 and ``q6_bounds`` as the benchmark runs them
(benchmark/queries/tpch_q6.py, tpch_q6_bounds.py, their references and
benchmark/datagen/tpch_lineitem.py, loaded by path as the harness does)
on XLA:CPU at SF0.01; the generator's promises; and a float64 column
whose batches travel differently (one scaled, one raw) grouping and
joining as one key set.  What the chip makes of the same doubles is
tests/test_wirecodec.py's and tests/test_cents.py's, under the pair
emulator."""
import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark.harness.cell import ROOT, load_module
from benchmark.harness.compare import rows_match
from spark_rapids_tpu import TpuSession
from spark_rapids_tpu.expr.aggregates import CountStar, Sum
from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.obs.registry import get_registry

SEEDS = [42, 2**31 + 331]
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
    """``data(seed)``: that seed's lineitem at SF0.01, generated once."""
    base = tmp_path_factory.mktemp("q6")

    def of(seed: int) -> str:
        path = str(base / f"seed{seed}")
        _bench("datagen", "tpch_lineitem").generate(path, 0.01, seed,
                                                    ["lineitem"])
        return path
    return of


@pytest.mark.parametrize("query", ["tpch_q6", "tpch_q6_bounds"])
@pytest.mark.parametrize("seed", SEEDS)
def test_device_path_against_the_reference(session, data, seed, query):
    path = data(seed)
    want = [tuple(r) for r in _bench("reference", query).rows(path)]
    df = _bench("queries", query).build(session, path)
    rows = [tuple(r) for r in df.collect()]
    assert all(ln.lstrip().startswith("*")
               for ln in df.explain().splitlines())
    assert rows_match(rows, want)
    record = get_registry().recent_queries(1)[0]["counters"]
    # discount, quantity and price all travelled as scaled integers
    assert record["wire.double.scaled"] == 3
    assert "wire.double.raw" not in record
    assert record["wire.double.bytes"] == (1 << 16) * (4 + 8 + 24) // 8
    if query == "tpch_q6_bounds":
        # one group a discount, the bounds included, counts as integers
        assert [r[0] for r in rows] == [0.05, 0.06, 0.07]
        assert [r[1] for r in rows] == [w[1] for w in want]
        assert all(isinstance(r[1], int) and r[1] > 100 for r in rows)
        assert [r[2] for r in rows] == [w[2] for w in want]    # exact cents


def test_generator_keeps_its_promises(tmp_path):
    gen = _bench("datagen", "tpch_lineitem")
    counts = gen.generate(str(tmp_path / "a"), 0.2, 2**31 + 7, ["lineitem"])
    assert counts == {"lineitem": 1_200_000}
    files = sorted(f for f in os.listdir(tmp_path / "a" / "lineitem")
                   if f.endswith(".parquet"))
    assert files == ["part-0.parquet", "part-1.parquet"]
    meta = [pq.read_metadata(tmp_path / "a" / "lineitem" / f) for f in files]
    assert [m.num_rows for m in meta] == [1 << 20, 1_200_000 - (1 << 20)]
    assert [m.num_row_groups for m in meta] == [1, 1]
    assert gen.FILE_ROWS == 1 << 20
    # a file hangs on the seed and its index alone
    first = pq.read_table(tmp_path / "a" / "lineitem" / "part-1.parquet")
    gen.generate(str(tmp_path / "b"), 0.2, 2**31 + 7, ["lineitem"])
    again = pq.read_table(tmp_path / "b" / "lineitem" / "part-1.parquet")
    assert first.equals(again)
    alone = gen.file_columns(2**31 + 7, 1, 1_200_000 - (1 << 20))
    assert (np.asarray(alone["l_extendedprice"])
            == first["l_extendedprice"].to_numpy()).all()
    other = gen.file_columns(2**31 + 8, 1, 1000)
    assert (np.asarray(other["l_quantity"])
            != first["l_quantity"].to_numpy()[:1000]).any()
    # the columns: Q6's four and Q1's other three, as tpch.py draws them
    assert set(first.column_names) == {
        "l_quantity", "l_extendedprice", "l_discount", "l_tax",
        "l_returnflag", "l_linestatus", "l_shipdate"}
    disc = first["l_discount"].to_numpy()
    table = np.round(np.arange(11) * 0.01, 2)
    assert (table == np.arange(11) / 100.0).all()
    k = np.rint(disc * 100).astype(int)
    assert (disc.view(np.int64) == table[k].view(np.int64)).all()
    assert set(k) == set(range(11))                   # all eleven
    tax = first["l_tax"].to_numpy()
    assert set(np.rint(tax * 100).astype(int)) == set(range(9))
    qty = first["l_quantity"].to_numpy()
    assert qty.min() == 1 and qty.max() == 50 and (qty == np.rint(qty)).all()
    price = first["l_extendedprice"].to_numpy()
    cents = np.rint(price * 100)
    assert (cents / 100 == price).all()
    assert (price >= 900 * qty).all() and (price <= 2100 * qty).all()
    ship = first["l_shipdate"].cast(pa.int32()).to_numpy()
    assert 8035 < ship.min() < 8050 and 10580 < ship.max() <= 10591
    assert set(first["l_returnflag"].to_pylist()) == {"R", "A", "N"}
    assert set(first["l_linestatus"].to_pylist()) == {"F", "O"}
    # a stamp: a second call makes nothing anew; other tables are refused
    before = os.stat(tmp_path / "a" / "lineitem" / "part-0.parquet").st_mtime
    gen.generate(str(tmp_path / "a"), 0.2, 2**31 + 7, ["lineitem"])
    assert os.stat(tmp_path / "a" / "lineitem"
                   / "part-0.parquet").st_mtime == before
    with pytest.raises(ValueError, match="orders"):
        gen.generate(str(tmp_path / "c"), 0.01, 1, ["orders"])


@pytest.mark.parametrize("op", ["group", "join"])
def test_batches_that_travel_differently_are_one_key_set(session, tmp_path,
                                                         op):
    """Whether a batch of doubles ships scaled is decided from its own
    values: a file with one value that is not whole cents ships raw, its
    neighbour scaled — and 0.05 is one key in both."""
    rng = np.random.default_rng(46)
    n = 5000
    keys = [rng.integers(0, 11, n) / 100.0 for _ in range(2)]
    keys[0][7] = 1 / 3                                # this file: raw
    os.makedirs(tmp_path / "both")
    for side, k in zip("ab", keys):
        os.makedirs(tmp_path / side)
        for path in (tmp_path / side / "part-0.parquet",
                     tmp_path / "both" / f"part-{side}.parquet"):
            pq.write_table(pa.table({"k": k, "v": np.ones(n)}), path)
    both = pd.DataFrame({"k": np.concatenate(keys)})
    if op == "group":
        df = session.read_parquet(str(tmp_path / "both"), columns=["k", "v"]) \
            .group_by("k").agg(CountStar().alias("n"),
                               Sum(col("v")).alias("s")) \
            .order_by(("k", True))
        want = [(float(k), int(c), float(c)) for k, c in
                both.groupby("k").size().sort_index().items()]
    else:
        a = session.read_parquet(str(tmp_path / "a"), columns=["k"])
        b = session.read_parquet(str(tmp_path / "b"), columns=["k"]) \
            .select(col("k").alias("k2"))
        df = a.join(b, on=[("k", "k2")]).group_by("k") \
            .agg(CountStar().alias("n")).order_by(("k", True))
        left = pd.Series(keys[0]).value_counts()
        right = pd.Series(keys[1]).value_counts()
        want = [(float(k), int(left[k] * right[k]))
                for k in sorted(set(left.index) & set(right.index))]
    rows = [tuple(r) for r in df.collect()]
    assert rows == want and len(rows) in (11, 12)
    record = get_registry().recent_queries(1)[0]["counters"]
    assert record["wire.double.raw"] >= 1            # the file with 1/3
    assert record["wire.double.scaled"] >= 1
