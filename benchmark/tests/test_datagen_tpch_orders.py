"""The generator of the ``tpch-sf10-chip1-orders`` configuration
(benchmark/datagen/tpch_orders.py) at a small scale: the comments'
lengths, the share Q13's pattern excludes, one seed made twice, and the
key columns against benchmark/datagen/tpch.py's."""
import os

import numpy as np
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from benchmark.datagen import tpch, tpch_orders

SF = 0.2     # 300k orders, 30k customers
SEEDS = [7, 2**31 + 11, 2**31 + 12]


@pytest.fixture(scope="module")
def made(tmp_path_factory):
    out = {}
    for seed in SEEDS:
        d = str(tmp_path_factory.mktemp(f"seed{seed}"))
        assert tpch_orders.generate(d, SF, seed, ["orders", "customer"]) \
            == {"customer": 30_000, "orders": 300_000}
        out[seed] = d
    return out


def _orders(d):
    return pq.read_table(os.path.join(d, "orders"))


@pytest.mark.parametrize("seed", SEEDS)
def test_comments_have_the_specifications_lengths_and_share(made, seed):
    c = _orders(made[seed]).column("o_comment").combine_chunks()
    assert c.null_count == 0
    lens = pc.utf8_length(c)
    assert pc.min(lens).as_py() == tpch_orders.COMMENT_MIN == 19
    assert pc.max(lens).as_py() == tpch_orders.COMMENT_MAX == 78
    assert 48.0 < pc.mean(lens).as_py() < 49.0
    assert pc.all(pc.equal(lens, pc.binary_length(c))).as_py()   # ASCII
    # dbgen's data excludes about 1 %: the band is 0.8 % .. 1.6 %
    excluded = pc.sum(pc.match_like(c, "%special%requests%")).as_py()
    assert 0.008 < excluded / len(c) < 0.016
    # practically every comment is distinct
    assert pc.count_distinct(c).as_py() > 0.97 * len(c)
    # and both words are common alone
    for word in ("special", "requests"):
        assert pc.sum(pc.match_substring(c, word)).as_py() > 0.05 * len(c)


def test_one_seed_made_twice_is_identical(made, tmp_path):
    again = str(tmp_path / "again")
    tpch_orders.generate(again, SF, SEEDS[1], None)
    for table in ("orders", "customer"):
        a = pq.read_table(os.path.join(made[SEEDS[1]], table))
        b = pq.read_table(os.path.join(again, table))
        assert a.equals(b)
    # a table alone equals the table made beside the other
    alone = str(tmp_path / "alone")
    tpch_orders.generate(alone, SF, SEEDS[1], ["orders"])
    assert not os.path.exists(os.path.join(alone, "customer"))
    assert pq.read_table(os.path.join(alone, "orders")).equals(
        pq.read_table(os.path.join(again, "orders")))
    # another seed, other data
    assert not _orders(made[SEEDS[0]]).equals(_orders(made[SEEDS[1]]))
    with pytest.raises(ValueError):
        tpch_orders.generate(alone, SF, 1, ["lineitem"])


def test_keys_are_drawn_as_datagen_tpch_draws_them(made, tmp_path):
    d = str(tmp_path / "tpch")
    tpch.generate(d, SF, SEEDS[0], ["customer", "orders"])
    assert tpch_orders.table_row_counts(SF) == {
        t: tpch.table_row_counts(SF)[t] for t in ("customer", "orders")}
    theirs = pq.read_table(os.path.join(d, "orders"),
                           columns=["o_orderkey", "o_custkey"])
    mine = _orders(made[SEEDS[0]])
    for name in ("o_orderkey", "o_custkey"):
        assert mine.schema.field(name).type == theirs.schema.field(name).type
    assert mine.column("o_orderkey").equals(theirs.column("o_orderkey"))
    cust = pq.read_table(os.path.join(made[SEEDS[0]], "customer"))
    assert cust.column("c_custkey").equals(pq.read_table(
        os.path.join(d, "customer"), columns=["c_custkey"]).column(0))
    # o_custkey: the same range (the lower two thirds of the customers,
    # so a third have no order), uniform
    for keys in (mine.column("o_custkey").to_numpy(),
                 theirs.column("o_custkey").to_numpy()):
        assert keys.min() == 1 and keys.max() == 30_000 * 2 // 3 - 1
    with_orders = np.unique(mine.column("o_custkey").to_numpy()).size
    assert 0.33 < 1 - with_orders / 30_000 < 0.34


def test_orders_come_in_files_of_a_batch_written_plain(tmp_path):
    d = str(tmp_path / "d")
    tpch_orders.generate(d, 1.0, 3, ["orders"])
    parts = sorted(f for f in os.listdir(os.path.join(d, "orders"))
                   if f.endswith(".parquet"))
    assert parts == ["part-0.parquet", "part-1.parquet"]
    rows = [pq.read_metadata(os.path.join(d, "orders", p)) for p in parts]
    assert [m.num_rows for m in rows] == [1 << 20, 1_500_000 - (1 << 20)]
    assert all(m.num_row_groups == 1 for m in rows)
    chunk = rows[0].row_group(0).column(4)
    assert chunk.path_in_schema == "o_comment"
    assert "PLAIN" in chunk.encodings \
        and not any("DICTIONARY" in e for e in chunk.encodings)
    keys = pq.read_table(os.path.join(d, "orders"),
                         columns=["o_orderkey"]).column(0).to_numpy()
    assert (keys == np.arange(1, 1_500_001)).all()
