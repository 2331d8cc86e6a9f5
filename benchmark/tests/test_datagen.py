"""The benchmark's generators are copies made faster (no per-row Python
objects, files written in parallel): their output is held to the
originals' here, table for table, at SF0.01 for two seeds.

But for one rule of the benchmark's own (PR 41; ROADMAP D10: the
benchmark owns its generator): (item, ticket) is unique among
store_sales rows, as dsdgen's primary key has it, where the package's
generator draws tickets at random and lets about 26 pairs a million
come twice.  ``ss_ticket_number`` and the ``sr_ticket_number`` sampled
from it are therefore held to the original on every row but those the
rule moved, and every other column of every table byte for byte."""
import os

import numpy as np
import pyarrow.parquet as pq
import pytest

from benchmark.datagen import tpcds, tpch


#: the columns the benchmark's rule may move, and the key's other half
OWN = {"store_sales": ("ss_ticket_number", "ss_item_sk"),
       "store_returns": ("sr_ticket_number", "sr_item_sk")}


def _same(a: str, b: str, table: str) -> None:
    ta = pq.read_table(os.path.join(a, table))
    tb = pq.read_table(os.path.join(b, table))
    assert ta.schema.equals(tb.schema), table
    if table in OWN:
        ticket, item = OWN[table]
        _same_but_for_pairs_drawn_twice(ta, tb, ticket, item)
        ta, tb = ta.drop([ticket]), tb.drop([ticket])
    assert ta.equals(tb), table


def _pairs(table, ticket: str, item: str) -> np.ndarray:
    return (table[item].to_numpy().astype(np.int64) << 32) \
        | table[ticket].to_numpy()


def _same_but_for_pairs_drawn_twice(ta, tb, ticket: str, item: str):
    """``tb`` (the copy) differs from ``ta`` only where the original
    repeats an (item, ticket) pair, there by a ticket past the drawn
    range, and holds no pair twice."""
    old, new = ta[ticket].to_numpy(), tb[ticket].to_numpy()
    moved = old != new
    first_seen = np.zeros(len(old), dtype=bool)
    first_seen[np.unique(_pairs(ta, ticket, item), return_index=True)[1]] \
        = True
    if ticket == "ss_ticket_number":
        # every later row of a repeated pair moved, and no other
        assert np.array_equal(moved, ~first_seen)
        assert moved.sum() > 0        # the test's seeds do repeat pairs
        # the rule's tickets are free because the draw stops short of
        # them: ``rng.integers(1, n // 3)`` leaves its upper end out
        first_free = max(len(old) // 3, 2)
        assert old.max() < first_free
        assert np.array_equal(np.sort(new[moved]),
                              first_free + np.arange(moved.sum()))
    assert (new[moved] > old.max()).all()
    assert len(np.unique(_pairs(tb, ticket, item))) == len(new)


def test_unique_tickets_moves_the_later_rows_of_a_pair_and_no_other():
    item = np.array([7, 7, 8, 7, 8, 7, 9], dtype=np.int32)
    ticket = np.array([1, 2, 1, 1, 1, 1, 2], dtype=np.int64)
    # rows 3 and 5 repeat row 0's pair, row 4 repeats row 2's
    got = tpcds._unique_tickets(item, ticket, first_free=3)
    assert got.tolist() == [1, 2, 1, 3, 4, 5, 2]
    assert got.dtype == ticket.dtype and ticket.tolist()[3:6] == [1, 1, 1]
    # where nothing repeats nothing moves
    assert np.array_equal(tpcds._unique_tickets(item[:3], ticket[:3], 3),
                          ticket[:3])


@pytest.mark.parametrize("seed", [3, 11])
def test_tpcds_copy_writes_the_originals_tables(tmp_path, seed):
    from spark_rapids_tpu.bench import tpcds_gen
    a, b = str(tmp_path / "original"), str(tmp_path / "copy")
    tpcds_gen.generate_tpcds(a, sf=0.01, seed=seed)
    rows = tpcds.generate(b, 0.01, seed, tpcds.TABLES)
    assert rows == tpcds_gen.table_row_counts(0.01)
    for table in tpcds.TABLES:
        _same(a, b, table)


@pytest.mark.parametrize("seed", [3, 11])
def test_tpch_copy_writes_the_originals_tables(tmp_path, seed):
    from spark_rapids_tpu.bench import tpch_gen
    a, b = str(tmp_path / "original"), str(tmp_path / "copy")
    tpch_gen.generate_tpch(a, sf=0.01, seed=seed)
    tpch.generate(b, 0.01, seed, tpch.TABLES)
    for table in tpch.TABLES:
        _same(a, b, table)


def test_generation_is_skipped_on_a_stamp_hit_and_only_then(tmp_path):
    d = str(tmp_path / "d")
    tpch.generate(d, 0.01, 3, ["lineitem"])
    part = os.path.join(d, "lineitem", "part-0.parquet")
    first = os.path.getmtime(part)
    tpch.generate(d, 0.01, 3, ["lineitem"])
    assert os.path.getmtime(part) == first
    assert not os.path.exists(os.path.join(d, "orders"))
    tpch.generate(d, 0.01, 4, ["lineitem"])     # another seed: rewritten
    assert os.path.getmtime(part) != first
