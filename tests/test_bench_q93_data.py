"""q93's data cannot change unseen: the benchmark's one rule of its own
(PR 41; benchmark/datagen/tpcds.py ``_unique_tickets``: (item, ticket)
is unique among store_sales rows, as dsdgen's primary key has it) held
here in tier-1 — the checks benchmark/tests/test_datagen.py makes, which
tier-1 does not run, on the two ticket columns: no pair twice, the
drawn tickets stop short of ``first_free``, the rule's tickets gap-free,
only the later rows of a repeated pair moved, and a hand-made array."""
import os

import numpy as np
import pyarrow.parquet as pq
import pytest

from benchmark.datagen import tpcds
from spark_rapids_tpu.bench import tpcds_gen

TABLES = ["store_sales", "store_returns"]


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


def _pairs(table, ticket: str, item: str) -> np.ndarray:
    return (table[item].to_numpy().astype(np.int64) << 32) \
        | table[ticket].to_numpy()


@pytest.fixture(scope="module", params=[3, 11], ids=["seed3", "seed11"])
def both(request, tmp_path_factory):
    """(original, copy): the package's and the benchmark's sales and
    returns of one seed at SF0.01."""
    base = tmp_path_factory.mktemp(f"q93data{request.param}")
    a, b = str(base / "original"), str(base / "copy")
    tpcds_gen.generate_tpcds(a, sf=0.01, seed=request.param, tables=TABLES)
    tpcds.generate(b, 0.01, request.param, TABLES)
    return {t: (pq.read_table(os.path.join(a, t)),
                pq.read_table(os.path.join(b, t))) for t in TABLES}


def test_no_pair_of_item_and_ticket_comes_twice(both):
    for table, ticket, item in (
            ("store_sales", "ss_ticket_number", "ss_item_sk"),
            ("store_returns", "sr_ticket_number", "sr_item_sk")):
        _, copy = both[table]
        assert len(np.unique(_pairs(copy, ticket, item))) == len(copy)
    # and every return carries a sale's pair
    sales = _pairs(both["store_sales"][1], "ss_ticket_number", "ss_item_sk")
    returns = _pairs(both["store_returns"][1], "sr_ticket_number",
                     "sr_item_sk")
    assert np.isin(returns, sales).all()


def test_only_the_later_rows_of_a_repeated_pair_moved(both):
    original, copy = both["store_sales"]
    old = original["ss_ticket_number"].to_numpy()
    new = copy["ss_ticket_number"].to_numpy()
    moved = old != new
    first_seen = np.zeros(len(old), dtype=bool)
    first_seen[np.unique(_pairs(original, "ss_ticket_number", "ss_item_sk"),
                         return_index=True)[1]] = True
    assert np.array_equal(moved, ~first_seen)
    assert moved.sum() > 0            # the test's seeds do repeat pairs
    # every other column of the table is the original's
    assert original.drop(["ss_ticket_number"]).equals(
        copy.drop(["ss_ticket_number"]))


def test_drawn_tickets_stop_short_of_first_free_and_the_rules_have_no_gap(
        both):
    original, copy = both["store_sales"]
    old = original["ss_ticket_number"].to_numpy()
    new = copy["ss_ticket_number"].to_numpy()
    moved = old != new
    # ``rng.integers(1, n // 3)`` leaves its upper end out
    first_free = max(len(old) // 3, 2)
    assert old.max() < first_free
    assert np.array_equal(np.sort(new[moved]),
                          first_free + np.arange(moved.sum()))
    # in row order, as the rule says
    assert np.array_equal(new[moved], first_free + np.arange(moved.sum()))
