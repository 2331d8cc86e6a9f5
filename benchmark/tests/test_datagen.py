"""The benchmark's generators are copies made faster (no per-row Python
objects, files written in parallel): their output is held to the
originals' here, table for table, at SF0.01 for two seeds."""
import os

import pyarrow.parquet as pq
import pytest

from benchmark.datagen import tpcds, tpch


def _same(a: str, b: str, table: str) -> None:
    ta = pq.read_table(os.path.join(a, table))
    tb = pq.read_table(os.path.join(b, table))
    assert ta.schema.equals(tb.schema), table
    assert ta.equals(tb), table


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
