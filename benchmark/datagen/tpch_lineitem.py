"""TPC-H LINEITEM alone, file by file (seeded, pruned, no per-row
Python): the generator of the ``tpch-sf10-chip1-lineitem``
configuration.

``benchmark/datagen/tpch.py`` makes all eight tables in memory at once
and numbers 6M line comments in Python: right at SF1, not at the 60M
rows of a chip's share.  Here LINEITEM is written one Parquet file of
2^20 rows (one row group) at a time, each file from a random stream of
its own (``[seed, file index]``), so no more than one file's rows are
ever held and a file's content depends on nothing but the seed and its
index.

The columns are Q6's four and Q1's other three (Parquet reads only
referenced columns), and each is drawn as ``datagen/tpch.py`` draws it:
``l_quantity`` 1..50, ``l_extendedprice`` the quantity times a uniform
900.00..2100.00 rounded to cents, ``l_discount`` 0.00..0.10 and
``l_tax`` 0.00..0.08 in hundredths, ``l_shipdate`` an order date
uniform over the specification's range plus 1..121 days,
``l_returnflag`` R or A where the receipt date (ship + 1..30 days) is
on or before 1995-06-17 and N after, ``l_linestatus`` O where the ship
date is after it.  The doubles are ``np.round(k * 0.01, 2)``: the
double nearest k/100, what a writer of decimal text or of a decimal
column cast to double gives.
"""
from __future__ import annotations

import os
import shutil

import numpy as np

TABLES = ("lineitem",)

_SCHEMA_VERSION = "v1"

#: rows a Parquet file, and a row group: one staged batch
FILE_ROWS = 1 << 20

#: dates are DAYS since 1970-01-01, as datagen/tpch.py has them
_DATE_LO, _DATE_HI = 8035, 10591
_SPLIT = 9204      # 1995-06-17-ish: return flag and line status turn


def _strings(values, codes):
    import pyarrow as pa
    return pa.DictionaryArray.from_arrays(
        pa.array(np.asarray(codes, dtype=np.int32)),
        pa.array(list(values), type=pa.string())).cast(pa.string())


def file_columns(seed: int, part: int, k: int) -> dict:
    """The ``k`` rows of file ``part``: name -> numpy or Arrow array."""
    import pyarrow as pa
    rng = np.random.default_rng([seed, part])
    odate = rng.integers(_DATE_LO, _DATE_HI - 121, k)
    qty = rng.integers(1, 51, k)
    price = np.round(rng.uniform(900.0, 2100.0, k) * qty, 2)
    disc = np.round(rng.integers(0, 11, k) * 0.01, 2)
    tax = np.round(rng.integers(0, 9, k) * 0.01, 2)
    ship = odate + rng.integers(1, 122, k)
    receipt = ship + rng.integers(1, 31, k)
    returnflag = np.where(receipt <= _SPLIT, rng.integers(0, 2, k), 2)
    return {
        "l_quantity": qty.astype(np.float64),
        "l_extendedprice": price,
        "l_discount": disc,
        "l_tax": tax,
        "l_returnflag": _strings(("R", "A", "N"), returnflag),
        "l_linestatus": _strings(("F", "O"), ship > _SPLIT),
        "l_shipdate": pa.array(ship.astype(np.int32), type=pa.date32()),
    }


def table_row_counts(sf: float) -> dict:
    """As datagen/tpch.py counts them (the specification's SF10 has
    59,986,052 lines; 6M a unit of scale here)."""
    return {"lineitem": max(1200, int(6_000_000 * sf))}


def generate(data_dir: str, sf: float, seed: int, tables=None) -> dict:
    """Generate (or re-use, on its stamp) ``lineitem`` under
    ``data_dir``; returns {table: rows}."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    unknown = set(tables or ()) - set(TABLES)
    if unknown:
        raise ValueError(f"tpch_lineitem generates {TABLES}, not "
                         f"{sorted(unknown)}")
    counts = table_row_counts(sf)
    out = os.path.join(data_dir, "lineitem")
    stamp = os.path.join(out, f"_{_SCHEMA_VERSION}_sf{sf:g}_seed{seed}")
    if os.path.exists(stamp):
        return counts
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    n = counts["lineitem"]
    for part, lo in enumerate(range(0, n, FILE_ROWS)):
        cols = file_columns(seed, part, min(FILE_ROWS, n - lo))
        pq.write_table(
            pa.Table.from_arrays([pa.array(c) if isinstance(c, np.ndarray)
                                  else c for c in cols.values()],
                                 names=list(cols)),
            os.path.join(out, f"part-{part}.parquet"),
            row_group_size=FILE_ROWS)
    with open(stamp, "w") as f:
        f.write(os.path.basename(stamp) + "\n")
    return counts
