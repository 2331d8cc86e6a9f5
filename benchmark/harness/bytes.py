"""What a query has to read, from the Parquet footers: a constant of
configuration, query and seed, never taken from the program.

``rows``: rows of the tables the query scans (each table once).
``bytes``: those rows times the decoded width of the columns the query
names — the least the chip can read to see its input once, which is the
numerator of ``pipeline_roofline``.  Fixed-width types count their
width; a string counts 4 bytes a row, the dictionary code it travels as
(a lower bound: validity bits and dictionaries are left out).
"""
from __future__ import annotations

import os

import pyarrow as pa
import pyarrow.parquet as pq


def _width(typ: pa.DataType) -> int:
    if pa.types.is_string(typ) or pa.types.is_large_string(typ) \
            or pa.types.is_dictionary(typ):
        return 4
    return typ.bit_width // 8


def scanned(data_dir: str, tables: dict) -> dict:
    """``{"rows": n, "bytes": n, "files": n}`` for ``{table: [columns]}``."""
    rows = nbytes = files = 0
    for table, columns in tables.items():
        tdir = os.path.join(data_dir, table)
        parts = sorted(f for f in os.listdir(tdir) if f.endswith(".parquet"))
        schema = pq.read_schema(os.path.join(tdir, parts[0]))
        width = sum(_width(schema.field(c).type) for c in columns)
        n = sum(pq.read_metadata(os.path.join(tdir, f)).num_rows
                for f in parts)
        rows += n
        nbytes += n * width
        files += len(parts)
    return {"rows": rows, "bytes": nbytes, "files": files}
