"""What a string match has to read, from the Parquet footers: the
decoded bytes of the matched column — a constant of configuration,
query and seed, never taken from the program's buffers (the padded
width a staged matrix happens to have, a capacity).  This is the
numerator of ``like_roofline``: it counts the same work whatever
implements the match, so a slimmer staging cannot push the share past
100 %.

A column chunk written PLAIN holds a 4-byte length and the bytes of
every value, so its bytes are the footer's ``total_uncompressed_size``
less four a value (page headers and definition levels, a few hundred
bytes a chunk, stay in: under 0.01 %).  A dictionary-encoded chunk
counts less than its strings decode to; the share it gives is then too
low, never too high.
"""
from __future__ import annotations

import os

import pyarrow.parquet as pq

#: the engine's programs whose condition matches strings, as the trace
#: names them (exec/basic.py ``string_match_filter``, exec/fused.py
#: ``string_match_stage``)
MATCH_PROGRAMS = ("jit_string_match_filter", "jit_string_match_stage")


def column_bytes(table_dir: str, column: str) -> int:
    """Decoded bytes of the string column ``column`` over the Parquet
    files of ``table_dir``."""
    total = 0
    for name in sorted(os.listdir(table_dir)):
        if not name.endswith(".parquet"):
            continue
        meta = pq.read_metadata(os.path.join(table_dir, name))
        for g in range(meta.num_row_groups):
            group = meta.row_group(g)
            for c in range(group.num_columns):
                chunk = group.column(c)
                if chunk.path_in_schema == column:
                    total += chunk.total_uncompressed_size \
                        - 4 * chunk.num_values
    return total


def matched_bytes(root: str, dataset: str, columns: dict):
    """Decoded bytes of ``{table: [string columns]}`` in the one seed's
    data a run keeps under ``<root>/.bench_data/<dataset>`` (the runner
    makes it anew and removes the last run's); None where there is not
    exactly one."""
    base = os.path.join(root, ".bench_data", dataset)
    seeds = [d for d in (os.listdir(base) if os.path.isdir(base) else [])
             if d.startswith("seed")]
    if len(seeds) != 1:
        return None
    return sum(column_bytes(os.path.join(base, seeds[0], table), c)
               for table, cols in columns.items() for c in cols)
