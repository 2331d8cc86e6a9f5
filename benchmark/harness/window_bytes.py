"""What a window operator has to move, from its rows and its schema:
a constant of the query and its data, never taken from the program's
buffers (capacities, padding, sort operands, prefix arrays).

A window operator reads every input row once and writes it back out
with one column appended for each window expression, so the least it
can move is ``rows x (input width + input width + appended width)``.
Widths are the decoded ones of ``harness/bytes.py``: a fixed-width type
its width, a string the 4-byte dictionary code it travels as; validity
bits are left out (a lower bound).  This is the numerator of
``window_roofline``: it reads the same work whatever implements the
frames.

The engine names an operator's schema in the counter that counts its
rows: ``window.rows@<input types>><appended types>``, the types' short
names joined by commas (``window.rows@int,date,double>double``).
"""
from __future__ import annotations

PREFIX = "window.rows@"

#: decoded bytes a value, by the engine's short type names
WIDTH = {"boolean": 1, "byte": 1, "short": 2, "int": 4, "long": 8,
         "float": 4, "double": 8, "date": 4, "timestamp": 8, "string": 4}


def window_bytes(rows: float, in_types, appended_types) -> float:
    """Bytes one window operator has to read and write for ``rows``
    rows of ``in_types`` columns and one ``appended_types`` column a
    window expression.  KeyError for a type with no decoded width."""
    width_in = sum(WIDTH[t] for t in in_types)
    return rows * (width_in + width_in + sum(WIDTH[t]
                                             for t in appended_types))


def record_bytes(counters: dict):
    """Over one collect's counters: the bytes of every window operator
    it ran, or None where it counted none (an engine from before the
    counter) or names a type with no decoded width."""
    total, found = 0.0, False
    for name, rows in counters.items():
        if not name.startswith(PREFIX):
            continue
        ins, _, outs = name[len(PREFIX):].partition(">")
        try:
            total += window_bytes(rows, [t for t in ins.split(",") if t],
                                  [t for t in outs.split(",") if t])
        except KeyError:
            return None
        found = True
    return total if found else None
