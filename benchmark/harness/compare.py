"""The comparison that decides ``correct``.

Copied from spark_rapids_tpu/bench/runner.py ``_rows_match`` (sound:
paired, no float takes part in any ordering), at the one tolerance the
configurations guarantee: 6 significant digits, else rel 1e-5 / abs
1e-7.  The chip's f64 is an f32 pair (about 48 mantissa bits); a
plain-f32 sum over 6M rows misses this by an order of magnitude.
"""
from __future__ import annotations

import math
from collections import defaultdict

DIGITS, REL, ABS = 6, 1e-5, 1e-7


def _norm(rows):
    def cell(x):
        if isinstance(x, float):
            return (x is None, f"{x:.{DIGITS}g}")
        return (x is None, str(x))
    return sorted(tuple(cell(x) for x in r) for r in rows)


def rows_match(got, want) -> bool:
    """Order-insensitive: equal at ``DIGITS`` significant digits, or
    pairable — rows bucketed by their non-float cells, each ``got`` row
    matched with an unused ``want`` row whose floats all agree within
    the relative tolerance (fixed-digit formatting alone flips on 1-ulp
    noise at a digit boundary)."""
    got = [tuple(r) for r in got]
    want = [tuple(r) for r in want]
    if _norm(got) == _norm(want):
        return True
    if len(got) != len(want):
        return False

    def fixed(r):
        return tuple((i, x is None, str(x)) for i, x in enumerate(r)
                     if not isinstance(x, float))

    def floats(r):
        return [(i, x) for i, x in enumerate(r) if isinstance(x, float)]

    def close(a, b):
        fa, fb = floats(a), floats(b)
        if [i for i, _ in fa] != [i for i, _ in fb]:
            return False
        for (_, x), (_, y) in zip(fa, fb):
            if math.isnan(x) and math.isnan(y):
                continue
            if math.isnan(x) or math.isnan(y):
                return False
            if not math.isclose(x, y, rel_tol=REL, abs_tol=ABS):
                return False
        return True

    buckets = defaultdict(list)
    for r in want:
        buckets[fixed(r)].append(r)
    for r in got:
        cands = buckets.get(fixed(r))
        if not cands:
            return False
        for i, w in enumerate(cands):
            if close(r, w):
                cands.pop(i)
                break
        else:
            return False
    return True
