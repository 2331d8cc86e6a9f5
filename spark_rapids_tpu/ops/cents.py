"""Sums and averages of doubles that are whole hundredths: money.

The engine has no decimal type, and money reaches it as doubles that
are whole cents.  A sum of such doubles taken one rounding at a time
depends on the order of its rows (batch boundaries, partitions, the
sort inside a group-by), and so does an average: two groups whose
averages are *equal* as numbers — 735.76 / 2 and 1103.64 / 3 — come out
an ulp apart or not by chance, and a ``rank()`` over them, a join on
them or a ``having`` that compares them is then a coin toss where SQL's
``avg(decimal)`` is exact.

So where every addend of a group is a whole number of cents, the sum is
taken over the cents as integers and rounded once (:func:`as_cents`,
:func:`from_cents`), and an average divides in lowest terms
(:func:`mean`): the same rational gives the same double whatever rows,
batches or partitions it came from.  Anything else (a value that is not
whole cents, NaN, infinity, a sum past ``SUM_LIMIT``) is summed and
divided as doubles, as before.  A double that only happens to lie
within ``_TOL`` of a whole cent is taken for one; the sum then moves by
less than ``_TOL`` of that addend.

Every function takes ``xp`` — ``numpy`` (the host oracle) or
``jax.numpy`` (traced) — like the expressions' ``EvalCtx.xp``.  The
chip's f64 is an f32 pair of about 48 bits, which is why whole cents
are told by a tolerance and not bit for bit: ``x * 100`` is within
2^-45 of its integer there, and the limits keep that under 2^-4.
"""
from __future__ import annotations

import numpy as np

__all__ = ["ROW_LIMIT", "SUM_LIMIT", "as_cents", "from_cents", "mean"]

#: an addend takes part as whole cents below this many of them; with a
#: reduction over at most 2^24 rows the integer sum stays inside int64
ROW_LIMIT = 1 << 38
#: a sum is given (and read back, by a merge or by :func:`mean`) as
#: whole cents below this many: 2^44 * 2^-45 leaves the rounding clear
SUM_LIMIT = 1 << 44
_TOL = 2.0 ** -42


def as_cents(xp, x, limit: int = ROW_LIMIT):
    """``(cents, whole)``: ``x`` (float64) in hundredths as int64, and
    where it is a whole number of them below ``limit``; ``cents`` is 0
    elsewhere."""
    with np.errstate(invalid="ignore", over="ignore"):  # inf, NaN: not whole
        scaled = x * 100.0
        c = xp.rint(scaled)
        whole = (xp.abs(scaled - c) <= _TOL * xp.maximum(xp.abs(c), 1.0)) \
            & (xp.abs(c) < float(limit))
    return xp.where(whole, c, 0.0).astype(xp.int64), whole


def from_cents(xp, cents):
    """``cents`` hundredths (int64) as a double: the one the wire codec
    decodes that many cents to (``columnar/wirecodec.py``; XLA turns a
    division by the constant 100 into this product anyway)."""
    return cents.astype(xp.float64) * 0.01


def mean(xp, total, count):
    """``total / count`` (float64, int64 >= 1).  A ``total`` that is
    whole cents is divided in lowest terms, so that equal rationals give
    equal doubles on hardware whose division is not correctly rounded.

    No 64-bit integer division (the chip expands each into a long
    program): quotients are taken in float64 and set right by their
    integer remainders, and the gcd is 32 bits wide, of the count and
    the total's remainder by it."""
    f64, i64 = xp.float64, xp.int64
    cents, whole = as_cents(xp, total, SUM_LIMIT)
    whole = whole & (count < (1 << 31))
    k = xp.where(whole, count, 1)
    kf = k.astype(f64)

    def divide(a, b, bf):
        # (a // b, a % b) for int64 a and b > 0, |a| < 2^45
        q = xp.floor(a.astype(f64) / bf).astype(i64)
        r = a - q * b
        q2 = xp.floor(r.astype(f64) / bf).astype(i64)   # 0 but for a slip
        q, r = q + q2, r - q2 * b
        low, high = r < 0, r >= b
        return (q - low.astype(i64) + high.astype(i64),
                r + xp.where(low, b, 0) - xp.where(high, b, 0))

    _, r = divide(xp.abs(cents), k, kf)
    g = xp.gcd(k.astype(xp.int32), r.astype(xp.int32)).astype(i64)
    gf = g.astype(f64)
    num, _ = divide(cents, g, gf)
    den, _ = divide(k, g, gf)
    exact = num.astype(f64) / (den * 100).astype(f64)
    return xp.where(whole, exact, total / count.astype(f64))
