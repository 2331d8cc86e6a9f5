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
2^-45 of its integer there, and the limits keep that under 2^-4.  It
is also why the way back (:func:`from_cents`) computes nothing in
float64: a product or a quotient in pair arithmetic is not the pair
the host's double turns into, and a sum handed back a hair under
``0.05`` fails ``>= 0.05``.  ``from_cents`` is the one place hundredths
become a double on the device — the wire codec's decode calls it too —
and tests/chip_f64.py runs it under an emulator of the pair arithmetic
(``xp`` there is neither numpy nor jax.numpy: keep to array methods,
``xp.where``, ``xp.abs``, ``xp.zeros`` and the dtypes).
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


def _pow2(xp, k):
    """``2.0 ** k`` as float32 (int32 ``k``, a normal exponent), put
    together from its bits: no rounding, on any backend."""
    return ((k + 127) << 23).view(xp.float32)


def _thirds(xp, cents):
    """``(hi, lo, rest)``: three float32 whose exact sum is the double
    nearest ``cents / 100`` (int64, ``|cents| < SUM_LIMIT``).  ``hi`` is
    the float32 nearest that double and ``lo`` the float32 nearest what
    is left, so ``(hi, lo)`` is the f32 pair the chip turns that double
    into; ``rest`` is the last five or so bits a pair cannot hold.

    All of it is integer work: the 53-bit quotient ``(|cents| << s) /
    100`` by long division in 15-bit limbs (each limb's quotient a
    float32 product set right by its integer remainder: no integer
    division, no float64), cut into 24 + 24 + 5 bits to nearest-even.
    The only floating point is the conversion of integers below 2^29
    to float32 and products by powers of two."""
    i64, i32, f32 = xp.int64, xp.int32, xp.float32
    a = xp.abs(cents)
    sign = xp.where(cents < 0, f32(-1.0), f32(1.0))
    sign = xp.where(a > 0, sign, f32(0.0))
    a = xp.where(a > 0, a, 1)
    # bit length of a: the exponent of an exactly converted 22-bit half
    top, low = (a >> 22).astype(i32), (a & 0x3FFFFF).astype(i32)
    wide = top > 0
    half = xp.where(wide, top, low)
    bits = (half.astype(f32).view(i32) >> 23) - 126 + 22 * wide.astype(i32)
    # n = a << s with n / 100 in [2^52, 2^53): the double's mantissa is
    # its rounded quotient, the double's exponent e = 52 - s
    n = a << (59 - bits).astype(i64)
    small = n < (25 << 54)
    n = xp.where(small, n << 1, n)
    e = bits - 7 - small.astype(i32)
    rem = xp.zeros(a.shape, i32)
    quot = []
    for shift in (45, 30, 15, 0):
        cur = rem * 32768 + ((n >> shift) & 0x7FFF).astype(i32)
        q = (cur.astype(f32) * f32(0.01)).astype(i32)     # within one
        rem = cur - q * 100
        slip = (rem >= 100).astype(i32) - (rem < 0).astype(i32)
        q, rem = q + slip, rem - slip * 100
        quot.append(q)
    q3, q2, q1, q0 = quot
    # the 53 bits are q3:q2:q1:q0 (8 + 15 + 15 + 15), rounded up where
    # the remainder is over half (n is a multiple of 4: never a tie)
    tail = ((q1 & 0x3FFF) << 15) + q0 + (rem >= 50).astype(i32)
    head = (q3 << 16) + (q2 << 1) + (q1 >> 14) + (tail >> 29)
    tail = tail & 0x1FFFFFFF
    # head: the top 24 bits; to nearest, ties to even
    up = ((tail > (1 << 28)) | ((tail == (1 << 28)) & ((head & 1) == 1))) \
        .astype(i32)
    head = head + up
    tail = tail - (up << 29)                    # signed, |tail| <= 2^28
    mid = tail.astype(f32)                      # to nearest even, 24 bits
    last = tail - mid.astype(i32)
    coarse, fine = sign * _pow2(xp, e - 23), sign * _pow2(xp, e - 52)
    return head.astype(f32) * coarse, mid * fine, last.astype(f32) * fine


def from_cents(xp, cents):
    """``cents`` hundredths (int64, ``|cents| < SUM_LIMIT``) as THE
    double ``cents / 100.0``, the correctly rounded quotient a host
    writes for that many cents — on every backend.  The chip's float64
    arithmetic is an f32 pair of about 48 bits, in which neither
    ``cents * 0.01`` nor ``cents / 100`` is the pair that double turns
    into (``5 * 0.01 >= 0.05`` is false there: TPC-H Q6 lost every row
    at a discount of 0.05), so the double is built from integers
    (:func:`_thirds`) and only widened and added here: in real float64
    the three parts sum to the double bit for bit; in pair arithmetic
    ``hi + lo`` is the pair already and adding ``rest`` rounds back to
    it.  The wire codec rebuilds a money column by this same function
    (``columnar/wirecodec.py``), so a sum and a scanned value that are
    the same number of cents are the same double."""
    f64 = xp.float64
    hi, lo, rest = _thirds(xp, cents)
    return hi.astype(f64) + lo.astype(f64) + rest.astype(f64)


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
