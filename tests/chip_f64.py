"""The chip's float64, on the CPU: what tier-1 (XLA:CPU has real
float64) cannot otherwise see.

On the TPU a float64 lives in HBM as itself, but every computation on
it runs on an unevaluated sum of two float32 (``hi`` the nearest
float32, ``lo`` the float32 nearest what is left: about 48 bits;
PERF.md Findings PR 23, 31 and 46).  ``_Pair`` is that arithmetic,
``_PairXP`` stands in for ``xp`` in the functions of ``ops/cents.py``
(and the wire codec's rebuild, which is one of them), so the same code
the chip runs is run here.  Each operation was held against the chip
once, on the same inputs, by ``scripts/probe_double_decode.py``.
"""
import numpy as np

__all__ = ["_Pair", "_Int64s", "_PairXP", "_on_chip"]


def _two_sum(a, b):
    """float32 ``(s, err)`` with ``s + err == a + b`` exactly."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


class _Pair:
    """A float64 array as the chip computes on it.  Products are an
    exact two-product of the high parts plus the cross terms in float32,
    renormalised; a sum is the two-sums of the high and of the low
    parts, renormalised twice; a comparison looks at ``hi``, then at
    ``lo``."""

    def __init__(self, hi, lo):
        self.hi = np.asarray(hi, np.float32)
        self.lo = np.asarray(lo, np.float32)

    @classmethod
    def of(cls, x):
        """What a float64 (a literal, a column from HBM) turns into."""
        x = np.asarray(x, np.float64)
        hi = x.astype(np.float32)
        return cls(hi, (x - hi.astype(np.float64)).astype(np.float32))

    def stored(self):
        """The float64 it is stored as (HBM keeps real float64)."""
        return self.hi.astype(np.float64) + self.lo.astype(np.float64)

    def __mul__(self, other):
        other = other if isinstance(other, _Pair) else _Pair.of(other)
        exact = self.hi.astype(np.float64) * other.hi.astype(np.float64)
        hi = exact.astype(np.float32)
        lo = (exact - hi.astype(np.float64)).astype(np.float32)
        lo = lo + (self.hi * other.lo + self.lo * other.hi)
        s = hi + lo                                  # fast two-sum
        return _Pair(s, lo - (s - hi))

    def __add__(self, other):
        other = other if isinstance(other, _Pair) else _Pair.of(other)
        s, e = _two_sum(self.hi, other.hi)
        t, f = _two_sum(self.lo, other.lo)
        e = e + t
        hi = s + e                                   # fast two-sum
        e = (e - (hi - s)) + f
        s = hi + e
        return _Pair(s, e - (s - hi))

    def is_the_pair_of(self, x) -> bool:
        """Bit for bit the pair the float64 array ``x`` turns into, and
        equal to it under every comparison."""
        want = _Pair.of(x)
        return bool((self.hi == want.hi).all() and (self.lo == want.lo).all()
                    and (self == want).all() and (self >= want).all()
                    and (self <= want).all())

    def _cmp(self, other):
        other = other if isinstance(other, _Pair) else _Pair.of(other)
        return np.where(self.hi != other.hi, np.sign(self.hi - other.hi),
                        np.sign(self.lo - other.lo))

    def __gt__(self, other):
        return self._cmp(other) > 0

    def __ge__(self, other):
        return self._cmp(other) >= 0

    def __le__(self, other):
        return self._cmp(other) <= 0

    def __eq__(self, other):
        return self._cmp(other) == 0

    __hash__ = None


class _ChipArray(np.ndarray):
    """An integer or float32 array whose ``astype(float64)`` is the
    chip's: a ``_Pair``.  Everything narrower is numpy's own (the chip's
    integers and float32 are the host's)."""

    def astype(self, dtype, *args, **kwargs):
        if dtype is not _PairXP.float64:
            return np.asarray(self).astype(dtype, *args, **kwargs) \
                .view(_ChipArray)
        v = np.asarray(self)
        if v.dtype == np.float32:                    # exact: (x, 0)
            return _Pair(v, np.zeros_like(v))
        # an integer, exact below 2^48: the high float32 and the rest
        v = v.astype(np.int64)
        hi = v.astype(np.float32)
        return _Pair(hi, (v - hi.astype(np.int64)).astype(np.float32))


def _on_chip(v):
    """An integer or float32 array as the chip holds it: numpy's own
    until it is widened to ``_PairXP.float64``."""
    return np.asarray(v).view(_ChipArray)


def _Int64s(v):
    """int64 values whose ``astype(float64)`` is a ``_Pair``."""
    return _on_chip(np.asarray(v, np.int64))


def _chip(fn):
    def call(*args, **kwargs):
        out = fn(*args, **kwargs)
        return out.view(_ChipArray) if isinstance(out, np.ndarray) else out
    return call


class _PairXP:
    """Stands in for ``xp``: numpy for integers and float32, ``_Pair``
    for whatever is widened to ``float64``."""
    float64 = object()
    float32, int32, int64, uint32 = np.float32, np.int32, np.int64, np.uint32
    abs, where, zeros = _chip(np.abs), _chip(np.where), _chip(np.zeros)
