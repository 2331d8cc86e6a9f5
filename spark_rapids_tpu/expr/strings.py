"""String expressions (reference stringFunctions.scala, 898 LoC).

Device representation is a padded uint8 byte matrix + lengths (see
columnar/column.py).  Kernels are dense VPU-friendly ops:

* Length / Substring are UTF-8 *character* correct (continuation-byte
  masks + cumulative character counts) matching Spark;
* Upper/Lower are ASCII-only on device (flagged incompat in the planner,
  like the reference's incompat string ops);
* Like evaluates every pattern made of literal segments and ``%`` on
  device (any number of segments, anchored or not at either end); ``_``
  and escapes are host-only (the reference likewise gates regex behind
  shims, Spark300Shims.scala:235);
* a literal needle (Like's segments, and StartsWith / EndsWith / Contains
  with a literal right side) is matched by static slices of the byte
  matrix against constants, never by a gather: an index an ``arange``
  decides is still a gather on the chip (PERF.md Findings PR 39).
"""
from __future__ import annotations

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.core import Expression, EvalCtx, Val, Literal
from spark_rapids_tpu.expr.predicates import _string_pair_device

__all__ = ["Upper", "Lower", "Length", "Substring", "Concat", "StartsWith",
           "EndsWith", "Contains", "Like", "StringTrim", "StringTrimLeft",
           "StringTrimRight", "StringReplace", "ConcatWs", "StringLocate",
           "SubstringIndex", "InitCap", "StringLPad", "StringRPad",
           "StringRepeat", "Hex"]


def _char_starts(data, lengths, xp):
    """bool[n,w]: byte j is the start of a character and inside the string."""
    w = data.shape[1]
    in_range = xp.arange(w, dtype=np.int32)[None, :] < lengths[:, None]
    return ((data & 0xC0) != 0x80) & in_range


def literal_needle(expr) -> bytes | None:
    """The UTF-8 bytes of a non-null string literal, else None."""
    if isinstance(expr, Literal) and isinstance(expr.value, str):
        return expr.value.encode("utf-8")
    return None


def _needle_at(data, needle: bytes):
    """bool[n, w - L + 1]: the needle's L bytes stand at offset p of the
    row — L static slices compared against L constants and and-ed, every
    offset at once.  The caller bounds ``p + L`` by the row's length
    (padding is zeros, and a needle may hold one).  L <= w, L >= 1."""
    span = data.shape[1] - len(needle) + 1
    hit = None
    for i, byte in enumerate(needle):
        eq = data[:, i:i + span] == np.uint8(byte)
        hit = eq if hit is None else hit & eq
    return hit


def _starts_with(data, lengths, needle: bytes, xp):
    if len(needle) > data.shape[1]:
        return xp.zeros(data.shape[0], dtype=bool)
    if not needle:
        return xp.ones(data.shape[0], dtype=bool)
    return _needle_at(data[:, :len(needle)], needle)[:, 0] \
        & (lengths >= len(needle))


def _ends_with(data, lengths, needle: bytes, xp, after=None):
    """The needle ends the row (and starts at or after ``after``)."""
    start = lengths - len(needle)
    ok = start >= (0 if after is None else after)
    if len(needle) > data.shape[1]:
        return xp.zeros(data.shape[0], dtype=bool)
    if not needle:
        return ok
    hit = _needle_at(data, needle)
    p = xp.arange(hit.shape[1], dtype=np.int32)[None, :]
    return ok & xp.any(hit & (p == start[:, None]), axis=1)


def _first_after(data, lengths, needle: bytes, after, xp):
    """``(found, end)``: the leftmost occurrence of the needle that
    starts at or after ``after`` (int32[n], or 0) and lies inside the
    row; ``end`` is one past it (past the width where none is found, so
    nothing matches after it either)."""
    n, w = data.shape
    if len(needle) > w:
        return xp.zeros(n, dtype=bool), xp.full(n, w + 1, dtype=np.int32)
    hit = _needle_at(data, needle)
    p = xp.arange(hit.shape[1], dtype=np.int32)[None, :]
    hit = hit & (p + len(needle) <= lengths[:, None])
    if after is not None:
        hit = hit & (p >= after[:, None])
    first = xp.min(xp.where(hit, p, np.int32(w)), axis=1)
    return first < w, first + np.int32(len(needle))


def string_matches(expr) -> list:
    """The device string matches of a bound expression tree, as their
    matched children: one entry for each ``Like`` and each
    ``StartsWith`` / ``EndsWith`` / ``Contains`` with a literal needle.
    An operator whose condition holds one launches under a program name
    of its own and counts ``like.device.rows`` / ``like.device.bytes``
    (exec/basic.py ``count_string_matches``)."""
    found = []

    def walk(e):
        if isinstance(e, Like) or (
                isinstance(e, _StringPredicate)
                and literal_needle(e.children[1]) is not None):
            found.append(e.children[0])
        for c in e.children:
            walk(c)
    walk(expr)
    return found



class _StringUnary(Expression):
    def __init__(self, child: Expression):
        self.children = (child,)

    @property
    def dtype(self):
        return T.StringType()

    def _eval(self, vals, ctx):
        a = vals[0]
        if not ctx.is_device:
            out = np.empty(ctx.capacity, dtype=object)
            for i in range(ctx.capacity):
                out[i] = self._host_one(a.data[i]) if a.validity[i] else None
            return Val(out, a.validity, None, T.StringType())
        data, lengths = self._device(a, ctx)
        return ctx.canonical(data, a.validity, T.StringType(), lengths)


class Upper(_StringUnary):
    sql_name = "Upper"
    #: ASCII-only on device (host oracle is full unicode) — incompat
    incompat = True

    def _host_one(self, s):
        return s.upper()

    def _device(self, a, ctx):
        xp = ctx.xp
        is_lower = (a.data >= ord("a")) & (a.data <= ord("z"))
        return xp.where(is_lower, a.data - 32, a.data), a.lengths


class Lower(_StringUnary):
    sql_name = "Lower"
    incompat = True

    def _host_one(self, s):
        return s.lower()

    def _device(self, a, ctx):
        xp = ctx.xp
        is_upper = (a.data >= ord("A")) & (a.data <= ord("Z"))
        return xp.where(is_upper, a.data + 32, a.data), a.lengths


class _TrimBase(_StringUnary):
    _left = True
    _right = True

    def _host_one(self, s):
        if self._left and self._right:
            return s.strip(" ")
        return s.lstrip(" ") if self._left else s.rstrip(" ")

    def _device(self, a, ctx):
        xp = ctx.xp
        w = a.data.shape[1]
        j = xp.arange(w, dtype=np.int32)[None, :]
        in_range = j < a.lengths[:, None]
        nonspace = (a.data != 32) & in_range
        any_ns = xp.any(nonspace, axis=1)
        first = xp.where(any_ns, xp.argmax(nonspace, axis=1), 0) \
            if self._left else xp.zeros_like(a.lengths)
        last_rev = xp.argmax(nonspace[:, ::-1], axis=1)
        last = xp.where(any_ns, w - 1 - last_rev, -1) \
            if self._right else a.lengths - 1
        new_len = xp.where(any_ns, xp.maximum(last - first + 1, 0), 0)
        new_len = new_len.astype(np.int32)
        idx = first[:, None] + xp.arange(w, dtype=np.int32)[None, :]
        idx = xp.clip(idx, 0, w - 1)
        shifted = xp.take_along_axis(a.data, idx, axis=1)
        keep = xp.arange(w, dtype=np.int32)[None, :] < new_len[:, None]
        return xp.where(keep, shifted, 0), new_len


class StringTrim(_TrimBase):
    sql_name = "StringTrim"


class StringTrimLeft(_TrimBase):
    sql_name = "StringTrimLeft"
    _right = False


class StringTrimRight(_TrimBase):
    sql_name = "StringTrimRight"
    _left = False


class Length(Expression):
    """Character count (Spark length), IntegerType."""
    sql_name = "Length"

    def __init__(self, child: Expression):
        self.children = (child,)

    @property
    def dtype(self):
        return T.IntegerType()

    def _eval(self, vals, ctx):
        a = vals[0]
        if not ctx.is_device:
            data = np.array([len(s) if v else 0
                             for s, v in zip(a.data, a.validity)], np.int32)
            return ctx.canonical(data, a.validity, T.IntegerType())
        starts = _char_starts(a.data, a.lengths, ctx.xp)
        data = ctx.xp.sum(starts, axis=1).astype(np.int32)
        return ctx.canonical(data, a.validity, T.IntegerType())


class Substring(Expression):
    """Spark substring(str, pos, len): 1-based, pos<=0 counts 0/from-end,
    character-indexed; out-of-range yields '' (not null)."""
    sql_name = "Substring"

    def __init__(self, child: Expression, pos: Expression, length: Expression):
        self.children = (child, pos, length)

    @property
    def dtype(self):
        return T.StringType()

    def _eval(self, vals, ctx):
        a, pos, length = vals
        if not ctx.is_device:
            out = np.empty(ctx.capacity, dtype=object)
            validity = a.validity & pos.validity & length.validity
            for i in range(ctx.capacity):
                if not validity[i]:
                    out[i] = None
                    continue
                out[i] = _substr_host(a.data[i], int(pos.data[i]),
                                      int(length.data[i]))
            return Val(out, validity, None, T.StringType())
        return self._device(a, pos, length, ctx)

    def _device(self, a, pos, length, ctx):
        xp = ctx.xp
        w = a.data.shape[1]
        validity = a.validity & pos.validity & length.validity
        starts = _char_starts(a.data, a.lengths, xp)
        nchars = xp.sum(starts, axis=1).astype(np.int32)
        p = pos.data.astype(np.int32)
        ln = xp.maximum(length.data.astype(np.int32), 0)
        # resolve 1-based / negative positions to 0-based char index
        start_char = xp.where(p > 0, p - 1, xp.where(p < 0, nchars + p, 0))
        neg_clip = xp.where(p < 0, xp.maximum(ln + xp.minimum(nchars + p, 0), 0), ln)
        start_char = xp.clip(start_char, 0, nchars)
        end_char = xp.clip(start_char + neg_clip, 0, nchars)
        # byte offset of char k: position of the (k+1)-th start; k==nchars -> len
        cs = xp.cumsum(starts.astype(np.int32), axis=1)
        def byte_of(k):
            hit = (cs == (k + 1)[:, None]) & starts
            found = xp.any(hit, axis=1)
            return xp.where(found, xp.argmax(hit, axis=1).astype(np.int32),
                            a.lengths)
        sb = byte_of(start_char)
        eb = byte_of(end_char)
        new_len = xp.maximum(eb - sb, 0).astype(np.int32)
        idx = xp.clip(sb[:, None] + xp.arange(w, dtype=np.int32)[None, :],
                      0, w - 1)
        shifted = xp.take_along_axis(a.data, idx, axis=1)
        keep = xp.arange(w, dtype=np.int32)[None, :] < new_len[:, None]
        data = xp.where(keep, shifted, 0)
        return ctx.canonical(data, validity, T.StringType(), new_len)


def _substr_host(s: str, pos: int, ln: int) -> str:
    if ln <= 0:
        return ""
    n = len(s)
    if pos > 0:
        start = pos - 1
    elif pos < 0:
        start = n + pos
    else:
        start = 0
    end = start + ln
    if start < 0:
        start = 0
    return s[start:end] if start < n else ""


class Concat(Expression):
    """concat(s1, s2, ...): null if any input null (Spark concat)."""
    sql_name = "Concat"

    def __init__(self, *children: Expression):
        self.children = tuple(children)

    def with_new_children(self, children):
        return Concat(*children)

    @property
    def dtype(self):
        return T.StringType()

    def _eval(self, vals, ctx):
        xp = ctx.xp
        validity = vals[0].validity
        for v in vals[1:]:
            validity = validity & v.validity
        if not ctx.is_device:
            out = np.empty(ctx.capacity, dtype=object)
            for i in range(ctx.capacity):
                out[i] = "".join(v.data[i] for v in vals) if validity[i] else None
            return Val(out, validity, None, T.StringType())
        acc = vals[0]
        data, lengths = acc.data, acc.lengths
        for v in vals[1:]:
            data, lengths = _concat2_device(data, lengths, v.data, v.lengths, xp)
        return ctx.canonical(data, validity, T.StringType(), lengths)


def _concat2_device(da, la, db, lb, xp):
    from spark_rapids_tpu.columnar.column import round_string_width
    wa, wb = da.shape[1], db.shape[1]
    w = round_string_width(wa + wb)
    n = da.shape[0]
    j = xp.arange(w, dtype=np.int32)[None, :]
    from_a = j < la[:, None]
    ia = xp.broadcast_to(xp.clip(j, 0, wa - 1), (n, w))
    ib = xp.clip(j - la[:, None], 0, wb - 1)
    av = xp.take_along_axis(da, ia, axis=1)
    bv = xp.take_along_axis(db, ib, axis=1)
    new_len = la + lb
    keep = j < new_len[:, None]
    return xp.where(keep, xp.where(from_a, av, bv), 0), new_len


class _StringPredicate(Expression):
    def __init__(self, left: Expression, right: Expression):
        self.children = (left, right)

    @property
    def dtype(self):
        return T.BooleanType()

    def _eval(self, vals, ctx):
        a, b = vals
        validity = a.validity & b.validity
        if not ctx.is_device:
            data = np.array([self._host_one(x, y) if va and vb else False
                             for x, y, va, vb in
                             zip(a.data, b.data, a.validity, b.validity)], bool)
            return ctx.canonical(data, validity, T.BooleanType())
        needle = literal_needle(self.children[1])
        if needle is not None:
            data = self._literal(a.data, a.lengths, needle, ctx.xp)
        else:  # a needle that is a column: its bytes differ row by row
            data = self._device(a, b, ctx)
        return ctx.canonical(data, validity, T.BooleanType())


class StartsWith(_StringPredicate):
    sql_name = "StartsWith"

    def _host_one(self, x, y):
        return x.startswith(y)

    _literal = staticmethod(_starts_with)

    def _device(self, a, b, ctx):
        xp = ctx.xp
        da, db = _string_pair_device(a, b, ctx)
        w = da.shape[1]
        j = xp.arange(w, dtype=np.int32)[None, :]
        within = j < b.lengths[:, None]
        match = xp.all(~within | (da == db), axis=1)
        return match & (a.lengths >= b.lengths)


class EndsWith(_StringPredicate):
    sql_name = "EndsWith"

    def _host_one(self, x, y):
        return x.endswith(y)

    _literal = staticmethod(_ends_with)

    def _device(self, a, b, ctx):
        xp = ctx.xp
        w = max(a.data.shape[1], b.data.shape[1])
        da, db = _string_pair_device(a, b, ctx)
        j = xp.arange(w, dtype=np.int32)[None, :]
        shift = (a.lengths - b.lengths)[:, None]
        idx = xp.clip(j + shift, 0, w - 1)
        tail = xp.take_along_axis(da, idx, axis=1)
        within = j < b.lengths[:, None]
        match = xp.all(~within | (tail == db), axis=1)
        return match & (a.lengths >= b.lengths)


class Contains(_StringPredicate):
    sql_name = "Contains"

    def _host_one(self, x, y):
        return y in x

    @staticmethod
    def _literal(data, lengths, needle, xp):
        if not needle:
            return xp.ones(data.shape[0], dtype=bool)
        return _first_after(data, lengths, needle, None, xp)[0]

    def _device(self, a, b, ctx):
        xp = ctx.xp
        da, db = _string_pair_device(a, b, ctx)
        w = da.shape[1]
        n = da.shape[0]
        j = xp.arange(w, dtype=np.int32)[None, :]
        within = j < b.lengths[:, None]
        found = xp.zeros(n, dtype=bool)
        # slide the needle over every start offset (static unroll over width;
        # VPU-dense compare per shift)
        for s in range(w):
            idx = xp.clip(j + s, 0, w - 1)
            win = xp.take_along_axis(da, idx, axis=1)
            m = xp.all(~within | (win == db), axis=1)
            found = found | (m & (s + b.lengths <= a.lengths))
        return found


class Like(Expression):
    """SQL LIKE.  The device evaluates every pattern made of literal
    segments and ``%`` — any number of segments, anchored or not at
    either end (``x``, ``x%``, ``%x``, ``%x%y%``, ``a%b%c`` …): the
    leftmost match of each segment at or after the end of the one
    before, over the padded byte matrix and the lengths, by static
    slices (bytes, which for literal segments is characters: UTF-8
    resynchronizes).  NULL in, NULL out.  A pattern with ``_`` or an
    escape character is host-only."""
    sql_name = "Like"

    def __init__(self, child: Expression, pattern: str, escape: str = "\\"):
        self.children = (child,)
        self.pattern = pattern
        self.escape = escape

    def with_new_children(self, children):
        return Like(children[0], self.pattern, self.escape)

    @property
    def dtype(self):
        return T.BooleanType()

    @property
    def device_supported(self):
        return self._segments() is not None

    def _segments(self):
        """``(head, middle, tail)`` of a pattern of literals and ``%``:
        the bytes the string must start with (b"" where the pattern
        starts with ``%``), the segments between two ``%`` in order, the
        bytes it must end with; ``(whole, None, None)`` for a pattern
        without ``%``; None where the pattern holds ``_`` or an escape."""
        p = self.pattern
        if "_" in p or (self.escape and self.escape in p):
            return None
        parts = [s.encode("utf-8") for s in p.split("%")]
        if len(parts) == 1:
            return parts[0], None, None
        return parts[0], [s for s in parts[1:-1] if s], parts[-1]

    def _regex(self):
        import re
        out = []
        i = 0
        p = self.pattern
        while i < len(p):
            c = p[i]
            if c == self.escape and i + 1 < len(p):
                out.append(re.escape(p[i + 1]))
                i += 2
                continue
            if c == "%":
                out.append(".*")
            elif c == "_":
                out.append(".")
            else:
                out.append(re.escape(c))
            i += 1
        return re.compile("(?s)^" + "".join(out) + "$")

    def _eval(self, vals, ctx):
        a = vals[0]
        if not ctx.is_device:
            rx = self._regex()
            data = np.array([bool(rx.match(s)) if v else False
                             for s, v in zip(a.data, a.validity)], bool)
            return ctx.canonical(data, a.validity, T.BooleanType())
        segments = self._segments()
        if segments is None:
            raise NotImplementedError(
                "LIKE with _ or an escape character is host-only")
        xp = ctx.xp
        head, middle, tail = segments
        if middle is None:  # no %: the whole string
            data = _starts_with(a.data, a.lengths, head, xp) \
                & (a.lengths == len(head))
            return ctx.canonical(data, a.validity, T.BooleanType())
        data = _starts_with(a.data, a.lengths, head, xp)
        end = xp.full(a.data.shape[0], len(head), dtype=np.int32) \
            if head else None
        for seg in middle:
            found, end = _first_after(a.data, a.lengths, seg, end, xp)
            data = data & found
        data = data & _ends_with(a.data, a.lengths, tail, xp, after=end)
        return ctx.canonical(data, a.validity, T.BooleanType())


class StringReplace(Expression):
    """replace(str, search, replace) with literal search — host-only for
    now (device literal replace lands with the breadth pass)."""
    sql_name = "StringReplace"

    def __init__(self, child: Expression, search: Expression,
                 replace: Expression):
        self.children = (child, search, replace)

    @property
    def dtype(self):
        return T.StringType()

    @property
    def device_supported(self):
        return False

    def _eval(self, vals, ctx):
        a, s, r = vals
        validity = a.validity & s.validity & r.validity
        out = np.empty(ctx.capacity, dtype=object)
        for i in range(ctx.capacity):
            if validity[i]:
                out[i] = a.data[i].replace(s.data[i], r.data[i]) \
                    if s.data[i] else a.data[i]
            else:
                out[i] = None
        return Val(out, validity, None, T.StringType())


# ---------------------------------------------------------------------------
# round-3 breadth (reference stringFunctions.scala GpuStringLocate /
# GpuConcatWs / GpuSubstringIndex / GpuInitCap / GpuStringLPad/RPad /
# GpuStringRepeat). Device kernels where the byte-matrix layout maps
# cleanly; pad/repeat/initcap are host-tagged (unicode-width semantics).
# ---------------------------------------------------------------------------

class ConcatWs(Expression):
    """concat_ws(sep, s1, s2, ...): null inputs are SKIPPED (no
    separator); result is null only when sep is null."""

    sql_name = "ConcatWs"

    def __init__(self, separator: str, *children: Expression):
        self.children = tuple(children)
        self.separator = separator

    def with_new_children(self, children):
        return ConcatWs(self.separator, *children)

    @property
    def dtype(self):
        return T.StringType()

    @property
    def nullable(self):
        return False

    def _eval(self, vals, ctx):
        xp = ctx.xp
        if not ctx.is_device:
            out = np.empty(ctx.capacity, dtype=object)
            for i in range(ctx.capacity):
                parts = [str(v.data[i]) for v in vals if v.validity[i]]
                out[i] = self.separator.join(parts)
            return Val(out, ctx.row_mask.copy(), None, T.StringType())
        sep = ctx._const_string(self.separator, ctx.row_mask)
        data = xp.zeros((ctx.capacity, 1), np.uint8)
        lengths = xp.zeros(ctx.capacity, np.int32)
        have_any = xp.zeros(ctx.capacity, bool)
        for v in vals:
            need_sep = have_any & v.validity
            sep_len = xp.where(need_sep, sep.lengths, 0)
            data, lengths = _concat2_device(data, lengths, sep.data, sep_len, xp)
            piece_len = xp.where(v.validity, v.lengths, 0)
            data, lengths = _concat2_device(data, lengths, v.data, piece_len, xp)
            have_any = have_any | v.validity
        validity = ctx.row_mask
        return ctx.canonical(data, validity, T.StringType(), lengths)


class StringLocate(Expression):
    """locate(substr, str[, start]): 1-based character position of the
    first occurrence at/after ``start``; 0 when absent; null inputs ->
    null (start is a literal int)."""

    sql_name = "StringLocate"

    def __init__(self, substr: Expression, string: Expression,
                 start: int = 1):
        self.children = (substr, string)
        self.start = start

    def with_new_children(self, children):
        return StringLocate(children[0], children[1], self.start)

    @property
    def dtype(self):
        return T.IntegerType()

    def _eval(self, vals, ctx):
        sub, s = vals
        xp = ctx.xp
        validity = sub.validity & s.validity
        if not ctx.is_device:
            out = np.zeros(ctx.capacity, np.int32)
            for i in range(ctx.capacity):
                if not validity[i]:
                    continue
                if self.start < 1:
                    out[i] = 0
                    continue
                out[i] = str(s.data[i]).find(str(sub.data[i]),
                                             self.start - 1) + 1
            return ctx.canonical(out, validity, T.IntegerType())
        if self.start < 1:
            return ctx.canonical(xp.zeros(ctx.capacity, np.int32), validity,
                                 T.IntegerType())
        w = s.data.shape[1]
        ws = sub.data.shape[1]
        j = xp.arange(w, dtype=np.int32)[None, :]
        # match[i, o] = bytes o..o+sublen match the needle
        match = xp.ones((ctx.capacity, w), bool)
        for k in range(ws):
            idx = xp.clip(j + k, 0, w - 1)
            sv = xp.take_along_axis(s.data, idx, axis=1)
            inside = k < sub.lengths[:, None]
            eq = sv == sub.data[:, k][:, None]
            valid_pos = (j + k) < s.lengths[:, None]
            match = match & xp.where(inside, eq & valid_pos, True)
        match = match & (j + sub.lengths[:, None] <= s.lengths[:, None])
        # character index of each byte + start filter (both char-based)
        starts = _char_starts(s.data, s.lengths, xp)
        char_idx = xp.cumsum(starts.astype(np.int32), axis=1) - 1
        match = match & starts & (char_idx >= (self.start - 1))
        empty = sub.lengths == 0
        found = xp.any(match, axis=1)
        first_byte = xp.argmax(match, axis=1)
        pos = xp.take_along_axis(char_idx, first_byte[:, None],
                                 axis=1)[:, 0] + 1
        nchars = xp.sum(starts, axis=1).astype(np.int32)
        out = xp.where(empty,
                       xp.where(self.start - 1 <= nchars, self.start, 0),
                       xp.where(found, pos, 0)).astype(np.int32)
        return ctx.canonical(out, validity, T.IntegerType())


class SubstringIndex(Expression):
    """substring_index(str, delim, count): prefix up to the count-th
    delimiter (suffix after |count|-th-from-end when count < 0);
    single-byte delimiters on device."""

    sql_name = "SubstringIndex"

    def __init__(self, child: Expression, delim: str, count: int):
        self.children = (child,)
        self.delim = delim
        self.count = count

    def with_new_children(self, children):
        return SubstringIndex(children[0], self.delim, self.count)

    @property
    def dtype(self):
        return T.StringType()

    @property
    def device_supported(self):
        return len(self.delim.encode("utf-8")) == 1

    def _eval(self, vals, ctx):
        a = vals[0]
        xp = ctx.xp
        if not ctx.is_device:
            out = np.empty(ctx.capacity, dtype=object)
            for i in range(ctx.capacity):
                if not a.validity[i]:
                    out[i] = None
                    continue
                s = str(a.data[i])
                c = self.count
                if c == 0 or not self.delim:
                    out[i] = ""
                elif c > 0:
                    out[i] = self.delim.join(s.split(self.delim)[:c])
                else:
                    out[i] = self.delim.join(s.split(self.delim)[c:])
            return Val(out, a.validity, None, T.StringType())
        w = a.data.shape[1]
        d = self.delim.encode("utf-8")[0]
        j = xp.arange(w, dtype=np.int32)[None, :]
        is_d = (a.data == np.uint8(d)) & (j < a.lengths[:, None])
        cum = xp.cumsum(is_d.astype(np.int32), axis=1)
        ndelim = xp.where(a.lengths > 0, cum[:, -1], 0) \
            if w > 0 else xp.zeros(ctx.capacity, np.int32)
        c = self.count
        if c == 0:
            return ctx.canonical(xp.zeros_like(a.data), a.validity,
                                 T.StringType(), xp.zeros_like(a.lengths))
        if c > 0:
            # end before the c-th delimiter (whole string if fewer)
            hit = is_d & (cum == c)
            found = xp.any(hit, axis=1)
            endb = xp.where(found, xp.argmax(hit, axis=1).astype(np.int32),
                            a.lengths)
            new_len = endb
            keep = j < new_len[:, None]
            data = xp.where(keep, a.data, 0)
            return ctx.canonical(data, a.validity, T.StringType(), new_len)
        # c < 0: start after the (ndelim + c)-th delimiter from the left
        k = ndelim + c + 1          # 1-based index of the delimiter
        hit = is_d & (cum == k[:, None])
        found = (k > 0) & xp.any(hit, axis=1)
        startb = xp.where(found,
                          xp.argmax(hit, axis=1).astype(np.int32) + 1, 0)
        new_len = (a.lengths - startb).astype(np.int32)
        idx = xp.clip(startb[:, None] + j, 0, w - 1)
        shifted = xp.take_along_axis(a.data, idx, axis=1)
        keep = j < new_len[:, None]
        data = xp.where(keep, shifted, 0)
        return ctx.canonical(data, a.validity, T.StringType(), new_len)


class _HostOnlyStringUnary(Expression):
    def __init__(self, child: Expression):
        self.children = (child,)

    @property
    def dtype(self):
        return T.StringType()

    @property
    def device_supported(self):
        return False


class InitCap(_HostOnlyStringUnary):
    """initcap: first letter of each word upper, rest lower (host-only:
    Java title-casing is unicode-table driven)."""

    sql_name = "InitCap"

    def _eval(self, vals, ctx):
        a = vals[0]
        out = np.empty(ctx.capacity, dtype=object)
        for i in range(ctx.capacity):
            if not a.validity[i]:
                out[i] = None
                continue
            s = str(a.data[i]).lower()
            out[i] = "".join(
                ch.upper() if k == 0 or s[k - 1] == " " else ch
                for k, ch in enumerate(s))
        return Val(out, a.validity, None, T.StringType())


class _PadBase(Expression):
    def __init__(self, child: Expression, length: int, pad: str = " "):
        self.children = (child,)
        self.length = length
        self.pad = pad

    def with_new_children(self, children):
        return type(self)(children[0], self.length, self.pad)

    @property
    def dtype(self):
        return T.StringType()

    @property
    def device_supported(self):
        return False  # char-width pad semantics are host-only for now

    def _eval(self, vals, ctx):
        a = vals[0]
        out = np.empty(ctx.capacity, dtype=object)
        for i in range(ctx.capacity):
            out[i] = self._pad(str(a.data[i])) if a.validity[i] else None
        return Val(out, a.validity, None, T.StringType())

    def _pad(self, s: str) -> str:
        n = max(self.length, 0)  # Spark: negative pad length -> ''
        if len(s) >= n:
            return s[:n]
        if not self.pad:
            return s
        fill = (self.pad * n)[: n - len(s)]
        return self._join(s, fill)


class StringLPad(_PadBase):
    sql_name = "StringLPad"

    def _join(self, s, fill):
        return fill + s


class StringRPad(_PadBase):
    sql_name = "StringRPad"

    def _join(self, s, fill):
        return s + fill


class StringRepeat(Expression):
    """repeat(str, n) (host-only: output width is data-dependent)."""

    sql_name = "StringRepeat"

    def __init__(self, child: Expression, times: Expression):
        self.children = (child, times)

    @property
    def dtype(self):
        return T.StringType()

    @property
    def device_supported(self):
        return False

    def _eval(self, vals, ctx):
        a, n = vals
        validity = a.validity & n.validity
        out = np.empty(ctx.capacity, dtype=object)
        for i in range(ctx.capacity):
            out[i] = str(a.data[i]) * max(int(n.data[i]), 0) \
                if validity[i] else None
        return Val(out, validity, None, T.StringType())


class Hex(Expression):
    """hex(n): uppercase hex of a long, leading zeros stripped, negative
    values as 16-digit two's complement — Spark Hex semantics
    (reference mathExpressions GpuHex; the mortgage benchmark
    anonymizes loan ids with hex(hash(id))).  Device path builds the
    byte matrix from nibbles in one vectorized program."""

    sql_name = "Hex"

    def __init__(self, child: Expression):
        self.children = (child,)

    @property
    def dtype(self):
        return T.StringType()

    def coerced(self):
        from spark_rapids_tpu.expr.cast import Cast
        t = self.children[0].dtype
        if t.integral and not isinstance(t, T.LongType):
            return Hex(Cast(self.children[0], T.LongType()))
        if not isinstance(t, T.LongType):
            raise TypeError(f"hex over {t} is not supported")
        return self

    def _eval(self, vals, ctx):
        a = vals[0]
        if not ctx.is_device:
            out = np.empty(ctx.capacity, dtype=object)
            for i in range(ctx.capacity):
                out[i] = format(int(a.data[i]) & 0xFFFFFFFFFFFFFFFF,
                                "X") if a.validity[i] else None
            return Val(out, a.validity.copy(), None, T.StringType())
        xp = ctx.xp
        v = a.data.astype(np.int64)
        shifts = xp.arange(60, -1, -4, dtype=np.int64)   # MSB nibble first
        nib = (v[:, None] >> shifts[None, :]) & 0xF
        chars = xp.where(nib < 10, nib + 48, nib + 55).astype(np.uint8)
        nz = nib != 0
        first = xp.argmax(nz, axis=1)
        first = xp.where(xp.any(nz, axis=1), first, 15)
        lengths = (16 - first).astype(np.int32)
        idx = xp.clip(first[:, None] + xp.arange(16)[None, :], 0, 15)
        data = xp.take_along_axis(chars, idx, axis=1)
        data = xp.where(a.validity[:, None], data, 0)
        return ctx.canonical(data, a.validity,
                             T.StringType(),
                             xp.where(a.validity, lengths, 0))
