"""The device's LIKE (every pattern of literal segments and ``%``) and
its literal-needle StartsWith / EndsWith / Contains against Python's
``re`` and ``str`` methods, row by row: adversarial strings (segments
overlapping, the second only before the first, a match ending on the
width's last byte, a string of exactly the bucket's width, empty, NULL),
one to four segments, each anchor, and seeded random text.
"""
import re

import jax
import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.columnar.column import round_string_width
from spark_rapids_tpu.expr import bind, col, eval_device, lit
from spark_rapids_tpu.expr import strings as S
from spark_rapids_tpu.host.batch import HostBatch

SCHEMA = T.Schema([T.StructField("s", T.StringType())])

#: 32 bytes, a width bucket exactly: round_string_width(32) == 32
_FULL = "x" * 17 + "special" + "requests"

ADVERSARIAL = [
    "special requests", "specialrequests", "specialequests",
    "requests special", "requests special requests", "special", "requests",
    "", None, "s", "sspecial rrequests", "special request",
    "specialspecial requestsrequests", "the special packages requests nag",
    _FULL, _FULL[:-1], "x" * 24 + "requests", "special" + "x" * 25,
    "abcabcabc", "abc", "ab", "cab", "aXbXc", "a%b", "héllo wörld spécial",
    "spécial requests", "special\x00requests", "ababab", "aba", "b",
    "xspecialrequestsx", "requestsspecial", "speci alrequests", None,
]

PATTERNS = [
    "%special%requests%", "special%requests", "special%", "%requests",
    "%special%", "special requests", "", "%", "%%", "special%requests%",
    "%special%requests", "a%b%c", "%a%b%c%", "a%b%c%d", "%ab%ab%ab%",
    "%ab%ab%ab%ab%", "ab%ab", "aba%ba", "%abc%abc%abc", "abc%abc",
    "%é%ö%", "héllo%", "%spécial", "x%special%requests",
    "%" + "x" * 17 + "%requests", "%" + "y" * 40 + "%", "a%", "%b",
    "%special%special%requests%requests%",
]


def _like_re(pattern: str):
    return re.compile("(?s)^" + "".join(
        ".*" if c == "%" else re.escape(c) for c in pattern) + "$")


def _device(expr, strings) -> list:
    hb = HostBatch.from_pydict({"s": list(strings)}, SCHEMA)
    bound = bind(expr, SCHEMA)
    db = hb.to_device()
    out = jax.jit(lambda b: eval_device(bound, b))(db)
    batch = ColumnBatch([out], db.num_rows,
                        T.Schema([T.StructField("r", T.BooleanType())]))
    return HostBatch.from_device(batch).columns[0].to_list()


def _random_strings(seed: int, n: int = 400) -> list:
    rng = np.random.default_rng(seed)
    words = ["special", "requests", "spec", "ial", "request", "s", " ",
             "pending", "ab", "abc", "c", "é", "packages"]
    out = []
    for _ in range(n):
        k = int(rng.integers(0, 7))
        s = "".join(words[i] for i in rng.integers(0, len(words), k))
        out.append(None if rng.random() < 0.03 else s[:60])
    return out


@pytest.mark.parametrize("pattern", PATTERNS)
def test_like_agrees_with_re_on_adversarial_strings(pattern):
    expr = col("s").like(pattern)
    assert bind(expr, SCHEMA).device_supported
    rx = _like_re(pattern)
    want = [None if s is None else bool(rx.match(s)) for s in ADVERSARIAL]
    assert _device(expr, ADVERSARIAL) == want


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_like_agrees_with_re_on_random_strings(seed):
    strings = _random_strings(seed)
    for pattern in ("%special%requests%", "special%requests",
                    "%ab%c%", "s%s", "%ial%request%s%", "%é%"):
        rx = _like_re(pattern)
        want = [None if s is None else bool(rx.match(s)) for s in strings]
        assert _device(col("s").like(pattern), strings) == want, pattern


def test_a_match_may_end_on_the_last_byte_of_the_width():
    w = round_string_width(len(_FULL))
    assert w == len(_FULL.encode())   # the string fills its bucket
    got = _device(col("s").like("%special%requests"), [_FULL, _FULL[:-1]])
    assert got == [True, False]
    assert _device(col("s").like("%special%requests%"), [_FULL]) == [True]


def test_not_like_is_the_existing_not_and_null_stays_null():
    got = _device(~col("s").like("%special%requests%"),
                  ["special requests", "requests special", None, ""])
    assert got == [False, True, None, True]


def test_underscore_and_escape_stay_on_the_host():
    for pattern in ("h_llo", "100\\%", "%a_b%"):
        e = bind(col("s").like(pattern), SCHEMA)
        assert not e.device_supported
    hb = HostBatch.from_pydict({"s": ["hello", "100%"]}, SCHEMA)
    from spark_rapids_tpu.expr import eval_host
    assert eval_host(bind(col("s").like("h_llo"), SCHEMA),
                     hb).to_list() == [True, False]
    assert eval_host(bind(col("s").like("100\\%"), SCHEMA),
                     hb).to_list() == [False, True]


@pytest.mark.parametrize("cls,py", [
    (S.StartsWith, str.startswith), (S.EndsWith, str.endswith),
    (S.Contains, lambda s, x: x in s)])
@pytest.mark.parametrize("needle", [
    "special", "requests", "s", "", "ab", "é", "x" * 17 + "special",
    _FULL, _FULL + "y"])
def test_literal_needle_predicates_agree_with_str(cls, py, needle):
    strings = ADVERSARIAL + _random_strings(7, 100)
    want = [None if s is None else py(s, needle) for s in strings]
    assert _device(cls(col("s"), lit(needle)), strings) == want


def test_a_literal_needle_takes_no_gather_and_a_column_needle_keeps_its():
    """No literal-needle match slides the needle by gathers any more;
    a needle that is a column still does."""
    sch = T.Schema([T.StructField("s", T.StringType()),
                    T.StructField("t", T.StringType())])
    hb = HostBatch.from_pydict({"s": ["special requests"] * 8,
                                "t": ["requests"] * 8}, sch)
    db = hb.to_device()

    def gathers(expr):
        bound = bind(expr, sch)
        text = str(jax.make_jaxpr(lambda b: eval_device(bound, b))(db))
        return text.count("gather")

    for expr in (col("s").like("%special%requests%"),
                 col("s").like("special%requests"),
                 S.Contains(col("s"), lit("requests")),
                 S.StartsWith(col("s"), lit("special")),
                 S.EndsWith(col("s"), lit("requests"))):
        assert gathers(expr) == 0, expr
    assert gathers(S.Contains(col("s"), col("t"))) > 0
    hb2 = HostBatch.from_pydict(
        {"s": ["special requests", "abc", None], "t": ["requests", "d", "x"]},
        sch)
    bound = bind(S.Contains(col("s"), col("t")), sch)
    out = jax.jit(lambda b: eval_device(bound, b))(hb2.to_device())
    assert list(np.asarray(out.data)[:3] & np.asarray(out.validity)[:3]) \
        == [True, False, False]


def test_string_matches_names_the_matched_children():
    bound = bind(~col("s").like("%special%requests%")
                 & S.Contains(col("s"), lit("x")), SCHEMA)
    assert [repr(c) for c in S.string_matches(bound)] == ["#0:s", "#0:s"]
    assert S.string_matches(bind(S.Contains(col("s"), col("s")),
                                 SCHEMA)) == []
