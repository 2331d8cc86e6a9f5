"""ops/cents.py: doubles that are whole cents are summed as integers
and averaged in lowest terms, so that a sum does not hang on the order
of its rows and equal averages are equal doubles — on the device path
and in the host oracle alike."""
from fractions import Fraction

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_f64 import _Int64s, _Pair, _PairXP
from spark_rapids_tpu import types as T
from spark_rapids_tpu.host.batch import HostBatch
from spark_rapids_tpu.ops import cents
from spark_rapids_tpu.ops import host_kernels as hk
from spark_rapids_tpu.ops.segmented import (AggSpec, group_by_update,
                                            sorted_group_by)

SCHEMA = T.Schema([T.StructField("k", T.IntegerType()),
                   T.StructField("v", T.DoubleType())])
SPECS = [AggSpec("sum", 1), AggSpec("count", 1), AggSpec("avg", 1)]


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jax"])
def test_whole_cents_are_told_from_other_doubles(xp):
    n = np.array([0, 1, -1, 57, 73576, -(1 << 37), (1 << 38) - 1])
    c, whole = cents.as_cents(xp, xp.asarray(n / 100.0))
    assert np.asarray(whole).all() and (np.asarray(c) == n).all()
    other = xp.asarray([1 / 3, 0.125, 0.1 + 1e-12, np.nan, np.inf, -np.inf,
                        1e300, float(1 << 38) / 100])
    c, whole = cents.as_cents(xp, other)
    assert not np.asarray(whole).any() and not np.asarray(c).any()


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jax"])
def test_equal_rationals_average_to_equal_doubles(xp):
    rng = np.random.default_rng(3)
    n = rng.integers(-10**7, 10**7, 20000)
    n[:500] = rng.integers(-(1 << 42), 1 << 42, 500)
    k = rng.integers(1, 60, 20000).astype(np.int64)
    k[:200] = rng.integers(1, (1 << 31) - 1, 200)
    m = rng.integers(2, 40, 20000)
    keep = (np.abs(n * m) < 1 << 43) & (k * m < 1 << 31)
    a = np.asarray(cents.mean(xp, xp.asarray(n / 100.0), xp.asarray(k)))
    b = np.asarray(cents.mean(xp, xp.asarray(n * m / 100.0),
                              xp.asarray(k * m)))
    assert (a[keep] == b[keep]).all()
    # plain division does not: that is what the reduction is for
    assert ((n * m / 100.0) / (k * m) != (n / 100.0) / k)[keep].any()
    want = [float(Fraction(int(p), 100 * int(q)))
            for p, q in zip(n[:2000], k[:2000])]
    assert (a[:2000] == np.array(want)).all()       # rounded once


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jax"])
def test_mean_of_other_doubles_is_the_plain_quotient(xp):
    t = np.array([1 / 3, np.nan, np.inf, 1e300, 5e13, 7.25])
    k = np.array([7, 1, 2, 3, 1 << 33, 2], np.int64)
    got = np.asarray(cents.mean(xp, xp.asarray(t), xp.asarray(k)))
    np.testing.assert_array_equal(got[:5], (t / k)[:5])
    assert got[5] == 3.625


def _group_by(keys, vals, fn):
    hb = HostBatch.from_pydict({"k": keys.astype(np.int32), "v": vals},
                               SCHEMA)
    res, *_ = jax.tree.leaves((fn(hb.to_device(capacity=1 << 12)),),
                              is_leaf=lambda x: hasattr(x, "columns"))
    nr = int(res.num_rows)
    return [np.asarray(c.data)[:nr] for c in res.columns]


@pytest.mark.parametrize("groups", [3, 700], ids=["dense", "sorted"])
def test_group_sums_do_not_hang_on_row_order(groups):
    rng = np.random.default_rng(groups)
    keys = rng.integers(0, groups, 3000)
    vals = rng.integers(-10**6, 10**6, 3000) / 100.0
    perm = rng.permutation(3000)
    update = jax.jit(lambda b: group_by_update(b, [0], SPECS)[0])
    k1, s1, c1, m1 = _group_by(keys, vals, update)
    k2, s2, c2, m2 = _group_by(keys[perm], vals[perm], update)
    assert (s1 == s2).all() and (m1 == m2).all()
    total = np.zeros(groups, np.int64)
    np.add.at(total, keys, np.rint(vals * 100).astype(np.int64))
    assert (s1 == total[k1] / 100.0).all()          # exact, rounded once
    # the host oracle sums the same way
    host = hk.host_group_by(
        HostBatch.from_pydict({"k": keys.astype(np.int32), "v": vals},
                              SCHEMA), [0], SPECS)
    assert (host.columns[1].data == s1).all()
    assert (host.columns[3].data == m1).all()
    # and so does a merge of partial sums
    buf = T.Schema([T.StructField("k", T.IntegerType()),
                    T.StructField("s", T.DoubleType())])
    hb = HostBatch.from_pydict({"k": np.concatenate([k1, k2]),
                                "s": np.concatenate([s1, s2])}, buf)
    merged = jax.jit(lambda b: sorted_group_by(b, [0], [AggSpec("sum", 1)]))(
        hb.to_device(capacity=1 << 11))
    ms = np.asarray(merged.columns[1].data)[:int(merged.num_rows)]
    assert (ms == 2 * total[k1] / 100.0).all()


def test_a_group_with_one_other_double_sums_as_doubles():
    keys = np.array([0, 0, 0, 1, 1])
    vals = np.array([0.1, 0.2, 1 / 3, 0.1, 0.2])
    update = jax.jit(lambda b: group_by_update(b, [0], SPECS)[0])
    _, s, _, m = _group_by(keys, vals, update)
    assert s[0] == pytest.approx(0.1 + 0.2 + 1 / 3, rel=1e-15)
    assert s[1] == 30 / 100.0 and m[1] == 0.15   # not (0.1 + 0.2) / 2


# ------------------------------------------- the chip's f64, on the CPU
# (tests/chip_f64.py: the emulator test_wirecodec.py shares)


def test_pair_arithmetic_is_the_chips():
    # what is known of the chip: real float64 in, the same out
    x = np.array([300.0, 0.05, 1 / 3, 499095.24])
    assert np.abs(_Pair.of(x).stored() - x).max() <= 2.0 ** -47 * x.max()
    assert (_Pair.of(np.array([300.0, 1e6])).lo == 0).all()
    # and TPC-H Q6's loss (PERF.md Findings PR 23), on record so that
    # nobody restores it: five hundredths rebuilt as ``5 * 0.01`` are
    # under the literal 0.05, six are not under 0.06 — ``l_discount >=
    # 0.05`` dropped the rows at exactly 0.05 until PR 46
    old = _Int64s([4, 5, 6, 7]).astype(_PairXP.float64) * 0.01
    assert (old >= 0.05).tolist() == [False, False, True, True]
    assert (np.array([4, 5, 6, 7]) * 0.01 >= 0.05).tolist() \
        == [False, True, True, True]                # real float64 keeps them
    # from_cents builds the pair the host's double turns into
    d = cents.from_cents(_PairXP, _Int64s([4, 5, 6, 7]))
    assert (d >= 0.05).tolist() == [False, True, True, True]
    assert (d <= 0.07).tolist() == [True, True, True, True]
    assert (d == np.array([4, 5, 6, 7]) / 100.0).all()


@pytest.mark.parametrize("scale", [1, 100], ids=["300", "30000"])
def test_a_sum_at_a_whole_literal_compares_exactly_in_f32_pairs(scale):
    """TPC-H Q18's ``having sum(l_quantity) > 300``: the sum is 30000
    hundredths given back as the double ``30000 / 100``, and 160 orders
    of SF1 sum to exactly 300.  In the chip's arithmetic that double is
    the pair (300.0, 0.0): not greater than the literal, and not less."""
    c = np.array([29999, 30000, 30001]) * scale
    lit = 300.0 * scale
    x = cents.from_cents(_PairXP, _Int64s(c))
    assert (x > lit).tolist() == [False, False, True]
    assert (x >= lit).tolist() == [False, True, True]
    assert x.hi[1] == lit and x.lo[1] == 0.0
    # every whole number of dollars up to 2^22 comes back as itself (past
    # 2^29 hundredths the cross terms leave a last-bit remainder: a sum
    # over five million dollars against a whole literal is not pinned)
    dollars = np.arange(1, 1 << 22, 97, dtype=np.int64)
    whole = cents.from_cents(_PairXP, _Int64s(dollars * 100))
    assert (whole.hi == dollars).all() and (whole.lo == 0).all()
    # and real float64 agrees at the boundary
    assert (cents.from_cents(np, c) > lit).tolist() == [False, False, True]


@pytest.mark.parametrize("limit", [200_000, 10**7, 1 << 31, 1 << 44],
                         ids=["2e5", "1e7", "2^31", "2^44"])
def test_from_cents_is_the_hosts_double_in_f32_pairs(limit):
    """``from_cents(n)`` compares equal to ``device_put(n / 100.0)`` in
    the chip's arithmetic: every hundredth to 200,000, then seeded
    ``|n| < limit`` up to ``SUM_LIMIT``; and in real float64 (numpy,
    XLA:CPU) it is that double bit for bit."""
    rng = np.random.default_rng(limit % 1000)
    n = np.arange(-limit, limit + 1, dtype=np.int64) if limit == 200_000 \
        else rng.integers(-limit + 1, limit, 300_000)
    n[:6] = [0, 5, -5, limit - 1, 1 - limit, 100 << 24][:6]
    want = n / 100.0
    assert cents.from_cents(_PairXP, _Int64s(n)).is_the_pair_of(want)
    assert (cents.from_cents(np, n).view(np.int64)
            == want.view(np.int64)).all()
    got = jax.jit(lambda c: cents.from_cents(jnp, c))(jnp.asarray(n))
    assert (np.asarray(got).view(np.int64) == want.view(np.int64)).all()
