"""Aggregate function expressions (reference AggregateFunctions.scala:531).

Declarative nodes: they do not evaluate elementwise.  The aggregate execs
(CPU oracle and TPU) lower each into the reference's three-phase shape
(aggregate.scala update/merge/final aggregates):

* ``update_ops``  — per-batch segmented ops over the input column(s);
* ``merge_ops``   — ops combining partial results across batches/partitions;
* ``final_expr``  — expression over the intermediate columns producing the
  result (e.g. Average = sum / count with double division, null on 0 count).

The intermediate layout is one column per update op.
"""
from __future__ import annotations

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.core import Expression, BoundReference, Literal

__all__ = ["AggregateFunction", "Sum", "Count", "CountStar", "Min", "Max",
           "Percentile",
           "Average", "MeanOf", "First", "Last", "CountDistinct", "stddev_samp",
           "is_aggregate", "has_aggregate"]


class AggregateFunction(Expression):
    """Base for aggregate functions. ``children[0]`` is the input (absent
    for COUNT(*))."""

    #: segmented op names for the update phase, one intermediate column each
    update_ops: tuple[str, ...] = ()
    #: op names merging intermediates (same arity as update_ops)
    merge_ops: tuple[str, ...] = ()

    def __init__(self, child: Expression):
        self.children = (child,)

    @property
    def input(self) -> Expression:
        return self.children[0]

    def intermediate_types(self) -> list[T.DataType]:
        raise NotImplementedError

    def final_expr(self, offsets: list[int]) -> Expression:
        """Expression over intermediate columns bound at ``offsets``."""
        raise NotImplementedError

    def _eval(self, vals, ctx):
        raise TypeError(f"{self.sql_name} must be planned by an aggregate "
                        "exec, not evaluated elementwise")


def is_aggregate(e: Expression) -> bool:
    return isinstance(e, AggregateFunction)


def has_aggregate(e: Expression) -> bool:
    if is_aggregate(e):
        return True
    return any(has_aggregate(c) for c in e.children)


class Sum(AggregateFunction):
    """Spark Sum: long for integral input, double for fractional; null on
    empty/all-null input; integral overflow wraps (non-ANSI)."""
    sql_name = "Sum"
    update_ops = ("sum",)
    merge_ops = ("sum",)

    @property
    def dtype(self):
        return T.LongType() if self.input.dtype.integral else T.DoubleType()

    @property
    def nullable(self):
        return True

    def coerced(self):
        from spark_rapids_tpu.expr.cast import Cast
        t = self.input.dtype
        if t.integral and not isinstance(t, T.LongType):
            return Sum(Cast(self.input, T.LongType()))
        if isinstance(t, T.FloatType):
            return Sum(Cast(self.input, T.DoubleType()))
        if not t.numeric:
            raise TypeError(f"sum over {t}")
        return self

    def intermediate_types(self):
        return [self.dtype]

    def final_expr(self, offsets):
        return BoundReference(offsets[0], self.dtype, True)


class Count(AggregateFunction):
    sql_name = "Count"
    update_ops = ("count",)
    merge_ops = ("sum",)

    @property
    def dtype(self):
        return T.LongType()

    @property
    def nullable(self):
        return False

    def intermediate_types(self):
        return [T.LongType()]

    def final_expr(self, offsets):
        from spark_rapids_tpu.expr.conditional import Coalesce
        return Coalesce(BoundReference(offsets[0], T.LongType(), True),
                        Literal(0, T.LongType()))


class CountStar(Count):
    sql_name = "CountStar"
    update_ops = ("count_star",)

    def __init__(self):
        self.children = ()

    @property
    def input(self):
        return None

    def with_new_children(self, children):
        return self

    def __repr__(self):
        return "count(*)"


class Min(AggregateFunction):
    sql_name = "Min"
    update_ops = ("min",)
    merge_ops = ("min",)

    @property
    def dtype(self):
        return self.input.dtype

    def intermediate_types(self):
        return [self.dtype]

    def final_expr(self, offsets):
        return BoundReference(offsets[0], self.dtype, True)


class Max(AggregateFunction):
    sql_name = "Max"
    update_ops = ("max",)
    merge_ops = ("max",)

    @property
    def dtype(self):
        return self.input.dtype

    def intermediate_types(self):
        return [self.dtype]

    def final_expr(self, offsets):
        return BoundReference(offsets[0], self.dtype, True)


class Average(AggregateFunction):
    """Spark Average: double result = sum/count, null when count == 0."""
    sql_name = "Average"
    update_ops = ("sum", "count")
    merge_ops = ("sum", "sum")

    @property
    def dtype(self):
        return T.DoubleType()

    def coerced(self):
        from spark_rapids_tpu.expr.cast import Cast
        t = self.input.dtype
        if not t.numeric:
            raise TypeError(f"avg over {t}")
        if not isinstance(t, T.DoubleType):
            return Average(Cast(self.input, T.DoubleType()))
        return self

    def intermediate_types(self):
        return [T.DoubleType(), T.LongType()]

    def final_expr(self, offsets):
        s = BoundReference(offsets[0], T.DoubleType(), True)
        c = BoundReference(offsets[1], T.LongType(), True)
        return MeanOf(s, c)


class MeanOf(Expression):
    """Average's last step, ``sum / count`` (double, long): null when
    the count is zero — exactly Spark avg — and, where the sum is whole
    cents, divided in lowest terms so that equal averages are equal
    doubles (ops/cents.py)."""
    sql_name = "MeanOf"

    def __init__(self, total: Expression, count: Expression):
        self.children = (total, count)

    @property
    def dtype(self):
        return T.DoubleType()

    def _eval(self, vals, ctx):
        from spark_rapids_tpu.ops import cents
        s, c = vals
        xp = ctx.xp
        some = c.data > 0
        data = cents.mean(xp, s.data, xp.where(some, c.data, 1))
        return ctx.canonical(data, s.validity & c.validity & some,
                             self.dtype)


class CountDistinct(Expression):
    """count(DISTINCT e[, e2, ...]) — a marker rewritten by
    ``GroupedData.agg`` into dedupe-then-count plans (Spark plans the same
    via Expand + two-phase aggregation).  It never reaches an aggregate
    exec directly."""
    sql_name = "CountDistinct"

    def __init__(self, *children: Expression):
        assert children, "count(distinct) needs at least one expression"
        self.children = tuple(children)

    def with_new_children(self, children):
        return CountDistinct(*children)

    @property
    def dtype(self):
        return T.LongType()

    @property
    def nullable(self):
        return False

    def _eval(self, vals, ctx):
        raise TypeError(
            "count(distinct) is only valid directly inside "
            "GroupedData.agg(...), which rewrites it; it cannot be "
            "evaluated elementwise or nested in other expressions")


def stddev_samp(e: Expression) -> Expression:
    """Sample standard deviation as composed aggregates:
    sqrt((sum(x^2) - sum(x)^2/n) / (n-1)); null on empty input, NaN for a
    single row (Spark CentralMomentAgg semantics).  Composed from
    Sum/Count so the three-phase aggregate machinery needs no new op
    (reference expresses stddev over cuDF's M2; here the sum-of-squares
    form keeps the segmented-op set minimal and differential tests
    compare doubles approximately)."""
    from spark_rapids_tpu.expr.cast import Cast
    from spark_rapids_tpu.expr.conditional import If
    from spark_rapids_tpu.expr.math_ops import Sqrt
    from spark_rapids_tpu.expr.predicates import EqualTo
    from spark_rapids_tpu.expr.predicates import LessThan
    d = Cast(e, T.DoubleType())
    n = Count(d)
    nd = Cast(n, T.DoubleType())
    s = Sum(d)
    s2 = Sum(d * d)
    var = (s2 - s * s / nd) / (nd - Literal(1.0, T.DoubleType()))
    # catastrophic cancellation on a constant column can leave var a tiny
    # negative; Spark's M2 form returns exactly 0.0 there, so clamp
    # (LessThan is false for NaN, which passes through untouched)
    zero = Literal(0.0, T.DoubleType())
    var = If(LessThan(var, zero), zero, var)
    return If(EqualTo(n, Literal(1, T.LongType())),
              Literal(float("nan"), T.DoubleType()), Sqrt(var))


class First(AggregateFunction):
    sql_name = "First"
    update_ops = ("first",)
    merge_ops = ("first",)

    def __init__(self, child: Expression, ignore_nulls: bool = False):
        self.children = (child,)
        self.ignore_nulls = ignore_nulls
        if ignore_nulls:
            self.update_ops = ("first_non_null",)
            self.merge_ops = ("first_non_null",)

    def with_new_children(self, children):
        return First(children[0], self.ignore_nulls)

    @property
    def dtype(self):
        return self.input.dtype

    def intermediate_types(self):
        return [self.dtype]

    def final_expr(self, offsets):
        return BoundReference(offsets[0], self.dtype, True)


class Last(AggregateFunction):
    sql_name = "Last"
    update_ops = ("last",)
    merge_ops = ("last",)

    def __init__(self, child: Expression, ignore_nulls: bool = False):
        self.children = (child,)
        self.ignore_nulls = ignore_nulls
        if ignore_nulls:
            self.update_ops = ("last_non_null",)
            self.merge_ops = ("last_non_null",)

    def with_new_children(self, children):
        return Last(children[0], self.ignore_nulls)

    @property
    def dtype(self):
        return self.input.dtype

    def intermediate_types(self):
        return [self.dtype]

    def final_expr(self, offsets):
        return BoundReference(offsets[0], self.dtype, True)


class Percentile(AggregateFunction):
    """Exact percentile with linear interpolation at q*(n-1) (Spark
    Percentile, ObjectHashAggregate-backed in the reference plugin's
    fallback list).  HOLISTIC: there is no mergeable intermediate — the
    planner aggregates the whole input in one pass (exec/aggregate.py
    _holistic), so partial/final split and mesh lowering are refused."""

    sql_name = "Percentile"
    update_ops = ("percentile",)
    merge_ops = ()          # no merge exists: holistic
    requires_complete = True

    def __init__(self, child: Expression, q: float):
        super().__init__(child)
        if not (0.0 <= float(q) <= 1.0):
            raise ValueError(f"percentile fraction must be in [0,1]: {q}")
        self.q = float(q)

    def with_new_children(self, children):
        return Percentile(children[0], self.q)

    @property
    def dtype(self):
        return T.DoubleType()

    def coerced(self):
        from spark_rapids_tpu.expr.cast import Cast
        t = self.input.dtype
        if not t.numeric:
            raise TypeError(f"percentile over {t}")
        if not isinstance(t, T.DoubleType):
            return Percentile(Cast(self.input, T.DoubleType()), self.q)
        return self

    def intermediate_types(self):
        return [T.DoubleType()]

    def final_expr(self, offsets):
        return BoundReference(offsets[0], T.DoubleType(), True)

    def __repr__(self):
        return f"Percentile({self.children[0]!r}, {self.q})"
