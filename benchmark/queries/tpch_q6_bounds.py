"""TPC-H Q6's ``where`` clause unchanged, then the kept rows counted
and their prices summed by discount, in the discount's order: three
rows (0.05, 0.06, 0.07).

It is in the cell's traffic beside Q6 for the comparison's sake.  Q6's
one sum is compared at rel 1e-5, and a single dropped row in 1.07M is a
tenth of that; these counts are integers, so one row lost at a bound
(``l_discount >= 0.05`` on a double rebuilt a hair under 0.05), or a
discount that splits into two groups because two batches rebuilt it
differently, fails the collect.  The sums are whole cents, so they take
the exact path of ``ops/cents.py`` and its ``from_cents``.

The filter is benchmark/queries/tpch_q6.py's, character for character:
the same fused stage over the same four columns at the same sizes."""
import datetime
import os

from spark_rapids_tpu.expr.aggregates import CountStar, Sum
from spark_rapids_tpu.expr.core import col, lit

#: the tables the query scans and the columns it names: Q6's
TABLES = {
    "lineitem": ["l_extendedprice", "l_discount", "l_shipdate",
                 "l_quantity"],
}


def build(session, data_dir: str):
    li = session.read_parquet(os.path.join(data_dir, "lineitem"),
                              columns=TABLES["lineitem"])
    return li.where((col("l_shipdate") >= lit(datetime.date(1994, 1, 1)))
                    & (col("l_shipdate") < lit(datetime.date(1995, 1, 1)))
                    & (col("l_discount") >= lit(0.05))
                    & (col("l_discount") <= lit(0.07))
                    & (col("l_quantity") < lit(24.0))) \
        .group_by("l_discount") \
        .agg(CountStar().alias("lines"),
             Sum(col("l_extendedprice")).alias("price")) \
        .order_by(("l_discount", True))
