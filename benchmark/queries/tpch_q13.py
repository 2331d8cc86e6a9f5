"""TPC-H Q13, customer distribution, as DataFrame code, from
spark_rapids_tpu/bench/tpch_queries.py::q13: customers counted by how
many orders they have placed, orders whose comment holds WORD1 and
later WORD2 left out (the validation values, ``special`` and
``requests``); a customer without such an order counts 0.

The pattern is evaluated on the device over the comment bytes of every
scanned order: no predicate is handed to the Parquet reader and nothing
is filtered or encoded on the host.  Nothing of Q13 is cut or
rewritten: the left outer join stays a left outer join (its right side,
the filtered orders, is the build), ``count(o_orderkey)`` counts the
non-NULL keys."""
import os

from spark_rapids_tpu.expr.aggregates import Count, CountStar
from spark_rapids_tpu.expr.core import col
# An engine that evaluates a multi-segment LIKE on the device, or no run
# at all: before it did, the plan below held a host-fallback filter,
# which every cell's test mode refuses — but only after 15M orders had
# been generated and the reference computed.  Such an engine fails here,
# in the first seconds and before any data is made.
from spark_rapids_tpu.expr.strings import string_matches  # noqa: F401

#: the tables the query scans and the columns it names
TABLES = {
    "customer": ["c_custkey"],
    "orders": ["o_orderkey", "o_custkey", "o_comment"],
}

#: the specification's validation values of WORD1 and WORD2
WORD1, WORD2 = "special", "requests"


def build(session, data_dir: str):
    def t(table):
        return session.read_parquet(os.path.join(data_dir, table),
                                    columns=TABLES[table])
    orders = t("orders") \
        .where(~col("o_comment").like(f"%{WORD1}%{WORD2}%")) \
        .select(col("o_custkey"), col("o_orderkey"))
    counts = t("customer") \
        .join(orders, on=[("c_custkey", "o_custkey")], how="left") \
        .group_by("c_custkey") \
        .agg(Count(col("o_orderkey")).alias("c_count"))
    return counts.group_by("c_count") \
        .agg(CountStar().alias("custdist")) \
        .order_by(("custdist", False), ("c_count", False))
