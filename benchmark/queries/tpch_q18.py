"""TPC-H Q18, large volume customer, as DataFrame code, from
spark_rapids_tpu/bench/tpch_queries.py::q18: of all orders, those whose
line quantities sum to more than QUANTITY = 300 (the validation
parameter); for them customer name and key, order key, date, total
price and the summed quantity, the hundred dearest first.

The ``IN`` subquery stays IN the plan, as a semi-join of lineitem
against the aggregate's kept keys: an eager ``collect()`` here would
move a fact scan and a 1.5M-group aggregate out of the timed window.
Nothing of Q18 is cut or rewritten."""
import os

from spark_rapids_tpu.expr.aggregates import Sum
from spark_rapids_tpu.expr.core import col, lit
# An engine that sorts a long key in passes, or no run at all: before it
# had them, the five-column group key below (an 18-byte string in it) was
# ONE sort of 22 operands at 2^16 rows, and the chip's compiler did not
# finish that one program in the 1200 s a first run may take, with every
# other program of the query already cached (PERF.md Findings PR 31).
# Such an engine fails here, in the first seconds and before any data is
# made, instead of being killed.
from spark_rapids_tpu.ops.sort import PASS_SORT_KEYS  # noqa: F401

#: the tables the query scans and the columns it names
TABLES = {
    "lineitem": ["l_orderkey", "l_quantity"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"],
    "customer": ["c_custkey", "c_name"],
}

#: the specification's validation value of QUANTITY
QUANTITY = 300.0


def build(session, data_dir: str):
    def t(table):
        return session.read_parquet(os.path.join(data_dir, table),
                                    columns=TABLES[table])
    li = t("lineitem")
    big = li.group_by("l_orderkey") \
        .agg(Sum(col("l_quantity")).alias("q")) \
        .where(col("q") > lit(QUANTITY)) \
        .select(col("l_orderkey").alias("big_orderkey"))
    return li.join(big, on=[("l_orderkey", "big_orderkey")], how="semi") \
        .join(t("orders"), on=[("l_orderkey", "o_orderkey")]) \
        .join(t("customer"), on=[("o_custkey", "c_custkey")]) \
        .group_by("c_name", "c_custkey", "o_orderkey", "o_orderdate",
                  "o_totalprice") \
        .agg(Sum(col("l_quantity")).alias("sum_qty")) \
        .order_by(("o_totalprice", False), ("o_orderdate", True)) \
        .limit(100)
