"""TPC-DS q93 as DataFrame code, from
spark_rapids_tpu/bench/tpcds_queries3.py::q93: what each customer's
store purchases came to once the returns made for 'reason 28' are taken
off, the hundred smallest first.

A sale is one (item, ticket number) pair, so ``store_sales`` is
left-joined to ``store_returns`` on BOTH keys: a fact stream against a
fact-sized build.  The text's ``, reason where sr_reason_sk =
r_reason_sk and r_reason_desc = 'reason 28'`` is the semi-join on the
one kept reason.  Nothing of q93 is cut or rewritten: the left join
stays a left join, every column the text names is scanned, the ``case``
is the text's.

``ordered`` is the text down to its ``order by``; ``build`` puts the
text's ``limit 100`` on it.  benchmark/queries/tpcds_q93_all.py collects
``ordered`` itself, every customer, beside this query in the cell's
traffic: at SF10 all but a few of the hundred rows carry 0.0, so the
comparison of THESE rows says which customers come first and next to
nothing of the sums."""
import os

from spark_rapids_tpu.expr.aggregates import Sum
from spark_rapids_tpu.expr.conditional import If
from spark_rapids_tpu.expr.core import col, lit

#: the tables the query scans and the columns it names
TABLES = {
    "store_sales": ["ss_item_sk", "ss_ticket_number", "ss_customer_sk",
                    "ss_quantity", "ss_sales_price"],
    "store_returns": ["sr_item_sk", "sr_ticket_number", "sr_reason_sk",
                      "sr_return_quantity"],
    "reason": ["r_reason_sk", "r_reason_desc"],
}

#: the reason of query93.tpl as Spark's tpcds/q93.sql writes it
REASON = "reason 28"


def ordered(session, data_dir: str):
    def t(table):
        return session.read_parquet(os.path.join(data_dir, table),
                                    columns=TABLES[table])
    reason = t("reason").where(col("r_reason_desc") == lit(REASON)) \
        .select(col("r_reason_sk"))
    act_sales = If(col("sr_return_quantity").is_not_null(),
                   (col("ss_quantity") - col("sr_return_quantity"))
                   * col("ss_sales_price"),
                   col("ss_quantity") * col("ss_sales_price"))
    return t("store_sales") \
        .join(t("store_returns"),
              on=[("ss_item_sk", "sr_item_sk"),
                  ("ss_ticket_number", "sr_ticket_number")], how="left") \
        .join(reason, on=[("sr_reason_sk", "r_reason_sk")], how="semi") \
        .group_by("ss_customer_sk") \
        .agg(Sum(act_sales).alias("sumsales")) \
        .order_by(("sumsales", True), ("ss_customer_sk", True))


def build(session, data_dir: str):
    return ordered(session, data_dir).limit(100)
