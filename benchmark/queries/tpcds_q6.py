"""TPC-DS q6 as DataFrame code, copied from
spark_rapids_tpu/bench/tpcds_queries.py (PR 22's headline query): state
count of customers buying items priced over 120 % of their category's
average, for one month."""
import os

from spark_rapids_tpu.expr.aggregates import Average, CountStar
from spark_rapids_tpu.expr.core import col, lit

#: the tables the query scans and the columns it names
TABLES = {
    "date_dim": ["d_date_sk", "d_year", "d_moy", "d_month_seq"],
    "item": ["i_item_sk", "i_category", "i_current_price"],
    "customer": ["c_customer_sk", "c_current_addr_sk"],
    "customer_address": ["ca_address_sk", "ca_state"],
    "store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_customer_sk"],
}


def build(session, data_dir: str):
    def t(table):
        return session.read_parquet(os.path.join(data_dir, table),
                                    columns=TABLES[table])
    dd = t("date_dim")
    # scalar subquery, evaluated eagerly and folded as a literal — the
    # plan Spark produces after subquery execution
    ms_rows = dd.where((col("d_year") == lit(2001))
                       & (col("d_moy") == lit(1))) \
        .select(col("d_month_seq")).limit(1).collect()
    ms = ms_rows[0][0]
    dt = dd.where(col("d_month_seq") == lit(ms)).select(col("d_date_sk"))

    item = t("item")
    avg_cat = item.group_by("i_category").agg(
        Average(col("i_current_price")).alias("avg_price")) \
        .select(col("i_category").alias("cat_avg_key"), col("avg_price"))
    it = item.join(avg_cat, on=[("i_category", "cat_avg_key")]) \
        .where(col("i_current_price") > lit(1.2) * col("avg_price")) \
        .select(col("i_item_sk"))

    return t("store_sales") \
        .join(dt, on=[("ss_sold_date_sk", "d_date_sk")]) \
        .join(it, on=[("ss_item_sk", "i_item_sk")]) \
        .join(t("customer"), on=[("ss_customer_sk", "c_customer_sk")]) \
        .join(t("customer_address"),
              on=[("c_current_addr_sk", "ca_address_sk")]) \
        .group_by("ca_state") \
        .agg(CountStar().alias("cnt")) \
        .where(col("cnt") >= lit(10)) \
        .order_by(("cnt", True)) \
        .limit(100)
