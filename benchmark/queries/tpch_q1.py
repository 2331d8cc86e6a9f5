"""TPC-H Q1, pricing summary report, as DataFrame code; copied from
spark_rapids_tpu/bench/tpch_queries.py."""
import datetime
import os

from spark_rapids_tpu.expr.aggregates import Average, CountStar, Sum
from spark_rapids_tpu.expr.core import col, lit

#: the tables the query scans and the columns it names
TABLES = {
    "lineitem": ["l_returnflag", "l_linestatus", "l_quantity",
                 "l_extendedprice", "l_discount", "l_tax", "l_shipdate"],
}


def _disc_price():
    return col("l_extendedprice") * (lit(1.0) - col("l_discount"))


def build(session, data_dir: str):
    li = session.read_parquet(os.path.join(data_dir, "lineitem"),
                              columns=TABLES["lineitem"])
    return li.where(col("l_shipdate") <= lit(datetime.date(1998, 9, 2))) \
        .group_by("l_returnflag", "l_linestatus") \
        .agg(Sum(col("l_quantity")).alias("sum_qty"),
             Sum(col("l_extendedprice")).alias("sum_base_price"),
             Sum(_disc_price()).alias("sum_disc_price"),
             Sum(_disc_price() * (lit(1.0) + col("l_tax")))
             .alias("sum_charge"),
             Average(col("l_quantity")).alias("avg_qty"),
             Average(col("l_extendedprice")).alias("avg_price"),
             Average(col("l_discount")).alias("avg_disc"),
             CountStar().alias("count_order")) \
        .order_by(("l_returnflag", True), ("l_linestatus", True))
