"""TPC-H Q6, forecasting revenue change, as DataFrame code; copied from
spark_rapids_tpu/bench/tpch_queries.py."""
import datetime
import os

from spark_rapids_tpu.expr.aggregates import Sum
from spark_rapids_tpu.expr.core import col, lit

#: the tables the query scans and the columns it names
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
        .agg(Sum(col("l_extendedprice") * col("l_discount"))
             .alias("revenue"))
