"""TPC-DS q44 as DataFrame code, from
spark_rapids_tpu/bench/tpcds_queries4.py::q44: of store 4's sales, the
average net profit per item, kept where it beats 0.9 x the store's
average over rows with a NULL address, ranked ascending and descending;
the ten best paired with the ten worst by rank, item names joined in.

The scalar subquery stays IN the plan, as a one-row aggregate
cross-joined into the ranking input: an eager ``collect()`` here would
move a fact scan out of the timed window.  One departure from the
package's version: its ``Coalesce(_base, 0.0)`` is gone.  By the SQL
text an empty subquery is NULL, ``rank_col > 0.9 * NULL`` is NULL, and
the ``having`` keeps nothing."""
import os

from spark_rapids_tpu.expr.aggregates import Average
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.expr.window import Rank, WindowExpression, WindowSpec

#: the tables the query scans and the columns it names
TABLES = {
    "store_sales": ["ss_item_sk", "ss_store_sk", "ss_addr_sk",
                    "ss_net_profit"],
    "item": ["i_item_sk", "i_product_name"],
}


def build(session, data_dir: str):
    def t(table):
        return session.read_parquet(os.path.join(data_dir, table),
                                    columns=TABLES[table])
    store4 = t("store_sales").where(col("ss_store_sk") == lit(4))
    base = store4.where(col("ss_addr_sk").is_null()) \
        .agg(Average(col("ss_net_profit")).alias("_base"))
    v1 = store4.group_by("ss_item_sk") \
        .agg(Average(col("ss_net_profit")).alias("rank_col")) \
        .join(base, how="cross") \
        .where(col("rank_col") > lit(0.9) * col("_base")) \
        .select(col("ss_item_sk"), col("rank_col"))

    def rank(ascending):
        return WindowExpression(Rank(), WindowSpec(
            order_by=((col("rank_col"), ascending),)))
    up = v1.select(col("ss_item_sk").alias("item_sk_a"),
                   rank(True).alias("rnk")).where(col("rnk") < lit(11))
    dn = v1.select(col("ss_item_sk").alias("item_sk_d"),
                   rank(False).alias("rnk_d")).where(col("rnk_d") < lit(11))
    i1 = t("item").select(col("i_item_sk").alias("i1_sk"),
                          col("i_product_name").alias("best_performing"))
    i2 = t("item").select(col("i_item_sk").alias("i2_sk"),
                          col("i_product_name").alias("worst_performing"))
    return up.join(dn, on=[("rnk", "rnk_d")]) \
        .join(i1, on=[("item_sk_a", "i1_sk")]) \
        .join(i2, on=[("item_sk_d", "i2_sk")]) \
        .select(col("rnk"), col("best_performing"),
                col("worst_performing")) \
        .order_by(("rnk", True)).limit(100)
