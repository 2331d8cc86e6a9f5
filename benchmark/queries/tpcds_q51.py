"""TPC-DS q51 as DataFrame code, from
spark_rapids_tpu/bench/tpcds_queries5.py::q51 and the text of
query51.tpl with ``DMS = 1200`` (the year 2000): for every item and day,
what the web channel and the store channel had each sold of it so far
that year, and the days on which the web's running total stood above
the store's.

``web_v1`` and ``store_v1`` are each a fact table joined to the year's
366 days, summed by (item, day), and a running ``sum`` of those daily
sums over the item's days (``rows between unbounded preceding and
current row``).  The two are FULL OUTER joined on (item, day): an item
sold in one channel on a day keeps its row with the other channel's
cumulative NULL.  Over the joined rows two running ``max``es carry each
channel's last cumulative forward through the days it sold nothing, and
the filter compares them: ``NULL > x`` and ``x > NULL`` are not true,
and two cumulatives that are the same number of cents are not ``>``.

Nothing of q51 is cut or rewritten: the ``is not null``, the two
``case``s, the four windows, the full join.  ``ordered`` is the text
down to its ``order by``; ``build`` puts the text's ``limit 100`` on
it.  benchmark/queries/tpcds_q51_all.py collects ``ordered`` itself
beside this query in the cell's traffic: the hundred rows with the
smallest item keys come from about twenty of 56,920 partitions."""
import os

# an engine from before PR 38 answers q51_all wrongly on the chip (its
# running sums carry the rounding of the rows before their partition, so
# nine of one seed's 26 exact ties came out ``>``) and needs 1,566 s of a
# first run's 1,200 to find that out (window_frame alone compiles for
# 1,029 s cold): it fails here instead, in seconds (PERF.md Findings PR 38)
from spark_rapids_tpu.exec.window import spec_key  # noqa: F401
from spark_rapids_tpu.expr.aggregates import Max, Sum
from spark_rapids_tpu.expr.conditional import If
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.expr.window import (CURRENT_ROW, UNBOUNDED,
                                          WindowExpression, WindowFrame,
                                          WindowSpec)

#: the tables the query scans and the columns it names
TABLES = {
    "web_sales": ["ws_item_sk", "ws_sold_date_sk", "ws_sales_price"],
    "store_sales": ["ss_item_sk", "ss_sold_date_sk", "ss_sales_price"],
    "date_dim": ["d_date_sk", "d_date", "d_month_seq"],
}

#: query51.tpl's qualification substitution: d_month_seq 1200..1211
DMS = 1200


def _running(item: str):
    """``partition by <item> order by d_date rows between unbounded
    preceding and current row``: built anew for every window
    expression, as the text writes it out each time."""
    return WindowSpec(partition_by=(col(item),),
                      order_by=((col("d_date"), True),),
                      frame=WindowFrame("rows", UNBOUNDED, CURRENT_ROW))


def ordered(session, data_dir: str):
    def t(table):
        return session.read_parquet(os.path.join(data_dir, table),
                                    columns=TABLES[table])

    def v1(table, item, sold, price):
        days = t("date_dim").where((col("d_month_seq") >= lit(DMS))
                                   & (col("d_month_seq") <= lit(DMS + 11)))
        daily = t(table).where(col(item).is_not_null()) \
            .join(days, on=[(sold, "d_date_sk")]) \
            .group_by(item, "d_date") \
            .agg(Sum(col(price)).alias("day_sales"))
        cume = WindowExpression(Sum(col("day_sales")), _running(item))
        return daily.select(col(item).alias("item_sk"), col("d_date"),
                            cume.alias("cume_sales"))

    def side(v, name):
        return v.select(col("item_sk").alias(f"{name}_item_sk"),
                        col("d_date").alias(f"{name}_d_date"),
                        col("cume_sales").alias(f"{name}_cume_sales"))

    web = side(v1("web_sales", "ws_item_sk", "ws_sold_date_sk",
                  "ws_sales_price"), "web")
    store = side(v1("store_sales", "ss_item_sk", "ss_sold_date_sk",
                    "ss_sales_price"), "store")
    x = web.join(store, on=[("web_item_sk", "store_item_sk"),
                            ("web_d_date", "store_d_date")], how="full") \
        .select(If(col("web_item_sk").is_not_null(), col("web_item_sk"),
                   col("store_item_sk")).alias("item_sk"),
                If(col("web_d_date").is_not_null(), col("web_d_date"),
                   col("store_d_date")).alias("d_date"),
                col("web_cume_sales").alias("web_sales"),
                col("store_cume_sales").alias("store_sales"))
    y = x.select(
        col("item_sk"), col("d_date"), col("web_sales"), col("store_sales"),
        WindowExpression(Max(col("web_sales")), _running("item_sk"))
        .alias("web_cumulative"),
        WindowExpression(Max(col("store_sales")), _running("item_sk"))
        .alias("store_cumulative"))
    return y.where(col("web_cumulative") > col("store_cumulative")) \
        .order_by(("item_sk", True), ("d_date", True))


def build(session, data_dir: str):
    return ordered(session, data_dir).limit(100)
