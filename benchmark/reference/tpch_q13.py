"""Plain reference for TPC-H Q13 (customer distribution; validation
values WORD1 = special, WORD2 = requests), written from the query text
with pyarrow and pandas over the same Parquet files.  Imports nothing of
the engine.

    select c_count, count(*) as custdist
    from (select c_custkey, count(o_orderkey)
          from customer left outer join orders
               on c_custkey = o_custkey
               and o_comment not like '%special%requests%'
          group by c_custkey) as c_orders (c_custkey, c_count)
    group by c_count
    order by custdist desc, c_count desc

The pattern is decided by ``pyarrow.compute.match_like`` (SQL LIKE over
the column's own bytes).  ``not like`` of a NULL comment is NULL, so
such an order fails the join condition and is not counted; an order
with a NULL ``o_custkey`` joins no customer; ``count(o_orderkey)``
skips a NULL key.  Every customer appears once in the inner query —
``c_custkey`` is the table's key, asserted — so its count is the
number of its kept orders, 0 where it has none.

The rows are ``(int, int)``: ``(c_count, custdist)``, ordered by
``custdist`` descending then ``c_count`` descending, which is a total
order (``c_count`` is the group key).
"""
import os

import numpy as np
import pandas as pd
import pyarrow.compute as pc
import pyarrow.parquet as pq

PATTERN = "%special%requests%"


def rows(data_dir: str) -> list:
    od = pq.read_table(os.path.join(data_dir, "orders"),
                       columns=["o_orderkey", "o_custkey", "o_comment"])
    keep = pc.fill_null(pc.invert(pc.match_like(od.column("o_comment"),
                                                PATTERN)), False)
    od = od.filter(keep).select(["o_orderkey", "o_custkey"]).to_pandas()
    od = od[od.o_custkey.notna() & od.o_orderkey.notna()]
    cu = pq.read_table(os.path.join(data_dir, "customer"),
                       columns=["c_custkey"]).to_pandas()
    cu = cu[cu.c_custkey.notna()]
    assert cu.c_custkey.is_unique, \
        "q13 reference: c_custkey is not unique in this data"
    per_customer = od.groupby("o_custkey", sort=False).o_orderkey.count()
    c_count = cu.c_custkey.map(per_customer).fillna(0).astype(np.int64)
    dist = c_count.value_counts(sort=False).rename("custdist") \
        .rename_axis("c_count").reset_index() \
        .sort_values(["custdist", "c_count"], ascending=[False, False])
    return [(int(c), int(d)) for c, d in zip(dist.c_count, dist.custdist)]
