"""Plain reference for ``q6_bounds``, written from its text with pandas
in float64 over the same Parquet files.  Imports nothing of the engine.

    select l_discount, count(*) as lines, sum(l_extendedprice) as price
    from lineitem
    where l_shipdate >= date '1994-01-01'
      and l_shipdate < date '1994-01-01' + interval '1' year
      and l_discount between 0.06 - 0.01 and 0.06 + 0.01
      and l_quantity < 24
    group by l_discount
    order by l_discount

Q6's ``where`` clause (benchmark/reference/tpch_q6.py says why the
bounds are the doubles 0.05 and 0.07, both inclusive).  The counts are
integers, compared by equality; the sums are of whole cents, so they
are taken over the cents as integers and are exact whatever the order
of the rows.
"""
import datetime
import os

import numpy as np
import pandas as pd


def rows(data_dir: str) -> list:
    li = pd.read_parquet(os.path.join(data_dir, "lineitem"), columns=[
        "l_extendedprice", "l_discount", "l_shipdate", "l_quantity"])
    li = li[(li.l_shipdate >= datetime.date(1994, 1, 1))
            & (li.l_shipdate < datetime.date(1995, 1, 1))
            & (li.l_discount >= 0.05) & (li.l_discount <= 0.07)
            & (li.l_quantity < 24)]
    cents = np.rint(li.l_extendedprice.to_numpy() * 100).astype(np.int64)
    out = pd.DataFrame({"d": li.l_discount.to_numpy(), "cents": cents}) \
        .groupby("d").agg(lines=("cents", "size"), cents=("cents", "sum")) \
        .sort_index()
    return [(float(d), int(r.lines), int(r.cents) / 100.0)
            for d, r in out.iterrows()]
