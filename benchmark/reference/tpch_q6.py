"""Plain reference for TPC-H Q6 (forecasting revenue change), written
from the query text with pandas in float64 over the same Parquet file.
Imports nothing of the engine.

    select sum(l_extendedprice * l_discount) as revenue
    from lineitem
    where l_shipdate >= date '1994-01-01'
      and l_shipdate < date '1994-01-01' + interval '1' year
      and l_discount between 0.06 - 0.01 and 0.06 + 0.01
      and l_quantity < 24

The spec's arithmetic is decimal, so the bounds are 0.05 and 0.07,
both inclusive; they are written as those doubles here (``0.06 + 0.01``
in binary floating point is below 0.07 and would drop a tenth of the
rows), and the generator's discounts are the doubles k/100 rounded to
two places.
"""
import datetime
import os

import pandas as pd


def rows(data_dir: str) -> list:
    li = pd.read_parquet(os.path.join(data_dir, "lineitem"), columns=[
        "l_extendedprice", "l_discount", "l_shipdate", "l_quantity"])
    li = li[(li.l_shipdate >= datetime.date(1994, 1, 1))
            & (li.l_shipdate < datetime.date(1995, 1, 1))
            & (li.l_discount >= 0.05) & (li.l_discount <= 0.07)
            & (li.l_quantity < 24)]
    revenue = (li.l_extendedprice * li.l_discount).sum()
    return [(float(revenue) if len(li) else None,)]
