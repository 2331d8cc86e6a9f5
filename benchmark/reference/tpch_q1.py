"""Plain reference for TPC-H Q1 (pricing summary report), written from
the query text with pandas in float64 over the same Parquet file.
Imports nothing of the engine.

    select l_returnflag, l_linestatus, sum(l_quantity),
           sum(l_extendedprice),
           sum(l_extendedprice * (1 - l_discount)),
           sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
           avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*)
    from lineitem
    where l_shipdate <= date '1998-12-01' - interval '90' day
    group by l_returnflag, l_linestatus
    order by l_returnflag, l_linestatus
"""
import datetime
import os

import pandas as pd


def rows(data_dir: str) -> list:
    li = pd.read_parquet(os.path.join(data_dir, "lineitem"), columns=[
        "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
        "l_discount", "l_tax", "l_shipdate"])
    cutoff = datetime.date(1998, 12, 1) - datetime.timedelta(days=90)
    li = li[li.l_shipdate <= cutoff]
    li = li.assign(
        disc_price=li.l_extendedprice * (1.0 - li.l_discount))
    li = li.assign(charge=li.disc_price * (1.0 + li.l_tax))
    g = li.groupby(["l_returnflag", "l_linestatus"], sort=True)
    out = pd.DataFrame({
        "sum_qty": g.l_quantity.sum(),
        "sum_base_price": g.l_extendedprice.sum(),
        "sum_disc_price": g.disc_price.sum(),
        "sum_charge": g.charge.sum(),
        "avg_qty": g.l_quantity.mean(),
        "avg_price": g.l_extendedprice.mean(),
        "avg_disc": g.l_discount.mean(),
        "count_order": g.size(),
    })
    return [(flag, status, *(float(v) for v in r[:7]), int(r[7]))
            for (flag, status), r in zip(out.index, out.to_numpy())]
