"""Plain reference for TPC-H Q18 (large volume customer; validation
parameter QUANTITY = 300), written from the query text with pandas
over the same Parquet files.  Imports nothing of the engine.

    select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
           sum(l_quantity)
    from customer, orders, lineitem
    where o_orderkey in (
              select l_orderkey from lineitem
              group by l_orderkey
              having sum(l_quantity) > 300)
      and c_custkey = o_custkey
      and o_orderkey = l_orderkey
    group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
    order by o_totalprice desc, o_orderdate
    limit 100

``l_quantity`` is ``decimal(15,2)`` in the specification and a whole
number in the data (asserted), so the sums are taken as integers and
``> 300`` is decided exactly: an order whose quantities sum to exactly
300 is out, whatever a double rebuilt from hundredths would say.  A
NULL ``l_quantity`` is skipped by ``sum``; an order whose quantities
are all NULL has a NULL sum and fails the ``having``.  A NULL key joins
nothing, so an order with a NULL ``o_custkey`` (or a customer the table
lacks) is dropped by the inner join.

The rows are ``(str, int, int, str, float, float)`` in the text's
column order, the date as an ISO string.  Which hundred come out hangs
on the order of ``(o_totalprice desc, o_orderdate)``: where the 100th
and the 101st qualifying row are equal on both, the text does not say
which is kept, and ``rows`` refuses such data (an AssertionError, in
set-up) instead of answering.  None of twelve SF1 seeds (2147483943 …
2147483954) is refused: a price is one of 49.9M cent values and a tie
must also share its date.
"""
import os

import numpy as np
import pandas as pd

QUANTITY = 300
LIMIT = 100


def _read(data_dir, table, columns):
    return pd.read_parquet(os.path.join(data_dir, table), columns=columns)


def rows(data_dir: str) -> list:
    li = _read(data_dir, "lineitem", ["l_orderkey", "l_quantity"])
    li = li[li.l_orderkey.notna() & li.l_quantity.notna()]
    qty = li.l_quantity.to_numpy(dtype=np.float64)
    assert (qty == np.rint(qty)).all(), \
        "q18 reference: l_quantity is not whole in this data"
    li = pd.DataFrame({"o_orderkey": li.l_orderkey.astype(np.int64),
                       "qty": qty.astype(np.int64)})
    total = li.groupby("o_orderkey", sort=False).qty.sum()
    big = total[total > QUANTITY].rename("sum_qty").reset_index()
    od = _read(data_dir, "orders", ["o_orderkey", "o_custkey",
                                    "o_orderdate", "o_totalprice"])
    cu = _read(data_dir, "customer", ["c_custkey", "c_name"])
    od = od[od.o_orderkey.notna() & od.o_custkey.notna()]
    cu = cu[cu.c_custkey.notna()]
    j = big.merge(od.astype({"o_orderkey": np.int64,
                             "o_custkey": np.int64}), on="o_orderkey") \
        .merge(cu.astype({"c_custkey": np.int64}),
               left_on="o_custkey", right_on="c_custkey")
    # a group of the text is one (customer, order) pair: both keys are
    # unique in their tables, so the join's rows are the groups already
    assert not j.duplicated(["c_custkey", "o_orderkey"]).any(), \
        "q18 reference: o_orderkey or c_custkey is not unique in this data"
    j = j.assign(_price=j.o_totalprice.fillna(-np.inf)) \
        .sort_values(["_price", "o_orderdate", "o_orderkey"],
                     ascending=[False, True, True], kind="stable")
    if len(j) > LIMIT:
        a, b = j.iloc[LIMIT - 1], j.iloc[LIMIT]
        assert (a.o_totalprice, a.o_orderdate) != \
            (b.o_totalprice, b.o_orderdate), \
            "q18 reference: the 100th and 101st rows tie on " \
            f"(o_totalprice, o_orderdate) = ({a.o_totalprice!r}, " \
            f"{a.o_orderdate}): the text does not say which is kept; " \
            "use another seed"
    j = j.head(LIMIT)
    return [(None if pd.isna(n) else str(n), int(ck), int(ok),
             None if pd.isna(d) else pd.Timestamp(d).date().isoformat(),
             None if pd.isna(p) else float(p), float(q))
            for n, ck, ok, d, p, q in zip(
                j.c_name, j.c_custkey, j.o_orderkey, j.o_orderdate,
                j.o_totalprice, j.sum_qty)]
