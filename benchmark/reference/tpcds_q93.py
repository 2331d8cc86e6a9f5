"""Plain reference for TPC-DS query 93 (query93.tpl with
``r_reason_desc = 'reason 28'``, as Spark's tpcds/q93.sql has it),
written from the query text with pandas over the same Parquet files.
Imports nothing of the engine.

    select ss_customer_sk, sum(act_sales) sumsales
    from (select ss_item_sk, ss_ticket_number, ss_customer_sk,
                 case when sr_return_quantity is not null
                      then (ss_quantity - sr_return_quantity) * ss_sales_price
                      else ss_quantity * ss_sales_price end act_sales
          from store_sales left outer join store_returns
               on (sr_item_sk = ss_item_sk
                   and sr_ticket_number = ss_ticket_number),
               reason
          where sr_reason_sk = r_reason_sk
            and r_reason_desc = 'reason 28') t
    group by ss_customer_sk
    order by sumsales, ss_customer_sk
    limit 100

A NULL key matches nothing: a return with a NULL item or ticket number
joins no sale, and a sale with one keeps its row with every ``sr_``
column NULL, which ``sr_reason_sk = r_reason_sk`` then drops, as it
drops every sale that was not returned.  A sale returned twice is two
rows.  A NULL ``ss_customer_sk`` is a group of its own.  ``sum`` skips
NULL addends (a NULL quantity or price), a sum over only NULLs is NULL,
and NULLs sort first in an ascending order.

``ss_sales_price`` is ``decimal(7,2)`` in the specification and whole
cents in the data (asserted), and the quantities are whole, so the sums
are taken over the cents as integers: two customers whose net sales are
the same amount tie exactly, whatever order their rows were added in,
and the second sort key decides between them.  At SF10 about 3,400
customers come to exactly 0.00 (all they bought was returned in full),
so the hundred rows are a few negative sums (a sale matched with the
larger return of a sale the generator gave the same item and ticket)
and then the zero-sum customers with the smallest keys: which rows they
are hangs on every match of the two-key join being found and none
invented, and the sums themselves are held by the rows of
benchmark/reference/tpcds_q93_all.py.  What the text leaves open is a
*near* tie: a sum unequal to the 100th row's yet within 1e-9 relative
of it (less than the chip's f32-pair doubles keep apart).  ``rows``
refuses such data (an AssertionError, in set-up) instead of answering.

The rows are ``(int or None, float or None)``;
``aggregate(data_dir)`` gives every group, before the limit.
"""
import os

import numpy as np
import pandas as pd

REASON = "reason 28"
LIMIT = 100
#: two unequal sums this close (relative) are a near tie
NEAR = 1e-9


def _read(data_dir, table, columns):
    return pd.read_parquet(os.path.join(data_dir, table), columns=columns)


def aggregate(data_dir: str) -> pd.DataFrame:
    """``ss_customer_sk`` (float64, NaN = the NULL group) and
    ``sumsales`` (NaN = NULL) of every group, in the text's order."""
    ss = _read(data_dir, "store_sales",
               ["ss_item_sk", "ss_ticket_number", "ss_customer_sk",
                "ss_quantity", "ss_sales_price"])
    sr = _read(data_dir, "store_returns",
               ["sr_item_sk", "sr_ticket_number", "sr_reason_sk",
                "sr_return_quantity"])
    re = _read(data_dir, "reason", ["r_reason_sk", "r_reason_desc"])
    # pandas pairs a NaN key with a NaN key; SQL pairs it with nothing.
    # The returns' NULL keys go; a sale's then find no partner
    sr = sr[sr.sr_item_sk.notna() & sr.sr_ticket_number.notna()]
    j = ss.merge(sr, how="left",
                 left_on=["ss_item_sk", "ss_ticket_number"],
                 right_on=["sr_item_sk", "sr_ticket_number"])
    kept = re.r_reason_sk[(re.r_reason_desc == REASON)
                          & re.r_reason_sk.notna()]
    j = j[j.sr_reason_sk.isin(kept.astype(np.float64))]

    price = j.ss_sales_price.to_numpy(dtype=np.float64) * 100.0
    cents = np.rint(price)
    assert np.all(np.isnan(price) | (np.abs(price - cents) < 1e-6)), \
        "q93 reference: ss_sales_price is not whole cents in this data"
    qty = j.ss_quantity.to_numpy(dtype=np.float64)
    back = j.sr_return_quantity.to_numpy(dtype=np.float64)
    # whole numbers far below 2^53: these doubles are exact integers
    act = np.where(np.isnan(back), qty, qty - back) * cents
    g = pd.DataFrame({"ss_customer_sk":
                      j.ss_customer_sk.to_numpy(dtype=np.float64),
                      "cents": act}) \
        .groupby("ss_customer_sk", dropna=False, sort=False) \
        .cents.sum(min_count=1).reset_index()
    g["sumsales"] = g.cents / 100.0
    return g.sort_values(["cents", "ss_customer_sk"], na_position="first",
                         kind="stable")[["ss_customer_sk", "sumsales"]]


def as_rows(g: pd.DataFrame) -> list:
    return [(None if np.isnan(c) else int(c),
             None if np.isnan(s) else float(s))
            for c, s in zip(g.ss_customer_sk, g.sumsales)]


def rows(data_dir: str) -> list:
    g = aggregate(data_dir)
    if len(g) > LIMIT:
        last = g.sumsales.iloc[LIMIT - 1]
        s = g.sumsales.to_numpy()
        near = (s != last) & np.isclose(s, last, rtol=NEAR, atol=NEAR)
        assert not near.any(), \
            f"q93 reference: the 100th row's sumsales {last!r} has a " \
            f"near tie ({s[near][:3].tolist()}): the text does not say " \
            "which is kept; use another seed"
    return as_rows(g.head(LIMIT))
