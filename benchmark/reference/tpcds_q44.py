"""Plain reference for TPC-DS query 44, written from the query text
(query44.tpl, qualification parameters: store 4, ``ss_addr_sk``) with
pandas over the same Parquet files.  Imports nothing of the engine.

    select asceding.rnk, i1.i_product_name best_performing,
           i2.i_product_name worst_performing
    from (select * from (
            select item_sk, rank() over (order by rank_col asc) rnk
            from (select ss_item_sk item_sk, avg(ss_net_profit) rank_col
                  from store_sales ss1
                  where ss_store_sk = 4
                  group by ss_item_sk
                  having avg(ss_net_profit) > 0.9 * (
                      select avg(ss_net_profit) rank_col
                      from store_sales
                      where ss_store_sk = 4 and ss_addr_sk is null
                      group by ss_store_sk)) V1) V11
          where rnk < 11) asceding,
         (... the same with ``order by rank_col desc`` ...
          where rnk < 11) descending,
         item i1, item i2
    where asceding.rnk = descending.rnk
      and i1.i_item_sk = asceding.item_sk
      and i2.i_item_sk = descending.item_sk
    order by asceding.rnk
    limit 100

A NULL ``ss_store_sk`` never equals 4.  ``avg`` skips NULL profits; an
item whose profits are all NULL has a NULL average and fails the
``having``.  An empty subquery is NULL, and then the ``having`` keeps
nothing.  ``rank()`` gives equal values one rank and skips the ranks
after them; the join on ``rnk`` then pairs every best with every worst
of that rank.

The rows are ``(int, str, str)``: no float takes part in the
comparison, so a precision slip shows as a wrong name.  Which names
come out hangs on the order of the averages.  ``ss_net_profit`` is
``decimal(7,2)`` in the specification and whole cents in the data, so
SQL's ``avg`` is exact and two items whose averages are the same
rational share a rank: 735.76 / 2 and 1103.64 / 3 tie, whatever their
doubles say.  Where every profit is whole cents the averages are
therefore taken exactly here (integer sums of cents, ``Fraction``),
and ties are ties.  What stays a coin toss on a chip whose f64 is an
f32 pair of about 48 bits is a *near* tie: two unequal averages among
the 11 smallest or the 11 largest kept ones, or the ``having``'s
threshold and the average next to it on either side, within 1e-9
relative of each other.  ``rows`` refuses such data (an
AssertionError, in set-up) instead of answering.
"""
import os
from fractions import Fraction

import numpy as np
import pandas as pd

#: unequal neighbours among the ranked averages must differ by more than
#: this share of the larger one
MIN_REL_GAP = 1e-9


def _read(data_dir, table, columns):
    return pd.read_parquet(os.path.join(data_dir, table), columns=columns)


def _assert_apart(values, what):
    """``values`` ascending: no two unequal neighbours within
    MIN_REL_GAP."""
    for a, b in zip(values, values[1:]):
        gap = abs(b - a)
        assert a == b or gap > MIN_REL_GAP * max(abs(a), abs(b)), \
            f"q44 reference: near-tie among the {what} averages " \
            f"({float(a)!r}, {float(b)!r}): this data cannot be ranked " \
            f"reliably in 48-bit arithmetic; use another seed"


def _averages(s):
    """``(per item, over rows with a NULL address)`` of store 4's
    profits, NULLs skipped: exact ``Fraction``s of a dollar where every
    profit is whole cents, doubles otherwise; an average over no row is
    absent / None."""
    s = s[s.ss_net_profit.notna()]
    cents = np.rint(s.ss_net_profit * 100)
    if (np.abs(s.ss_net_profit * 100 - cents)
            <= 1e-13 * np.maximum(np.abs(cents), 1)).all():
        s = s.assign(c=cents.astype(np.int64))
        g = s.groupby("ss_item_sk").c.agg(["sum", "count"])
        v = pd.Series([Fraction(int(t), 100 * int(k))
                       for t, k in zip(g["sum"], g["count"])],
                      index=g.index, dtype=object)
        null = s[s.ss_addr_sk.isna()].c
        base = Fraction(int(null.sum()), 100 * len(null)) if len(null) \
            else None
        return v, base, Fraction(9, 10)
    v = s.groupby("ss_item_sk").ss_net_profit.mean()
    base = s[s.ss_addr_sk.isna()].ss_net_profit.mean()
    return v, None if pd.isna(base) else base, 0.9


def rows(data_dir: str) -> list:
    s = _read(data_dir, "store_sales", ["ss_item_sk", "ss_store_sk",
                                        "ss_addr_sk", "ss_net_profit"])
    v, base, share = _averages(s[s.ss_store_sk == 4])  # NULL == 4: not true
    if base is None:                            # empty subquery: NULL
        return []
    vals, items = map(list, zip(*sorted(zip(v.values, v.index)))) \
        if len(v) else ([], [])
    cut = sum(x <= share * base for x in vals)  # the having drops these
    _assert_apart(vals[max(cut - 1, 0):cut] + [share * base]
                  + vals[cut:cut + 1], "having's threshold and its")
    items, vals = items[cut:], vals[cut:]
    _assert_apart(vals[:11], "11 smallest")
    _assert_apart(vals[-11:], "11 largest")
    # rank(): one more than the number of rows strictly before
    dense = pd.Series(pd.factorize(pd.Series(vals, dtype=object))[0])
    up = pd.DataFrame({"item_sk_a": items,
                       "rnk": dense.rank(method="min", ascending=True)})
    dn = pd.DataFrame({"item_sk_d": items,
                       "rnk": dense.rank(method="min", ascending=False)})
    i = _read(data_dir, "item", ["i_item_sk", "i_product_name"])
    j = up[up.rnk < 11].merge(dn[dn.rnk < 11], on="rnk") \
        .merge(i, left_on="item_sk_a", right_on="i_item_sk") \
        .merge(i, left_on="item_sk_d", right_on="i_item_sk",
               suffixes=("_best", "_worst")) \
        .sort_values("rnk", kind="stable")
    return [(int(r), None if pd.isna(b) else str(b),
             None if pd.isna(w) else str(w))
            for r, b, w in zip(j.rnk, j.i_product_name_best,
                               j.i_product_name_worst)][:100]
