"""Plain reference for TPC-DS query 51 (query51.tpl with ``DMS =
1200``, as Spark's tpcds/q51.sql has it), written from the query text
with numpy and pandas over the same Parquet files.  Imports nothing of
the engine.

    with web_v1 as (
      select ws_item_sk item_sk, d_date,
             sum(sum(ws_sales_price)) over (partition by ws_item_sk
               order by d_date rows between unbounded preceding
               and current row) cume_sales
      from web_sales, date_dim
      where ws_sold_date_sk = d_date_sk
        and d_month_seq between 1200 and 1200 + 11
        and ws_item_sk is not null
      group by ws_item_sk, d_date),
    store_v1 as (the same over store_sales)
    select * from (
      select item_sk, d_date, web_sales, store_sales,
             max(web_sales) over (partition by item_sk order by d_date
               rows between unbounded preceding and current row)
               web_cumulative,
             max(store_sales) over (... the same frame) store_cumulative
      from (select case when web.item_sk is not null then web.item_sk
                        else store.item_sk end item_sk,
                   case when web.d_date is not null then web.d_date
                        else store.d_date end d_date,
                   web.cume_sales web_sales, store.cume_sales store_sales
            from web_v1 web full outer join store_v1 store
              on (web.item_sk = store.item_sk
                  and web.d_date = store.d_date)) x) y
    where web_cumulative > store_cumulative
    order by item_sk, d_date
    limit 100

A sale with a NULL date joins no day.  ``sum`` skips NULL prices, and a
day whose prices are all NULL sums to NULL; the running ``sum`` skips
such days and is NULL until a day counts.  The full outer join keeps an
(item, day) one channel alone sold, the other channel's cumulative
NULL; both sides are grouped, so a pair matches at most once.  The
running ``max`` skips NULLs and is NULL until its channel has sold the
item; ``NULL > x`` and ``x > NULL`` are not true.

The prices are ``decimal(7,2)`` in the specification and whole cents in
the data (asserted), so all money here is ``int64`` cents: summed,
cumulated and compared as integers, and turned into doubles once, at
the end.  Two cumulatives that are the same amount are therefore equal,
whatever order their sales were added in, and ``>`` is false for them
(``ties`` counts such rows: about twenty a seed at SF10).  (item, day)
is unique in the output, so the order and the limit leave nothing open.

The rows are ``(int, str, float or None, float or None, float,
float)``; the day is its ISO text, which is what the comparison makes
of the engine's ``datetime.date`` and what the harness's JSON keeps.
"""
import datetime
import os

import numpy as np
import pandas as pd

DMS = 1200
LIMIT = 100
_DAY_BITS = 20      # days since 1970 fit 20 bits until the year 4840


def _read(data_dir, table, columns):
    return pd.read_parquet(os.path.join(data_dir, table), columns=columns)


def _year(data_dir):
    """``d_date_sk`` -> days since 1970-01-01 for the twelve months."""
    dd = _read(data_dir, "date_dim", ["d_date_sk", "d_date", "d_month_seq"])
    dd = dd[(dd.d_month_seq >= DMS) & (dd.d_month_seq <= DMS + 11)
            & dd.d_date_sk.notna() & dd.d_date.notna()]
    days = (pd.to_datetime(dd.d_date).to_numpy().astype("datetime64[D]")
            - np.datetime64("1970-01-01")).astype(np.int64)
    return pd.Series(days, index=dd.d_date_sk.to_numpy(dtype=np.int64))


def _v1(data_dir, year, table, item, sold, price):
    """``(key, cume, has)`` of web_v1 / store_v1, ascending by key =
    (item, day) packed: ``cume`` the running sum in cents, ``has``
    False where it is NULL."""
    f = _read(data_dir, table, [item, sold, price])
    i = f[item].to_numpy(dtype=np.float64)
    s = f[sold].to_numpy(dtype=np.float64)
    p = f[price].to_numpy(dtype=np.float64) * 100.0
    keep = ~np.isnan(i) & ~np.isnan(s)
    keep[keep] = np.isin(s[keep].astype(np.int64), year.index.to_numpy())
    i, s, p = i[keep].astype(np.int64), s[keep].astype(np.int64), p[keep]
    cents = np.rint(p)
    assert np.all(np.isnan(p) | (np.abs(p - cents) < 1e-6)), \
        f"q51 reference: {price} is not whole cents in this data"
    priced = ~np.isnan(p)
    cents = np.where(priced, cents, 0).astype(np.int64)
    key = (i << _DAY_BITS) | year.reindex(s).to_numpy()
    # group by (item, day): integer sums, NULL where no price counted
    order = np.argsort(key, kind="stable")
    key, cents, priced = key[order], cents[order], priced[order]
    first = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    if len(key) == 0:
        return key, cents, priced
    day_sum = np.add.reduceat(cents, first)
    day_has = np.add.reduceat(priced.astype(np.int64), first) > 0
    key = key[first]
    # running sum over the item's days, skipping NULL days
    seg = np.flatnonzero(np.r_[True, (key[1:] >> _DAY_BITS)
                               != (key[:-1] >> _DAY_BITS)])
    sizes = np.diff(np.r_[seg, len(key)])

    def running(x):
        total = np.cumsum(x)
        before = np.r_[0, total][seg]
        return total - np.repeat(before, sizes)
    return key, running(np.where(day_has, day_sum, 0)), \
        running(day_has.astype(np.int64)) > 0


def _running_max(seg_id, x, has):
    """Per segment, the largest counted ``x`` so far and whether any
    counted: one ``maximum.accumulate`` over values lifted by segment."""
    lo = int(x[has].min()) if has.any() else 0
    span = int(x[has].max()) - lo + 2 if has.any() else 2
    assert (int(seg_id[-1]) + 1) * span < (1 << 62)
    lifted = np.where(has, x - lo + 1, 0) + seg_id * span
    top = np.maximum.accumulate(lifted) - seg_id * span
    return top + lo - 1, top > 0


def cumulatives(data_dir: str) -> pd.DataFrame:
    """Every row of ``y`` (before the filter), in the text's order:
    ``item_sk``, ``day`` (since 1970), and cents with a ``_has`` flag
    (False = NULL) for ``web_sales``, ``store_sales``,
    ``web_cumulative``, ``store_cumulative``."""
    year = _year(data_dir)
    wk, wc, wh = _v1(data_dir, year, "web_sales", "ws_item_sk",
                     "ws_sold_date_sk", "ws_sales_price")
    sk, sc, sh = _v1(data_dir, year, "store_sales", "ss_item_sk",
                     "ss_sold_date_sk", "ss_sales_price")
    key = np.union1d(wk, sk)            # the full outer join, in order
    out = {"item_sk": key >> _DAY_BITS,
           "day": key & ((1 << _DAY_BITS) - 1)}
    seg_id = np.cumsum(np.r_[False, out["item_sk"][1:]
                             != out["item_sk"][:-1]]).astype(np.int64)
    for name, k, c, h in (("web", wk, wc, wh), ("store", sk, sc, sh)):
        at = np.searchsorted(key, k)
        cents = np.zeros(len(key), np.int64)
        has = np.zeros(len(key), bool)
        cents[at], has[at] = c, h
        out[f"{name}_sales"], out[f"{name}_sales_has"] = cents, has
        if len(key):
            out[f"{name}_cumulative"], out[f"{name}_cumulative_has"] = \
                _running_max(seg_id, cents, has)
        else:
            out[f"{name}_cumulative"], out[f"{name}_cumulative_has"] = \
                cents, has
    return pd.DataFrame(out)


def ties(y: pd.DataFrame) -> pd.DataFrame:
    """The rows of ``cumulatives`` whose two cumulatives are the same
    number of cents: ``>`` is false there."""
    return y[y.web_cumulative_has & y.store_cumulative_has
             & (y.web_cumulative == y.store_cumulative)]


def qualifying(y: pd.DataFrame) -> pd.DataFrame:
    return y[y.web_cumulative_has & y.store_cumulative_has
             & (y.web_cumulative > y.store_cumulative)]


def as_rows(y: pd.DataFrame) -> list:
    def money(name):
        return [c / 100.0 if h else None for c, h in
                zip(y[name].tolist(), y[name + "_has"].tolist())]
    epoch = datetime.date(1970, 1, 1).toordinal()
    days = [datetime.date.fromordinal(epoch + d).isoformat()
            for d in y.day.tolist()]
    return list(zip(y.item_sk.tolist(), days, money("web_sales"),
                    money("store_sales"), money("web_cumulative"),
                    money("store_cumulative")))


def rows(data_dir: str) -> list:
    return as_rows(qualifying(cumulatives(data_dir)).head(LIMIT))
