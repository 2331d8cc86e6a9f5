"""Plain reference for TPC-DS query 6, written from the query text
(query6.tpl) with pandas over the same Parquet files.  Imports nothing
of the engine.

    select a.ca_state state, count(*) cnt
    from customer_address a, customer c, store_sales s, date_dim d, item i
    where a.ca_address_sk = c.c_current_addr_sk
      and c.c_customer_sk = s.ss_customer_sk
      and s.ss_sold_date_sk = d.d_date_sk
      and s.ss_item_sk = i.i_item_sk
      and d.d_month_seq = (select distinct d_month_seq from date_dim
                           where d_year = 2001 and d_moy = 1)
      and i.i_current_price > 1.2 * (select avg(j.i_current_price)
                                     from item j
                                     where j.i_category = i.i_category)
    group by a.ca_state having count(*) >= 10
    order by cnt limit 100

A NULL never equals anything, so rows with a NULL join key or category
drop out; a NULL ``ca_state`` is a group of its own.  ``limit 100`` cuts
nothing: there are at most 51 groups.
"""
import os

import pandas as pd


def _read(data_dir, table, columns):
    return pd.read_parquet(os.path.join(data_dir, table), columns=columns)


def rows(data_dir: str) -> list:
    d = _read(data_dir, "date_dim", ["d_date_sk", "d_year", "d_moy",
                                     "d_month_seq"])
    seqs = d[(d.d_year == 2001) & (d.d_moy == 1)].d_month_seq.unique()
    assert len(seqs) == 1, seqs
    days = d[d.d_month_seq == seqs[0]].d_date_sk

    i = _read(data_dir, "item", ["i_item_sk", "i_category",
                                 "i_current_price"])
    i = i[i.i_category.notna()]
    avg = i.groupby("i_category").i_current_price.mean()   # skips NULLs
    items = i[i.i_current_price > 1.2 * i.i_category.map(avg)].i_item_sk

    s = _read(data_dir, "store_sales", ["ss_sold_date_sk", "ss_item_sk",
                                        "ss_customer_sk"])
    # both dimension keys are unique, so membership is the inner join
    s = s[s.ss_sold_date_sk.isin(days) & s.ss_item_sk.isin(items)
          & s.ss_customer_sk.notna()]
    c = _read(data_dir, "customer", ["c_customer_sk", "c_current_addr_sk"])
    c = c[c.c_current_addr_sk.notna()]
    a = _read(data_dir, "customer_address", ["ca_address_sk", "ca_state"])
    j = s.merge(c, left_on="ss_customer_sk", right_on="c_customer_sk") \
        .merge(a, left_on="c_current_addr_sk", right_on="ca_address_sk")
    cnt = j.groupby("ca_state", dropna=False).size()
    cnt = cnt[cnt >= 10].sort_values(kind="stable")
    return [(None if pd.isna(state) else str(state), int(n))
            for state, n in cnt.items()][:100]
