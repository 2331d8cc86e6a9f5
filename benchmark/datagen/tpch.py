"""Synthetic TPC-H data generator (pruned, self-consistent, seeded).

The benchmark's own copy of spark_rapids_tpu/bench/tpch_gen.py, so that
a later change there cannot change what is measured; it differs in
speed (lineitem and orders without per-row Python) and in keeping one
stamp a table, never in what it writes.

Reference: integration_tests/.../tpch/TpchLikeSpark.scala defines the 8
TPC-H tables + 22 queries as Spark DataFrame code; this generator
produces the same relational structure (orders->lineitem parentage,
part/supplier cross links) at a requested scale factor, the same way
tpcds_gen.py does for TPC-DS.  It measures engine speed, not dbgen
bit-exactness.
"""
from __future__ import annotations

import os
import shutil

import numpy as np

TABLES = ("region", "nation", "supplier", "customer", "part", "partsupp",
          "orders", "lineitem")

_SCHEMA_VERSION = "v1"

_REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
_NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1)]
_SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY",
             "HOUSEHOLD"]
_PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED",
               "5-LOW"]
_SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
_INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                 "TAKE BACK RETURN"]
_TYPES = [f"{a} {b} {c}" for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE",
                                   "ECONOMY", "PROMO")
          for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED",
                    "BRUSHED")
          for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
_CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
               for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK",
                         "CAN", "DRUM")]

#: dates are DAYS since 1970-01-01 (DateType), TPC-H range 1992..1998
_DATE_LO = 8035    # 1992-01-01
_DATE_HI = 10591   # 1998-12-31


def table_row_counts(sf: float) -> dict[str, int]:
    return {
        "region": 5,
        "nation": 25,
        "supplier": max(10, int(10_000 * sf)),
        "customer": max(30, int(150_000 * sf)),
        "part": max(40, int(200_000 * sf)),
        "partsupp": max(160, int(800_000 * sf)),
        "orders": max(300, int(1_500_000 * sf)),
        "lineitem": max(1200, int(6_000_000 * sf)),
    }


def _gen_region() -> dict[str, np.ndarray]:
    return {
        "r_regionkey": np.arange(5, dtype=np.int32),
        "r_name": np.array(_REGIONS, dtype=object),
        "r_comment": np.array([f"region comment {i}" for i in range(5)],
                              dtype=object),
    }


def _gen_nation() -> dict[str, np.ndarray]:
    return {
        "n_nationkey": np.arange(25, dtype=np.int32),
        "n_name": np.array([n for n, _ in _NATIONS], dtype=object),
        "n_regionkey": np.array([r for _, r in _NATIONS], dtype=np.int32),
        "n_comment": np.array([f"nation comment {i}" for i in range(25)],
                              dtype=object),
    }


def _gen_supplier(rng, n: int) -> dict[str, np.ndarray]:
    comments = np.array([f"supplier comment {i}" for i in range(n)],
                        dtype=object)
    # dbgen plants Complaint/Recommends markers used by q16
    for i in rng.choice(n, size=max(1, n // 100), replace=False):
        comments[i] = f"blah Customer Complaints blah {i}"
    return {
        "s_suppkey": np.arange(1, n + 1, dtype=np.int32),
        "s_name": np.array([f"Supplier#{k:09d}" for k in range(1, n + 1)],
                           dtype=object),
        "s_address": np.array([f"addr {k}" for k in range(n)],
                              dtype=object),
        "s_nationkey": rng.integers(0, 25, n).astype(np.int32),
        "s_phone": np.array([f"{11 + k % 25}-{k % 999:03d}-555-{k % 9999:04d}"
                             for k in range(n)], dtype=object),
        "s_acctbal": np.round(rng.uniform(-999.99, 9999.99, n), 2),
        "s_comment": comments,
    }


def _gen_customer(rng, n: int) -> dict[str, np.ndarray]:
    nat = rng.integers(0, 25, n).astype(np.int32)
    return {
        "c_custkey": np.arange(1, n + 1, dtype=np.int32),
        "c_name": np.array([f"Customer#{k:09d}" for k in range(1, n + 1)],
                           dtype=object),
        "c_address": np.array([f"addr {k}" for k in range(n)],
                              dtype=object),
        "c_nationkey": nat,
        "c_phone": np.array([f"{11 + v}-{k % 999:03d}-555-{k % 9999:04d}"
                             for k, v in enumerate(nat)], dtype=object),
        "c_acctbal": np.round(rng.uniform(-999.99, 9999.99, n), 2),
        "c_mktsegment": np.array([_SEGMENTS[v] for v in
                                  rng.integers(0, 5, n)], dtype=object),
        "c_comment": np.array([f"customer comment {k}" for k in range(n)],
                              dtype=object),
    }


def _gen_part(rng, n: int) -> dict[str, np.ndarray]:
    colors = ["almond", "antique", "aquamarine", "azure", "beige", "bisque",
              "black", "blanched", "blue", "blush", "brown", "burlywood",
              "burnished", "chartreuse", "chiffon", "chocolate", "coral",
              "cornflower", "cornsilk", "cream", "cyan", "dark", "deep",
              "dim", "dodger", "drab", "firebrick", "floral", "forest",
              "frosted", "gainsboro", "ghost", "goldenrod", "green",
              "grey", "honeydew", "hot", "hot pink", "indian", "ivory",
              "khaki", "lace", "lavender", "lawn", "lemon", "light",
              "lime", "linen", "magenta", "maroon", "medium", "metallic",
              "midnight", "mint", "misty", "moccasin", "navajo", "navy",
              "olive", "orange", "orchid", "pale", "papaya", "peach",
              "peru", "pink", "plum", "powder", "puff", "purple", "red",
              "rose", "rosy", "royal", "saddle", "salmon", "sandy",
              "seashell", "sienna", "sky", "slate", "smoke", "snow",
              "spring", "steel", "tan", "thistle", "tomato", "turquoise",
              "violet", "wheat", "white", "yellow"]
    c1 = rng.integers(0, len(colors), n)
    c2 = rng.integers(0, len(colors), n)
    return {
        "p_partkey": np.arange(1, n + 1, dtype=np.int32),
        "p_name": np.array([f"{colors[a]} {colors[b]}"
                            for a, b in zip(c1, c2)], dtype=object),
        "p_mfgr": np.array([f"Manufacturer#{1 + k % 5}" for k in range(n)],
                           dtype=object),
        "p_brand": np.array([f"Brand#{1 + k % 5}{1 + (k // 5) % 5}"
                             for k in range(n)], dtype=object),
        "p_type": np.array([_TYPES[v] for v in
                            rng.integers(0, len(_TYPES), n)], dtype=object),
        "p_size": rng.integers(1, 51, n).astype(np.int32),
        "p_container": np.array([_CONTAINERS[v] for v in
                                 rng.integers(0, len(_CONTAINERS), n)],
                                dtype=object),
        "p_retailprice": np.round(900.0 + rng.uniform(0, 1200, n), 2),
        "p_comment": np.array([f"part comment {k}" for k in range(n)],
                              dtype=object),
    }


def _gen_partsupp(rng, n: int, n_part: int,
                  n_supp: int) -> dict[str, np.ndarray]:
    # 4 suppliers per part, dbgen-style
    part = np.repeat(np.arange(1, n_part + 1, dtype=np.int32), 4)[:n]
    supp = ((part * 7919 + np.tile(np.arange(4), n_part)[:n] *
             (n_supp // 4 + 1)) % n_supp + 1).astype(np.int32)
    m = len(part)
    return {
        "ps_partkey": part,
        "ps_suppkey": supp,
        "ps_availqty": rng.integers(1, 10_000, m).astype(np.int32),
        "ps_supplycost": np.round(rng.uniform(1.0, 1000.0, m), 2),
        "ps_comment": np.array([f"partsupp comment {k}" for k in range(m)],
                               dtype=object),
    }


def _strings(values, codes):
    """Arrow string column ``values[codes]`` with no per-row Python."""
    import pyarrow as pa
    return pa.DictionaryArray.from_arrays(
        pa.array(np.asarray(codes, dtype=np.int32)),
        pa.array(list(values), type=pa.string())).cast(pa.string())


def _numbered(prefix: str, n: int):
    """Arrow string column ``f"{prefix} {k}"`` for k in range(n)."""
    import pyarrow as pa
    import pyarrow.compute as pc
    return pc.binary_join_element_wise(
        pa.scalar(prefix), pa.array(np.arange(n)).cast(pa.string()), " ")


def _gen_orders(rng, n: int, n_cust: int) -> dict[str, np.ndarray]:
    odate = rng.integers(_DATE_LO, _DATE_HI - 121, n).astype(np.int32)
    return {
        "o_orderkey": np.arange(1, n + 1, dtype=np.int32),
        # dbgen: only ~2/3 of customers have orders
        "o_custkey": (rng.integers(1, max(n_cust * 2 // 3, 2), n)
                      .astype(np.int32)),
        "o_orderstatus": _strings(("F", "O", "P"), rng.integers(0, 3, n)),
        "o_totalprice": np.round(rng.uniform(800.0, 500_000.0, n), 2),
        "o_orderdate": odate,
        "o_orderpriority": _strings(_PRIORITIES, rng.integers(0, 5, n)),
        "o_clerk": _strings([f"Clerk#{k:09d}" for k in range(1000)],
                            np.arange(n) % 1000),
        "o_shippriority": np.zeros(n, dtype=np.int32),
        "o_comment": _numbered("order comment", n),
    }


def _gen_lineitem(rng, n: int, orders: dict,
                  n_part: int, n_supp: int) -> dict[str, np.ndarray]:
    """The original's lineitem, draw for draw, with its per-row Python
    (a 6M-step line-number loop and five list comprehensions at SF1)
    done by numpy and Arrow (orders likewise);
    benchmark/tests/test_datagen.py holds the output to the original's."""
    n_ord = len(orders["o_orderkey"])
    # ~4 lines per order, line numbers 1..7
    oidx = np.sort(rng.integers(0, n_ord, n))
    okey = orders["o_orderkey"][oidx]
    odate = orders["o_orderdate"][oidx].astype(np.int64)
    # position within each run of equal order keys, counted from 1
    row = np.arange(n, dtype=np.int64)
    first = np.concatenate([[True], okey[1:] != okey[:-1]])
    linenumber = row - np.maximum.accumulate(np.where(first, row, 0)) + 1
    qty = rng.integers(1, 51, n).astype(np.int32)
    price = np.round(rng.uniform(900.0, 2100.0, n) * qty, 2)
    disc = np.round(rng.integers(0, 11, n) * 0.01, 2)
    tax = np.round(rng.integers(0, 9, n) * 0.01, 2)
    ship = odate + rng.integers(1, 122, n)
    commit = odate + rng.integers(30, 91, n)
    receipt = ship + rng.integers(1, 31, n)
    # 1995-06-17-ish split, dbgen uses receipt date
    returnflag = np.where(receipt <= 9204, rng.integers(0, 2, n), 2)
    return {
        "l_orderkey": okey.astype(np.int32),
        "l_partkey": rng.integers(1, n_part + 1, n).astype(np.int32),
        "l_suppkey": rng.integers(1, n_supp + 1, n).astype(np.int32),
        "l_linenumber": linenumber.astype(np.int32),
        "l_quantity": qty.astype(np.float64),
        "l_extendedprice": price,
        "l_discount": disc,
        "l_tax": tax,
        "l_returnflag": _strings(("R", "A", "N"), returnflag),
        "l_linestatus": _strings(("F", "O"), ship > 9204),
        "l_shipdate": ship.astype(np.int32),
        "l_commitdate": commit.astype(np.int32),
        "l_receiptdate": receipt.astype(np.int32),
        "l_shipinstruct": _strings(_INSTRUCTIONS, rng.integers(0, 4, n)),
        "l_shipmode": _strings(_SHIPMODES, rng.integers(0, 7, n)),
        "l_comment": _numbered("line comment", n),
    }


_DATE_COLS = {"o_orderdate", "l_shipdate", "l_commitdate",
              "l_receiptdate"}


def _write_parquet(path: str, data: dict, date_cols=()) -> None:
    import pyarrow as pa
    import pyarrow.parquet as pq
    arrays, names = [], []
    for name, arr in data.items():
        if name in _DATE_COLS:
            arrays.append(pa.array(np.asarray(arr, dtype=np.int32),
                                   type=pa.date32()))
        elif isinstance(arr, np.ndarray) and arr.dtype == object:
            arrays.append(pa.array(arr.tolist()))
        else:
            arrays.append(pa.array(arr))
        names.append(name)
    os.makedirs(path, exist_ok=True)
    pq.write_table(pa.Table.from_arrays(arrays, names=names),
                   os.path.join(path, "part-0.parquet"))


def generate_tpch(data_dir: str, sf: float = 0.01, seed: int = 7,
                  tables=None) -> dict[str, int]:
    """Generate (or re-use) the wanted TPC-H tables under ``data_dir``;
    returns {table: rows}.  One stamp per table, as the TPC-DS generator
    keeps them.  All eight tables share one random stream, so a missing
    table means generating all of them in memory, in order."""
    counts = table_row_counts(sf)
    want = [t for t in TABLES if t in set(tables or TABLES)]
    stamp = f"_{_SCHEMA_VERSION}_sf{sf:g}_seed{seed}"
    missing = [t for t in want
               if not os.path.exists(os.path.join(data_dir, t, stamp))]
    if not missing:
        return {t: counts[t] for t in want}
    rng = np.random.default_rng(seed)
    datasets: dict[str, dict] = {}
    datasets["region"] = _gen_region()
    datasets["nation"] = _gen_nation()
    datasets["supplier"] = _gen_supplier(rng, counts["supplier"])
    datasets["customer"] = _gen_customer(rng, counts["customer"])
    datasets["part"] = _gen_part(rng, counts["part"])
    datasets["partsupp"] = _gen_partsupp(rng, counts["partsupp"],
                                         counts["part"],
                                         counts["supplier"])
    datasets["orders"] = _gen_orders(rng, counts["orders"],
                                     counts["customer"])
    datasets["lineitem"] = _gen_lineitem(rng, counts["lineitem"],
                                         datasets["orders"],
                                         counts["part"],
                                         counts["supplier"])
    for t in missing:
        out = os.path.join(data_dir, t)
        shutil.rmtree(out, ignore_errors=True)
        _write_parquet(out, datasets[t])
        with open(os.path.join(out, stamp), "w") as f:
            f.write(stamp + "\n")
    return {t: counts[t] for t in want}


#: the harness's entry point, the same in every datagen file:
#: ``generate(data_dir, sf, seed, tables)``
generate = generate_tpch
