"""SF-scalable TPC-DS-shaped data generator (column-pruned, parquet).

The benchmark's own copy of spark_rapids_tpu/bench/tpcds_gen.py, so that
a later change there cannot change what is measured; it differs in
speed (nullable numbers as masked arrays, files written by a thread
pool), never in what it writes.

Generates the tables the 20-query slice uses — store_sales, catalog_sales,
web_sales, date_dim, time_dim, item, customer, customer_address, store,
customer_demographics, household_demographics, promotion — with
dsdgen-like row counts, key ranges, null fractions, and surrogate-key
conventions (d_date_sk epoch 2415022 = 1900-01-01, store_sales ~2.88M
rows/SF).  Columns are pruned to those the queries touch; distributions
are synthetic (deterministic numpy, seeded), NOT dsdgen bit-exact — this
measures engine speed, not dsdgen conformance.  Reference harness:
TpcdsLikeSpark.scala (explicit schemas + csv-to-parquet conversion),
docs/benchmarks.md:104-147.
"""
from __future__ import annotations

import os
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Sequence

import numpy as np

__all__ = ["generate_tpcds", "table_row_counts", "TABLES"]

TABLES = ("date_dim", "time_dim", "item", "customer", "customer_address",
          "store", "customer_demographics", "household_demographics",
          "promotion", "warehouse", "ship_mode", "reason", "income_band",
          "call_center", "web_site", "web_page", "catalog_page",
          "inventory", "store_sales", "store_returns",
          "catalog_sales", "catalog_returns", "web_sales", "web_returns")

#: bump when generated schemas change; tables regenerate on mismatch
_SCHEMA_VERSION = "v7"

#: returns tables are sampled FROM their parent's rows so that joins on
#: (item_sk, ticket/order number) actually match (dsdgen links them the
#: same way); generated right after the parent from its in-memory data
_RETURNS_PARENT = {"store_returns": "store_sales",
                   "catalog_returns": "catalog_sales",
                   "web_returns": "web_sales"}

_DATE_SK_EPOCH = 2415022            # dsdgen: d_date_sk of 1900-01-01
_DATE_DIM_DAYS = 73049              # 1900-01-01 .. 2099-12-31
_SALES_DATE_LO = 35794              # days(1998-01-01 - 1900-01-01)
_SALES_DATE_HI = 37985              # days(2003-12-31 - 1900-01-01)
_UNIX_EPOCH_OFF = 25567             # days(1970-01-01 - 1900-01-01)

_CATEGORIES = ["Books", "Children", "Electronics", "Home", "Jewelry",
               "Men", "Music", "Shoes", "Sports", "Women"]
_CLASSES = ["accent", "bedding", "birdal", "blinds/shades", "classical",
            "computers", "curtains/drapes", "decor", "dresses", "earings",
            "fiction", "fragrances", "furniture", "glassware", "history",
            "infants", "jewelry boxes", "kids", "maternity", "mattresses",
            "mens", "musical", "mystery", "pants", "pendants", "pop",
            "reference", "rock", "romance", "rugs", "scanners", "shirts",
            "swimwear", "tables", "wallpaper", "womens"]
_STATES = ["AL", "AK", "AZ", "AR", "CA", "CO", "CT", "DE", "FL", "GA",
           "HI", "ID", "IL", "IN", "IA", "KS", "KY", "LA", "ME", "MD",
           "MA", "MI", "MN", "MS", "MO", "MT", "NE", "NV", "NH", "NJ",
           "NM", "NY", "NC", "ND", "OH", "OK", "OR", "PA", "RI", "SC",
           "SD", "TN", "TX", "UT", "VT", "VA", "WA", "WV", "WI", "WY"]
_FIRST = ["James", "Mary", "John", "Patricia", "Robert", "Jennifer",
          "Michael", "Linda", "William", "Elizabeth", "David", "Barbara"]
_LAST = ["Smith", "Johnson", "Williams", "Brown", "Jones", "Garcia",
         "Miller", "Davis", "Rodriguez", "Martinez", "Hernandez", "Lopez"]


def table_row_counts(sf: float) -> dict[str, int]:
    """dsdgen-like scaling: fact tables linear in SF; dimensions
    sublinear (item SF1=18k, customer SF1=100k)."""
    sf = max(sf, 0.001)
    n_cust = max(200, int(100_000 * sf ** 0.7))
    n_ss = max(1000, int(2_880_000 * sf))
    n_cs = max(500, int(1_440_000 * sf))
    n_ws = max(250, int(720_000 * sf))
    return {
        "date_dim": _DATE_DIM_DAYS,
        "time_dim": 86_400,
        "item": max(100, int(18_000 * sf ** 0.5)),
        "customer": n_cust,
        "customer_address": max(100, n_cust // 2),
        "store": max(4, int(12 * sf ** 0.5)),
        "customer_demographics": max(500, int(50_000 * sf ** 0.5)),
        "household_demographics": 7_200,
        "promotion": max(30, int(300 * sf ** 0.5)),
        "warehouse": max(2, int(5 * sf ** 0.5)),
        "ship_mode": 20,
        "reason": 35,
        "income_band": 20,
        "call_center": max(2, int(6 * sf ** 0.25)),
        "web_site": max(2, int(30 * sf ** 0.25)),
        "web_page": max(10, int(60 * sf ** 0.25)),
        "catalog_page": max(100, int(11_000 * sf ** 0.25)),
        # dsdgen inventory is (items x warehouses x weeks); sampled to a
        # bench-sized subset that still exercises the same join/agg shapes
        "inventory": max(5000, int(1_200_000 * sf)),
        "store_sales": n_ss,
        "store_returns": max(100, n_ss // 10),
        "catalog_sales": n_cs,
        "catalog_returns": max(50, n_cs // 10),
        "web_sales": n_ws,
        "web_returns": max(25, n_ws // 10),
    }


def _gen_date_dim(counts) -> dict[str, np.ndarray]:
    days = np.arange(_DATE_DIM_DAYS, dtype=np.int64)
    dates = np.datetime64("1900-01-01") + days
    y = dates.astype("datetime64[Y]").astype(int) + 1970
    m = dates.astype("datetime64[M]").astype(int) % 12 + 1
    dom = (dates - dates.astype("datetime64[M]")).astype(int) + 1
    dow = (days + 1) % 7            # 1900-01-01 was a Monday; 0 = Sunday
    day_names = np.array(["Sunday", "Monday", "Tuesday", "Wednesday",
                          "Thursday", "Friday", "Saturday"], dtype=object)
    q = ((m - 1) // 3 + 1)
    return {
        "d_date_sk": (days + _DATE_SK_EPOCH).astype(np.int32),
        "d_date": (days - _UNIX_EPOCH_OFF).astype(np.int32),  # DateType
        "d_year": y.astype(np.int32),
        "d_moy": m.astype(np.int32),
        "d_dom": dom.astype(np.int32),
        "d_dow": dow.astype(np.int32),
        "d_month_seq": ((y - 1900) * 12 + (m - 1)).astype(np.int32),
        "d_qoy": q.astype(np.int32),
        # weeks start Sunday (dow 0); 1900-01-01 (Monday) is in week 1
        "d_week_seq": ((days + 1) // 7 + 1).astype(np.int32),
        "d_day_name": day_names[dow],
        "d_quarter_name": np.array([f"{yy}Q{qq}" for yy, qq in zip(y, q)],
                                   dtype=object),
    }


def _gen_time_dim(_counts) -> dict[str, np.ndarray]:
    secs = np.arange(86_400, dtype=np.int64)
    return {
        "t_time_sk": secs.astype(np.int32),
        "t_time": secs.astype(np.int32),  # seconds since midnight (dsdgen)
        "t_hour": (secs // 3600).astype(np.int32),
        "t_minute": ((secs // 60) % 60).astype(np.int32),
        # dsdgen meal-time bands; NULL outside them
        "t_meal_time": np.where(
            (secs >= 6 * 3600) & (secs < 9 * 3600), "breakfast",
            np.where((secs >= 12 * 3600) & (secs < 14 * 3600), "lunch",
                     np.where((secs >= 17 * 3600) & (secs < 21 * 3600),
                              "dinner", None))).astype(object),
    }


def _with_nulls(rng, arr: np.ndarray, frac: float) -> np.ndarray:
    """~frac nulls: a masked array for numbers (written to Arrow with
    its mask, so no per-row Python objects — the original's object
    arrays were most of SF10's 94 s), an object array holding None for
    strings.  The random draws are the original's, one for one."""
    if arr.dtype == object:
        out = arr.astype(object)
        if frac > 0:
            out[rng.random(len(arr)) < frac] = None
        return out
    nulls = (rng.random(len(arr)) < frac if frac > 0
             else np.zeros(len(arr), dtype=bool))
    return np.ma.masked_array(arr, mask=nulls)


def _as_objects(data: dict) -> dict:
    """Masked columns back to object arrays holding None — the form the
    returns generators sample their parent's rows in."""
    out = {}
    for name, arr in data.items():
        if isinstance(arr, np.ma.MaskedArray):
            obj = arr.data.astype(object)
            obj[np.ma.getmaskarray(arr)] = None
            arr = obj
        out[name] = arr
    return out


def _gen_item(rng, n: int) -> dict[str, np.ndarray]:
    brand_id = rng.integers(1001001, 1010016, n).astype(np.int32)
    cat_idx = rng.integers(0, len(_CATEGORIES), n)
    cls_idx = rng.integers(0, len(_CLASSES), n)
    manu = rng.integers(1, 1001, n).astype(np.int32)
    return {
        "i_item_sk": np.arange(1, n + 1, dtype=np.int32),
        "i_item_id": np.array([f"AAAAAAAA{k:08d}" for k in range(1, n + 1)],
                              dtype=object),
        "i_item_desc": np.array(
            [f"desc {k} {_CLASSES[c]}" for k, c in enumerate(cls_idx)],
            dtype=object),
        "i_brand_id": brand_id,
        "i_brand": np.array([f"Brand#{b % 100}" for b in brand_id],
                            dtype=object),
        "i_class_id": (cls_idx + 1).astype(np.int32),
        "i_class": np.array([_CLASSES[i] for i in cls_idx], dtype=object),
        "i_category_id": (cat_idx + 1).astype(np.int32),
        "i_category": _with_nulls(
            rng, np.array([_CATEGORIES[i] for i in cat_idx], dtype=object),
            0.005),
        "i_current_price": _with_nulls(
            rng, np.round(rng.uniform(0.09, 99.99, n), 2), 0.01),
        "i_manufact_id": manu,
        "i_manufact": np.array([f"manufact#{v}" for v in manu], dtype=object),
        "i_manager_id": rng.integers(1, 101, n).astype(np.int32),
        "i_size": np.array([("small", "medium", "large", "extra large",
                             "economy", "N/A", "petite")[v]
                            for v in rng.integers(0, 7, n)], dtype=object),
        "i_color": np.array([("red", "blue", "green", "yellow", "pale",
                              "chiffon", "smoke", "orchid", "peach",
                              "saddle", "powder", "burnished")[v]
                             for v in rng.integers(0, 12, n)], dtype=object),
        "i_units": np.array([("Each", "Dozen", "Case", "Pallet", "Gross",
                              "Oz", "Lb", "Ton")[v]
                             for v in rng.integers(0, 8, n)], dtype=object),
        "i_product_name": np.array([f"product{k}" for k in range(1, n + 1)],
                                   dtype=object),
        "i_wholesale_cost": np.round(rng.uniform(0.05, 80.0, n), 2),
    }


def _gen_customer(rng, n: int, n_addr: int, n_cdemo: int,
                  n_hdemo: int) -> dict[str, np.ndarray]:
    return {
        "c_customer_sk": np.arange(1, n + 1, dtype=np.int32),
        "c_customer_id": np.array(
            [f"AAAAAAAA{k:08d}" for k in range(1, n + 1)], dtype=object),
        "c_current_addr_sk": _with_nulls(
            rng, rng.integers(1, n_addr + 1, n).astype(np.int32), 0.01),
        "c_current_cdemo_sk": _with_nulls(
            rng, rng.integers(1, n_cdemo + 1, n).astype(np.int32), 0.01),
        "c_current_hdemo_sk": _with_nulls(
            rng, rng.integers(1, n_hdemo + 1, n).astype(np.int32), 0.01),
        "c_first_name": _with_nulls(
            rng, np.array([_FIRST[i] for i in
                           rng.integers(0, len(_FIRST), n)], dtype=object),
            0.01),
        "c_last_name": _with_nulls(
            rng, np.array([_LAST[i] for i in
                           rng.integers(0, len(_LAST), n)], dtype=object),
            0.01),
        "c_salutation": _with_nulls(
            rng, np.array([("Mr.", "Mrs.", "Ms.", "Dr.", "Miss", "Sir")[v]
                           for v in rng.integers(0, 6, n)], dtype=object),
            0.01),
        "c_preferred_cust_flag": _with_nulls(
            rng, np.array([("Y", "N")[v] for v in rng.integers(0, 2, n)],
                          dtype=object), 0.03),
        "c_birth_year": _with_nulls(
            rng, rng.integers(1924, 1993, n).astype(np.int32), 0.02),
        "c_birth_month": _with_nulls(
            rng, rng.integers(1, 13, n).astype(np.int32), 0.02),
        "c_birth_day": _with_nulls(
            rng, rng.integers(1, 29, n).astype(np.int32), 0.02),
        "c_birth_country": _with_nulls(
            rng, np.array([("UNITED STATES", "CANADA", "MEXICO", "FRANCE",
                            "GERMANY", "JAPAN", "BRAZIL", "INDIA")[v]
                           for v in rng.integers(0, 8, n)], dtype=object),
            0.02),
        "c_first_sales_date_sk": _with_nulls(
            rng, (rng.integers(_SALES_DATE_LO - 1500, _SALES_DATE_HI - 300,
                               n) + _DATE_SK_EPOCH).astype(np.int32), 0.03),
        "c_first_shipto_date_sk": _with_nulls(
            rng, (rng.integers(_SALES_DATE_LO - 1400, _SALES_DATE_HI - 200,
                               n) + _DATE_SK_EPOCH).astype(np.int32), 0.03),
        "c_email_address": np.array(
            [f"user{k}@example.com" for k in range(1, n + 1)], dtype=object),
        # dsdgen leaves c_login almost entirely NULL
        "c_login": _with_nulls(
            rng, np.array([f"login{k}" for k in range(1, n + 1)],
                          dtype=object), 0.95),
        # StringType in the reference schema (TpcdsLikeSpark.scala:442)
        "c_last_review_date": _with_nulls(
            rng, np.array([str(_DATE_SK_EPOCH + int(v)) for v in
                           rng.integers(_SALES_DATE_LO, _SALES_DATE_HI, n)],
                          dtype=object), 0.05),
    }


def _gen_customer_address(rng, n: int) -> dict[str, np.ndarray]:
    return {
        "ca_address_sk": np.arange(1, n + 1, dtype=np.int32),
        "ca_state": _with_nulls(
            rng, np.array([_STATES[i] for i in
                           rng.integers(0, len(_STATES), n)], dtype=object),
            0.01),
        "ca_city": np.array([f"City{v:03d}" for v in
                             rng.integers(0, 400, n)], dtype=object),
        "ca_county": np.array([f"County{v:03d}" for v in
                               rng.integers(0, 200, n)], dtype=object),
        "ca_zip": np.array([f"{v:05d}" for v in
                            rng.integers(10000, 99999, n)], dtype=object),
        "ca_gmt_offset": rng.choice([-10.0, -9.0, -8.0, -7.0, -6.0, -5.0],
                                    n),
        "ca_country": _with_nulls(
            rng, np.array(["United States"] * n, dtype=object), 0.005),
        "ca_street_number": np.array([f"{v}" for v in
                                      rng.integers(1, 1000, n)],
                                     dtype=object),
        "ca_street_name": np.array([f"Street{v:03d}" for v in
                                    rng.integers(0, 300, n)], dtype=object),
        "ca_street_type": _with_nulls(
            rng, np.array([("Street", "Ave", "Blvd", "Ct", "Dr", "Ln")[v]
                           for v in rng.integers(0, 6, n)], dtype=object),
            0.01),
        "ca_suite_number": _with_nulls(
            rng, np.array([f"Suite {v}" for v in rng.integers(0, 100, n)],
                          dtype=object), 0.01),
        "ca_location_type": _with_nulls(
            rng, np.array([("apartment", "condo", "single family")[v]
                           for v in rng.integers(0, 3, n)], dtype=object),
            0.01),
    }


def _gen_store(rng, n: int) -> dict[str, np.ndarray]:
    return {
        "s_store_sk": np.arange(1, n + 1, dtype=np.int32),
        "s_store_id": np.array([f"AAAAAAAA{k:08d}" for k in range(1, n + 1)],
                               dtype=object),
        "s_store_name": np.array(
            [["ought", "able", "pri", "ese", "anti", "cally", "ation",
              "eing"][k % 8] for k in range(n)], dtype=object),
        "s_state": np.array([_STATES[i] for i in
                             rng.integers(0, 10, n)], dtype=object),
        "s_county": np.array([f"County{v:03d}" for v in
                              rng.integers(0, 30, n)], dtype=object),
        "s_city": np.array([f"City{v:03d}" for v in
                            rng.integers(0, 40, n)], dtype=object),
        "s_company_id": rng.integers(1, 7, n).astype(np.int32),
        "s_company_name": np.array(["Unknown"] * n, dtype=object),
        "s_gmt_offset": np.array([(-8.0, -7.0, -6.0, -5.0)[k % 4]
                                  for k in range(n)]),
        "s_number_employees": rng.integers(200, 301, n).astype(np.int32),
        "s_floor_space": rng.integers(5_000_000, 10_000_000,
                                      n).astype(np.int32),
        "s_market_id": rng.integers(1, 11, n).astype(np.int32),
        "s_zip": np.array([f"{v:05d}" for v in
                           rng.integers(10000, 99999, n)], dtype=object),
        "s_street_number": np.array([f"{v}" for v in
                                     rng.integers(1, 1000, n)], dtype=object),
        "s_street_name": np.array([f"Street{v:03d}" for v in
                                   rng.integers(0, 300, n)], dtype=object),
        "s_street_type": np.array([("Street", "Ave", "Blvd", "Ct")[k % 4]
                                   for k in range(n)], dtype=object),
        "s_suite_number": np.array([f"Suite {v}" for v in
                                    rng.integers(0, 100, n)], dtype=object),
    }


def _gen_customer_demographics(rng, n: int) -> dict[str, np.ndarray]:
    eds = ["Primary", "Secondary", "College", "2 yr Degree", "4 yr Degree",
           "Advanced Degree", "Unknown"]
    return {
        "cd_demo_sk": np.arange(1, n + 1, dtype=np.int32),
        "cd_gender": np.array([("M", "F")[v] for v in
                               rng.integers(0, 2, n)], dtype=object),
        "cd_marital_status": np.array(
            [("M", "S", "D", "W", "U")[v] for v in rng.integers(0, 5, n)],
            dtype=object),
        "cd_education_status": np.array(
            [eds[v] for v in rng.integers(0, len(eds), n)], dtype=object),
        "cd_purchase_estimate": (rng.integers(1, 21, n) * 500).astype(
            np.int32),
        "cd_credit_rating": np.array(
            [("Low Risk", "Good", "High Risk", "Unknown")[v]
             for v in rng.integers(0, 4, n)], dtype=object),
        "cd_dep_count": rng.integers(0, 7, n).astype(np.int32),
        "cd_dep_employed_count": rng.integers(0, 7, n).astype(np.int32),
        "cd_dep_college_count": rng.integers(0, 7, n).astype(np.int32),
    }


def _gen_household_demographics(rng, n: int) -> dict[str, np.ndarray]:
    return {
        "hd_demo_sk": np.arange(1, n + 1, dtype=np.int32),
        "hd_dep_count": rng.integers(0, 10, n).astype(np.int32),
        "hd_vehicle_count": rng.integers(-1, 5, n).astype(np.int32),
        "hd_buy_potential": np.array(
            [(">10000", "5001-10000", "1001-5000", "501-1000", "0-500",
              "Unknown")[v] for v in rng.integers(0, 6, n)], dtype=object),
        "hd_income_band_sk": rng.integers(1, 21, n).astype(np.int32),
    }


def _gen_warehouse(rng, n: int) -> dict[str, np.ndarray]:
    return {
        "w_warehouse_sk": np.arange(1, n + 1, dtype=np.int32),
        "w_warehouse_name": np.array([f"Warehouse {k}" for k in
                                      range(1, n + 1)], dtype=object),
        "w_warehouse_sq_ft": rng.integers(50_000, 1_000_000,
                                          n).astype(np.int32),
        "w_city": np.array([f"City{v:03d}" for v in
                            rng.integers(0, 40, n)], dtype=object),
        "w_county": np.array([f"County{v:03d}" for v in
                              rng.integers(0, 30, n)], dtype=object),
        "w_state": np.array([_STATES[i] for i in rng.integers(0, 10, n)],
                            dtype=object),
        "w_country": np.array(["United States"] * n, dtype=object),
    }


def _gen_ship_mode(rng, n: int) -> dict[str, np.ndarray]:
    types = ("EXPRESS", "NEXT DAY", "OVERNIGHT", "REGULAR", "TWO DAY")
    carriers = ("UPS", "FEDEX", "AIRBORNE", "USPS", "DHL", "TBS", "ZHOU",
                "LATVIAN", "DIAMOND", "BARIAN")
    return {
        "sm_ship_mode_sk": np.arange(1, n + 1, dtype=np.int32),
        "sm_type": np.array([types[k % len(types)] for k in range(n)],
                            dtype=object),
        "sm_carrier": np.array([carriers[k % len(carriers)]
                                for k in range(n)], dtype=object),
        "sm_code": np.array([("AIR", "SURFACE", "SEA", "LIBRARY")[k % 4]
                             for k in range(n)], dtype=object),
    }


def _gen_reason(rng, n: int) -> dict[str, np.ndarray]:
    return {
        "r_reason_sk": np.arange(1, n + 1, dtype=np.int32),
        "r_reason_desc": np.array(
            [f"reason {k}" for k in range(1, n + 1)], dtype=object),
    }


def _gen_income_band(rng, n: int) -> dict[str, np.ndarray]:
    sk = np.arange(1, n + 1, dtype=np.int32)
    return {
        "ib_income_band_sk": sk,
        "ib_lower_bound": ((sk - 1) * 10_000).astype(np.int32),
        "ib_upper_bound": (sk * 10_000 - 1).astype(np.int32),
    }


def _gen_call_center(rng, n: int) -> dict[str, np.ndarray]:
    return {
        "cc_call_center_sk": np.arange(1, n + 1, dtype=np.int32),
        "cc_call_center_id": np.array(
            [f"AAAAAAAA{k:08d}" for k in range(1, n + 1)], dtype=object),
        "cc_name": np.array([f"call center {k}" for k in range(1, n + 1)],
                            dtype=object),
        "cc_manager": np.array(
            [f"{_FIRST[rng.integers(0, len(_FIRST))]} "
             f"{_LAST[rng.integers(0, len(_LAST))]}" for _ in range(n)],
            dtype=object),
        "cc_county": np.array([f"County{v:03d}" for v in
                               rng.integers(0, 30, n)], dtype=object),
    }


def _gen_web_site(rng, n: int) -> dict[str, np.ndarray]:
    return {
        "web_site_sk": np.arange(1, n + 1, dtype=np.int32),
        "web_site_id": np.array(
            [f"AAAAAAAA{k:08d}" for k in range(1, n + 1)], dtype=object),
        "web_name": np.array([f"site_{k % 30}" for k in range(n)],
                             dtype=object),
        "web_company_name": np.array(
            [("pri", "ought", "able", "ese", "anti", "cally")[k % 6]
             for k in range(n)], dtype=object),
    }


def _gen_web_page(rng, n: int) -> dict[str, np.ndarray]:
    return {
        "wp_web_page_sk": np.arange(1, n + 1, dtype=np.int32),
        "wp_char_count": rng.integers(100, 8_000, n).astype(np.int32),
    }


def _gen_catalog_page(rng, n: int) -> dict[str, np.ndarray]:
    return {
        "cp_catalog_page_sk": np.arange(1, n + 1, dtype=np.int32),
        "cp_catalog_page_id": np.array(
            [f"AAAAAAAA{k:08d}" for k in range(1, n + 1)], dtype=object),
    }


def _gen_inventory(rng, n: int, counts) -> dict[str, np.ndarray]:
    # weekly snapshot dates across the sales window (dsdgen convention);
    # (date, item, warehouse) triples sampled instead of the full cross
    # product (bench-sized; the join/agg shapes are what matter)
    weeks = np.arange(_SALES_DATE_LO, _SALES_DATE_HI + 1, 7, dtype=np.int64)
    return {
        "inv_date_sk": (rng.choice(weeks, n)
                        + _DATE_SK_EPOCH).astype(np.int32),
        "inv_item_sk": rng.integers(1, counts["item"] + 1,
                                    n).astype(np.int32),
        "inv_warehouse_sk": rng.integers(1, counts["warehouse"] + 1,
                                         n).astype(np.int32),
        "inv_quantity_on_hand": _with_nulls(
            rng, rng.integers(0, 1_000, n).astype(np.int32), 0.02),
    }


def _gen_promotion(rng, n: int) -> dict[str, np.ndarray]:
    yn = lambda frac: np.array(  # noqa: E731
        [("Y" if v else "N") for v in rng.random(n) < frac], dtype=object)
    return {
        "p_promo_sk": np.arange(1, n + 1, dtype=np.int32),
        "p_channel_email": yn(0.1),
        "p_channel_event": yn(0.15),
        "p_channel_dmail": yn(0.1),
        "p_channel_tv": yn(0.1),
    }


def _sales_common(rng, n, counts, prefix):
    qty = rng.integers(1, 101, n).astype(np.int32)
    price = np.round(np.exp(rng.normal(2.5, 1.0, n)).clip(0.01, 300.0), 2)
    wholesale = np.round(price * rng.uniform(0.3, 0.9, n), 2)
    ext = np.round(price * qty, 2)
    return qty, price, wholesale, ext


def _unique_tickets(item: np.ndarray, ticket: np.ndarray,
                    first_free: int) -> np.ndarray:
    """``ticket`` with every (item, ticket) pair made unique, as
    store_sales' primary key has it in dsdgen: the later rows of a pair
    drawn twice get the tickets ``first_free``, ``first_free + 1``, …
    in row order.  Nothing is drawn, so the random stream, every other
    column and every other row stay as they were (about 26 rows a
    million at any scale: n / 2 pairs of n × n / 3 keys × 57k items)."""
    key = (item.astype(np.int64) << 32) | ticket
    # the keys drawn more than once (a plain sort: an argsort of every
    # row costs 10 s at SF10), then the few rows that hold them
    ordered = np.sort(key)
    twice = np.unique(ordered[1:][ordered[1:] == ordered[:-1]])
    if not len(twice):
        return ticket
    at = np.minimum(np.searchsorted(twice, key), len(twice) - 1)
    rows = np.flatnonzero(twice[at] == key)         # in row order
    order = np.argsort(key[rows], kind="stable")
    held = key[rows][order]
    again = np.sort(rows[order][1:][held[1:] == held[:-1]])
    out = ticket.copy()
    out[again] = first_free + np.arange(len(again), dtype=ticket.dtype)
    return out


def _gen_store_sales(rng, n: int, counts) -> dict[str, np.ndarray]:
    data = _draw_store_sales(rng, n, counts)
    data["ss_ticket_number"] = _unique_tickets(
        data["ss_item_sk"], data["ss_ticket_number"], max(n // 3, 2))
    return data


def _draw_store_sales(rng, n: int, counts) -> dict[str, np.ndarray]:
    """store_sales as the repo's generator draws it (tickets at random,
    so a few (item, ticket) pairs come twice)."""
    qty, price, wholesale, ext = _sales_common(rng, n, counts, "ss")
    return {
        "ss_sold_date_sk": _with_nulls(
            rng, (rng.integers(_SALES_DATE_LO, _SALES_DATE_HI + 1, n)
                  + _DATE_SK_EPOCH).astype(np.int32), 0.02),
        "ss_sold_time_sk": _with_nulls(
            rng, rng.integers(0, 86_400, n).astype(np.int32), 0.02),
        "ss_item_sk": rng.integers(1, counts["item"] + 1, n).astype(np.int32),
        "ss_customer_sk": _with_nulls(
            rng, rng.integers(1, counts["customer"] + 1, n).astype(np.int32),
            0.04),
        "ss_cdemo_sk": _with_nulls(
            rng, rng.integers(1, counts["customer_demographics"] + 1,
                              n).astype(np.int32), 0.04),
        "ss_hdemo_sk": _with_nulls(
            rng, rng.integers(1, counts["household_demographics"] + 1,
                              n).astype(np.int32), 0.04),
        "ss_store_sk": _with_nulls(
            rng, rng.integers(1, counts["store"] + 1, n).astype(np.int32),
            0.02),
        "ss_promo_sk": _with_nulls(
            rng, rng.integers(1, counts["promotion"] + 1, n).astype(np.int32),
            0.02),
        "ss_ticket_number": rng.integers(1, max(n // 3, 2),
                                         n).astype(np.int64),
        "ss_addr_sk": _with_nulls(
            rng, rng.integers(1, counts["customer_address"] + 1,
                              n).astype(np.int32), 0.03),
        "ss_quantity": qty,
        "ss_list_price": np.round(price * rng.uniform(1.0, 1.5, n), 2),
        "ss_sales_price": price,
        "ss_ext_sales_price": ext,
        "ss_ext_list_price": np.round(price * rng.uniform(1.0, 1.5, n)
                                      * qty, 2),
        "ss_ext_discount_amt": np.round(
            ext * rng.choice([0.0, 0.0, 0.05, 0.2], n), 2),
        "ss_ext_tax": np.round(ext * 0.08, 2),
        "ss_wholesale_cost": wholesale,
        "ss_ext_wholesale_cost": np.round(wholesale * qty, 2),
        "ss_coupon_amt": np.round(
            ext * rng.choice([0.0, 0.0, 0.0, 0.1, 0.3], n), 2),
        "ss_net_paid": np.round(ext * rng.uniform(0.7, 1.0, n), 2),
        "ss_net_paid_inc_tax": np.round(ext * 1.08, 2),
        "ss_net_profit": np.round(ext - wholesale * qty, 2),
    }


def _gen_catalog_sales(rng, n: int, counts) -> dict[str, np.ndarray]:
    qty, price, wholesale, ext = _sales_common(rng, n, counts, "cs")
    sold = (rng.integers(_SALES_DATE_LO, _SALES_DATE_HI + 1, n)
            + _DATE_SK_EPOCH).astype(np.int64)
    return {
        "cs_sold_date_sk": _with_nulls(rng, sold.astype(np.int32), 0.02),
        "cs_sold_time_sk": _with_nulls(
            rng, rng.integers(0, 86_400, n).astype(np.int32), 0.02),
        "cs_ship_date_sk": _with_nulls(
            rng, (sold + rng.integers(1, 120, n)).astype(np.int32), 0.02),
        "cs_item_sk": rng.integers(1, counts["item"] + 1, n).astype(np.int32),
        "cs_order_number": rng.integers(1, max(n // 2, 2),
                                        n).astype(np.int64),
        "cs_bill_customer_sk": _with_nulls(
            rng, rng.integers(1, counts["customer"] + 1, n).astype(np.int32),
            0.03),
        "cs_bill_cdemo_sk": _with_nulls(
            rng, rng.integers(1, counts["customer_demographics"] + 1,
                              n).astype(np.int32), 0.03),
        "cs_bill_hdemo_sk": _with_nulls(
            rng, rng.integers(1, counts["household_demographics"] + 1,
                              n).astype(np.int32), 0.03),
        "cs_bill_addr_sk": _with_nulls(
            rng, rng.integers(1, counts["customer_address"] + 1,
                              n).astype(np.int32), 0.03),
        "cs_ship_customer_sk": _with_nulls(
            rng, rng.integers(1, counts["customer"] + 1, n).astype(np.int32),
            0.03),
        "cs_ship_addr_sk": _with_nulls(
            rng, rng.integers(1, counts["customer_address"] + 1,
                              n).astype(np.int32), 0.03),
        "cs_ship_mode_sk": _with_nulls(
            rng, rng.integers(1, counts["ship_mode"] + 1,
                              n).astype(np.int32), 0.02),
        "cs_warehouse_sk": _with_nulls(
            rng, rng.integers(1, counts["warehouse"] + 1,
                              n).astype(np.int32), 0.02),
        "cs_call_center_sk": _with_nulls(
            rng, rng.integers(1, counts["call_center"] + 1,
                              n).astype(np.int32), 0.02),
        "cs_catalog_page_sk": _with_nulls(
            rng, rng.integers(1, counts["catalog_page"] + 1,
                              n).astype(np.int32), 0.02),
        "cs_promo_sk": _with_nulls(
            rng, rng.integers(1, counts["promotion"] + 1, n).astype(np.int32),
            0.02),
        "cs_quantity": qty,
        "cs_list_price": np.round(price * rng.uniform(1.0, 1.5, n), 2),
        "cs_sales_price": price,
        "cs_ext_sales_price": ext,
        "cs_ext_list_price": np.round(price * rng.uniform(1.0, 1.5, n)
                                      * qty, 2),
        "cs_ext_discount_amt": np.round(
            ext * rng.choice([0.0, 0.0, 0.05, 0.2], n), 2),
        "cs_ext_ship_cost": np.round(ext * rng.uniform(0.01, 0.1, n), 2),
        "cs_wholesale_cost": wholesale,
        "cs_ext_wholesale_cost": np.round(wholesale * qty, 2),
        "cs_coupon_amt": np.round(
            ext * rng.choice([0.0, 0.0, 0.0, 0.1, 0.3], n), 2),
        "cs_net_paid": np.round(ext * rng.uniform(0.7, 1.0, n), 2),
        "cs_net_paid_inc_tax": np.round(ext * 1.08, 2),
        "cs_net_profit": np.round(ext - wholesale * qty, 2),
    }


def _gen_web_sales(rng, n: int, counts) -> dict[str, np.ndarray]:
    qty, price, wholesale, ext = _sales_common(rng, n, counts, "ws")
    sold = (rng.integers(_SALES_DATE_LO, _SALES_DATE_HI + 1, n)
            + _DATE_SK_EPOCH).astype(np.int64)
    return {
        "ws_sold_date_sk": _with_nulls(rng, sold.astype(np.int32), 0.02),
        "ws_sold_time_sk": _with_nulls(
            rng, rng.integers(0, 86_400, n).astype(np.int32), 0.02),
        "ws_ship_date_sk": _with_nulls(
            rng, (sold + rng.integers(1, 120, n)).astype(np.int32), 0.02),
        "ws_item_sk": rng.integers(1, counts["item"] + 1, n).astype(np.int32),
        "ws_order_number": rng.integers(1, max(n // 2, 2),
                                        n).astype(np.int64),
        "ws_bill_customer_sk": _with_nulls(
            rng, rng.integers(1, counts["customer"] + 1, n).astype(np.int32),
            0.03),
        "ws_bill_addr_sk": _with_nulls(
            rng, rng.integers(1, counts["customer_address"] + 1,
                              n).astype(np.int32), 0.03),
        "ws_ship_addr_sk": _with_nulls(
            rng, rng.integers(1, counts["customer_address"] + 1,
                              n).astype(np.int32), 0.03),
        "ws_web_site_sk": _with_nulls(
            rng, rng.integers(1, counts["web_site"] + 1,
                              n).astype(np.int32), 0.02),
        "ws_web_page_sk": _with_nulls(
            rng, rng.integers(1, counts["web_page"] + 1,
                              n).astype(np.int32), 0.02),
        "ws_ship_mode_sk": _with_nulls(
            rng, rng.integers(1, counts["ship_mode"] + 1,
                              n).astype(np.int32), 0.02),
        "ws_promo_sk": _with_nulls(
            rng, rng.integers(1, counts["promotion"] + 1, n).astype(np.int32),
            0.02),
        "ws_warehouse_sk": _with_nulls(
            rng, rng.integers(1, counts["warehouse"] + 1,
                              n).astype(np.int32), 0.02),
        "ws_ship_customer_sk": _with_nulls(
            rng, rng.integers(1, counts["customer"] + 1, n).astype(np.int32),
            0.03),
        "ws_ship_hdemo_sk": _with_nulls(
            rng, rng.integers(1, counts["household_demographics"] + 1,
                              n).astype(np.int32), 0.03),
        "ws_quantity": qty,
        "ws_list_price": np.round(price * rng.uniform(1.0, 1.5, n), 2),
        "ws_sales_price": price,
        "ws_ext_sales_price": ext,
        "ws_ext_list_price": np.round(price * rng.uniform(1.0, 1.5, n)
                                      * qty, 2),
        "ws_ext_discount_amt": np.round(
            ext * rng.choice([0.0, 0.0, 0.05, 0.2], n), 2),
        "ws_ext_ship_cost": np.round(ext * rng.uniform(0.01, 0.1, n), 2),
        "ws_wholesale_cost": wholesale,
        "ws_ext_wholesale_cost": np.round(wholesale * qty, 2),
        "ws_net_paid": np.round(ext * rng.uniform(0.7, 1.0, n), 2),
        "ws_net_profit": np.round(ext - wholesale * qty, 2),
    }


def _pick(col, idx):
    """Sample parent column values at row indices ``idx`` (object arrays
    keep their Nones)."""
    return np.asarray(col)[idx]


def _ret_date_col(rng, ret_date: np.ndarray, null_frac: float):
    """returned_date_sk column: sentinel 0 (parent sold date was NULL)
    becomes None — dsdgen emits NULL there, and a non-null 0 would be
    unjoinable-but-countable in IS NULL / outer-join queries."""
    out = ret_date.astype(object)
    out[ret_date == 0] = None
    return _with_nulls(rng, out, null_frac)


def _returns_common(rng, parent: dict, n: int, item_col: str,
                    date_col: str, qty_col: str, price_col: str):
    """Sample n parent rows; returned date = sold date + U(1,90) days,
    return qty <= sold qty, amounts derived from the parent price."""
    pn = len(parent[item_col])
    idx = rng.choice(pn, size=min(n, pn), replace=False)
    idx.sort()
    sold = parent[date_col]
    sold_days = np.array([0 if v is None else int(v) for v in
                          np.asarray(sold, dtype=object)[idx]]
                         if np.asarray(sold).dtype == object
                         else np.asarray(sold)[idx], dtype=np.int64)
    ret_date = np.where(sold_days > 0,
                        sold_days + rng.integers(1, 91, len(idx)),
                        0).astype(np.int64)
    qty = np.asarray(parent[qty_col])[idx].astype(np.int64)
    rqty = rng.integers(1, np.maximum(qty, 1) + 1).astype(np.int32)
    price = np.asarray(parent[price_col])[idx].astype(np.float64)
    amt = np.round(price * rqty, 2)
    return idx, ret_date, rqty, amt


def _gen_store_returns(rng, counts, parent: dict) -> dict[str, np.ndarray]:
    n = counts["store_returns"]
    idx, ret_date, rqty, amt = _returns_common(
        rng, parent, n, "ss_item_sk",
        "ss_sold_date_sk", "ss_quantity", "ss_sales_price")
    return {
        "sr_returned_date_sk": _ret_date_col(rng, ret_date, 0.02),
        "sr_item_sk": _pick(parent["ss_item_sk"], idx).astype(np.int32),
        "sr_ticket_number": _pick(parent["ss_ticket_number"],
                                  idx).astype(np.int64),
        "sr_customer_sk": _pick(parent["ss_customer_sk"], idx),
        "sr_cdemo_sk": _pick(parent["ss_cdemo_sk"], idx),
        "sr_store_sk": _pick(parent["ss_store_sk"], idx),
        "sr_reason_sk": _with_nulls(
            rng, rng.integers(1, counts["reason"] + 1,
                              len(idx)).astype(np.int32), 0.02),
        "sr_return_quantity": _with_nulls(rng, rqty, 0.02),
        "sr_return_amt": amt,
        "sr_net_loss": np.round(amt * rng.uniform(0.3, 1.1, len(idx)), 2),
        "sr_fee": np.round(rng.uniform(0.5, 100.0, len(idx)), 2),
        "sr_refunded_cash": np.round(amt * rng.uniform(0.0, 1.0, len(idx)),
                                     2),
        "sr_return_amt_inc_tax": np.round(amt * 1.08, 2),
    }


def _gen_catalog_returns(rng, counts, parent: dict) -> dict[str, np.ndarray]:
    n = counts["catalog_returns"]
    idx, ret_date, rqty, amt = _returns_common(
        rng, parent, n, "cs_item_sk",
        "cs_sold_date_sk", "cs_quantity", "cs_sales_price")
    return {
        "cr_returned_date_sk": _ret_date_col(rng, ret_date, 0.02),
        "cr_item_sk": _pick(parent["cs_item_sk"], idx).astype(np.int32),
        "cr_order_number": _pick(parent["cs_order_number"],
                                 idx).astype(np.int64),
        "cr_returning_customer_sk": _pick(parent["cs_bill_customer_sk"],
                                          idx),
        "cr_refunded_customer_sk": _pick(parent["cs_bill_customer_sk"], idx),
        "cr_returning_addr_sk": _with_nulls(
            rng, rng.integers(1, counts["customer_address"] + 1,
                              len(idx)).astype(np.int32), 0.03),
        "cr_call_center_sk": _pick(parent["cs_call_center_sk"], idx),
        "cr_catalog_page_sk": _pick(parent["cs_catalog_page_sk"], idx),
        "cr_reason_sk": _with_nulls(
            rng, rng.integers(1, counts["reason"] + 1,
                              len(idx)).astype(np.int32), 0.02),
        "cr_return_quantity": _with_nulls(rng, rqty, 0.02),
        "cr_return_amount": amt,
        "cr_return_amt_inc_tax": np.round(amt * 1.08, 2),
        "cr_net_loss": np.round(amt * rng.uniform(0.3, 1.1, len(idx)), 2),
        "cr_refunded_cash": np.round(amt * rng.uniform(0.0, 0.6, len(idx)),
                                     2),
        "cr_reversed_charge": np.round(
            amt * rng.uniform(0.0, 0.3, len(idx)), 2),
        "cr_store_credit": np.round(amt * rng.uniform(0.0, 0.3, len(idx)),
                                    2),
    }


def _gen_web_returns(rng, counts, parent: dict) -> dict[str, np.ndarray]:
    n = counts["web_returns"]
    idx, ret_date, rqty, amt = _returns_common(
        rng, parent, n, "ws_item_sk",
        "ws_sold_date_sk", "ws_quantity", "ws_sales_price")
    return {
        "wr_returned_date_sk": _ret_date_col(rng, ret_date, 0.02),
        "wr_item_sk": _pick(parent["ws_item_sk"], idx).astype(np.int32),
        "wr_order_number": _pick(parent["ws_order_number"],
                                 idx).astype(np.int64),
        "wr_returning_customer_sk": _pick(parent["ws_bill_customer_sk"],
                                          idx),
        "wr_refunded_customer_sk": _pick(parent["ws_bill_customer_sk"], idx),
        "wr_returning_addr_sk": _with_nulls(
            rng, rng.integers(1, counts["customer_address"] + 1,
                              len(idx)).astype(np.int32), 0.03),
        "wr_refunded_addr_sk": _with_nulls(
            rng, rng.integers(1, counts["customer_address"] + 1,
                              len(idx)).astype(np.int32), 0.03),
        "wr_refunded_cdemo_sk": _with_nulls(
            rng, rng.integers(1, counts["customer_demographics"] + 1,
                              len(idx)).astype(np.int32), 0.03),
        "wr_returning_cdemo_sk": _with_nulls(
            rng, rng.integers(1, counts["customer_demographics"] + 1,
                              len(idx)).astype(np.int32), 0.03),
        "wr_web_page_sk": _pick(parent["ws_web_page_sk"], idx),
        "wr_reason_sk": _with_nulls(
            rng, rng.integers(1, counts["reason"] + 1,
                              len(idx)).astype(np.int32), 0.02),
        "wr_return_quantity": _with_nulls(rng, rqty, 0.02),
        "wr_return_amt": amt,
        "wr_fee": np.round(rng.uniform(0.5, 100.0, len(idx)), 2),
        "wr_refunded_cash": np.round(amt * rng.uniform(0.0, 1.0, len(idx)),
                                     2),
        "wr_net_loss": np.round(amt * rng.uniform(0.3, 1.1, len(idx)), 2),
    }


_GENERATORS = {
    "date_dim": lambda rng, counts: _gen_date_dim(counts),
    "time_dim": lambda rng, counts: _gen_time_dim(counts),
    "item": lambda rng, counts: _gen_item(rng, counts["item"]),
    "customer": lambda rng, counts: _gen_customer(
        rng, counts["customer"], counts["customer_address"],
        counts["customer_demographics"],
        counts["household_demographics"]),
    "customer_address": lambda rng, counts: _gen_customer_address(
        rng, counts["customer_address"]),
    "store": lambda rng, counts: _gen_store(rng, counts["store"]),
    "customer_demographics": lambda rng, counts: _gen_customer_demographics(
        rng, counts["customer_demographics"]),
    "household_demographics": lambda rng, counts:
        _gen_household_demographics(rng, counts["household_demographics"]),
    "promotion": lambda rng, counts: _gen_promotion(rng, counts["promotion"]),
    "warehouse": lambda rng, counts: _gen_warehouse(
        rng, counts["warehouse"]),
    "ship_mode": lambda rng, counts: _gen_ship_mode(
        rng, counts["ship_mode"]),
    "reason": lambda rng, counts: _gen_reason(rng, counts["reason"]),
    "income_band": lambda rng, counts: _gen_income_band(
        rng, counts["income_band"]),
    "call_center": lambda rng, counts: _gen_call_center(
        rng, counts["call_center"]),
    "web_site": lambda rng, counts: _gen_web_site(rng, counts["web_site"]),
    "web_page": lambda rng, counts: _gen_web_page(rng, counts["web_page"]),
    "catalog_page": lambda rng, counts: _gen_catalog_page(
        rng, counts["catalog_page"]),
    "inventory": lambda rng, counts: _gen_inventory(
        rng, counts["inventory"], counts),
    "store_sales": lambda rng, counts: _gen_store_sales(
        rng, counts["store_sales"], counts),
    "catalog_sales": lambda rng, counts: _gen_catalog_sales(
        rng, counts["catalog_sales"], counts),
    "web_sales": lambda rng, counts: _gen_web_sales(
        rng, counts["web_sales"], counts),
}

_RETURNS_GENERATORS = {
    "store_returns": _gen_store_returns,
    "catalog_returns": _gen_catalog_returns,
    "web_returns": _gen_web_returns,
}


def _write_parquet(path: str, data: dict, rows_per_file: int,
                   date_cols: Sequence[str] = ()) -> None:
    import pyarrow as pa
    import pyarrow.parquet as pq
    os.makedirs(path, exist_ok=True)
    n = len(next(iter(data.values())))
    cols = {}
    for name, arr in data.items():
        if name in date_cols:
            cols[name] = pa.array(np.asarray(arr, dtype=np.int32),
                                  type=pa.int32()).cast(pa.date32())
        elif isinstance(arr, np.ma.MaskedArray):
            # as the original wrote its None-holding columns: int32 or
            # float64 whatever the width drawn
            cols[name] = pa.array(
                arr.data, mask=np.ma.getmaskarray(arr)).cast(
                    pa.float64() if arr.dtype.kind == "f" else pa.int32())
        elif arr.dtype == object:
            base = next((x for x in arr if x is not None), 0)
            if isinstance(base, str):
                cols[name] = pa.array(list(arr), type=pa.string())
            elif isinstance(base, float):
                cols[name] = pa.array(
                    [None if x is None else float(x) for x in arr],
                    type=pa.float64())
            else:
                cols[name] = pa.array(
                    [None if x is None else int(x) for x in arr],
                    type=pa.int32())
        else:
            cols[name] = pa.array(arr)
    table = pa.table(cols)
    nfiles = max(1, -(-n // rows_per_file))

    def write(i: int) -> None:
        part = table.slice(i * rows_per_file,
                           min(rows_per_file, n - i * rows_per_file))
        pq.write_table(part, os.path.join(path, f"part-{i:05d}.parquet"))

    # encoding + compression release the GIL; the files are the
    # original's byte for byte whatever the order they are written in
    with ThreadPoolExecutor(max_workers=min(8, nfiles)) as pool:
        list(pool.map(write, range(nfiles)))


def generate_tpcds(data_dir: str, sf: float = 0.01, seed: int = 42,
                   tables: Sequence[str] = TABLES,
                   rows_per_file: int = 1 << 20) -> dict[str, int]:
    """Generate the pruned TPC-DS tables under ``data_dir/<table>/``.

    Returns {table: rows}.  Skips tables already generated at the current
    schema version (marker file); regenerates on version mismatch.
    """
    counts = table_row_counts(sf)
    # returns rows are sampled from their parent's rows, so the on-disk
    # parent must match THIS (sf, seed) — the marker encodes all three
    # (a schema-only marker let a different seed/sf regenerate returns
    # that join to nothing)
    stamp = f"_{_SCHEMA_VERSION}_sf{sf:g}_seed{seed}"
    written = {}

    def _needs_gen(t: str) -> bool:
        return not os.path.exists(os.path.join(data_dir, t, stamp))

    # parent sales data kept in memory only between a parent and its
    # returns table (the returns rows are sampled from the parent's)
    parents: dict[str, dict] = {}
    for t in tables:
        out = os.path.join(data_dir, t)
        written[t] = counts[t]
        if not _needs_gen(t):
            continue
        if os.path.isdir(out):
            import shutil
            shutil.rmtree(out)
        rng = np.random.default_rng(seed + zlib.crc32(t.encode()) % 1000)
        if t in _RETURNS_GENERATORS:
            pname = _RETURNS_PARENT[t]
            parent = parents.pop(pname, None)
            if parent is None:
                # parent already on disk from an earlier run at the SAME
                # (version, sf, seed): deterministic, so regenerate it in
                # memory for sampling
                prng = np.random.default_rng(
                    seed + zlib.crc32(pname.encode()) % 1000)
                parent = _GENERATORS[pname](prng, counts)
            data = _RETURNS_GENERATORS[t](rng, counts, _as_objects(parent))
            del parent
        else:
            data = _GENERATORS[t](rng, counts)
            retname = next((r for r, p in _RETURNS_PARENT.items()
                            if p == t), None)
            # hold the parent in memory only if its returns table is
            # about to be generated in this run (else multi-GB of object
            # arrays would sit resident for the rest of the loop)
            if retname in tables and _needs_gen(retname):
                parents[t] = data
        _write_parquet(out, data, rows_per_file,
                       date_cols=("d_date",) if t == "date_dim" else ())
        with open(os.path.join(out, stamp), "w") as f:
            f.write(stamp + "\n")
    return written


#: the harness's entry point, the same in every datagen file:
#: ``generate(data_dir, sf, seed, tables)``
generate = generate_tpcds
