"""The benchmark's TPC-DS tables, drawn from the seed on the host.

Three tables of the TPC-DS v3.2.0 schema, at the row counts the
configuration gives and with every column the specification defines:
``store_sales`` (23 columns), ``item`` (22) and ``date_dim`` (28). Keys are
the specification's surrogate keys: ``d_date_sk`` is the Julian day number
(2415022 is 1900-01-02), ``i_item_sk`` counts from 1. Decimal columns are
float32 rounded to cents, character columns int32 codes, dates int32 Julian
day numbers. The value rules follow dsdgen where the queries can see them:

- ``date_dim`` is the Gregorian calendar from 1900-01-02, no draw at all;
- an item draws its category (1-10), class (1-16), brand (1-10 within the
  class, ``i_brand_id = category * 1e6 + class * 1e3 + brand``),
  manufacturer (1-1000) and manager (1-100) uniformly;
- ``store_sales`` comes in tickets of 8 to 16 lines that share the date,
  time, customer, demographics, address and store; every line draws its
  item and quantity (1-100), and prices follow dsdgen's pricing:
  wholesale cost 1-100, list price a markup of 0-200% on it, sales price a
  discount of 0-100% off it, the ``ext_`` amounts times the quantity, tax
  0-9%, a coupon on a fifth of the lines, and net paid and net profit from
  those. Sales dates are uniform over 1998-01-02 to 2003-01-02.

The same host arrays go to the engine's catalog and to the reference, so
the yardstick reads what the engine was given, never what it made.
"""

from __future__ import annotations

from typing import Dict, Mapping

import numpy as np

Columns = Dict[str, np.ndarray]

#: First ``d_date_sk``: the Julian day number of 1900-01-02.
JULIAN_1900_01_02 = 2415022
#: ``store_sales`` dates span 1998-01-02 to 2003-01-02, as dsdgen's.
SALES_FIRST = np.datetime64("1998-01-02")
SALES_LAST = np.datetime64("2003-01-02")
_DAY0 = np.datetime64("1900-01-02")

#: fact key column -> dimension whose primary key it draws from.
FK_DIMENSIONS = {"ss_sold_date_sk": "date_dim", "ss_item_sk": "item"}
PRIMARY_KEYS = {"date_dim": "d_date_sk", "item": "i_item_sk"}


def julian(day: np.datetime64) -> int:
    return JULIAN_1900_01_02 + int((day - _DAY0).astype(int))


def _cents(x: np.ndarray) -> np.ndarray:
    return (np.round(x * 100.0) / 100.0).astype(np.float32)


def date_dim(n: int) -> Columns:
    """The calendar: ``n`` days from 1900-01-02."""
    i = np.arange(n, dtype=np.int64)
    days = _DAY0 + i
    sk = (JULIAN_1900_01_02 + i).astype(np.int32)
    year = days.astype("M8[Y]").astype(np.int64) + 1970
    month0 = days.astype("M8[M]")
    moy = month0.astype(np.int64) % 12 + 1
    dom = (days - month0.astype("M8[D]")).astype(np.int64) + 1
    dow = (days.astype(np.int64) + 4) % 7           # 1970-01-01: Thursday
    qoy = (moy - 1) // 3 + 1
    month_seq = (year - 1900) * 12 + moy - 1
    quarter_seq = (year - 1900) * 4 + qoy
    week_seq = (i + 1) // 7 + 1                    # 1900-01-01: Monday
    first = month0.astype("M8[D]")
    last = (month0 + 1).astype("M8[D]") - 1
    holiday = ((moy == 1) & (dom == 1)) | ((moy == 7) & (dom == 4)) | \
        ((moy == 12) & (dom == 25))
    zero = np.zeros(n, np.int32)

    def jd(d):
        return (JULIAN_1900_01_02 + (d - _DAY0).astype(np.int64)
                ).astype(np.int32)

    cols = {
        "d_date_sk": sk, "d_date_id": (i + 1).astype(np.int32),
        "d_date": sk.copy(), "d_month_seq": month_seq,
        "d_week_seq": week_seq, "d_quarter_seq": quarter_seq,
        "d_year": year, "d_dow": dow, "d_moy": moy, "d_dom": dom,
        "d_qoy": qoy, "d_fy_year": year, "d_fy_quarter_seq": quarter_seq,
        "d_fy_week_seq": week_seq, "d_day_name": dow,
        "d_quarter_name": year * 10 + qoy, "d_holiday": holiday,
        "d_weekend": (dow == 0) | (dow == 6),
        "d_following_holiday": np.roll(holiday, 1),
        "d_first_dom": jd(first), "d_last_dom": jd(last),
        "d_same_day_ly": sk - 365, "d_same_day_lq": sk - 91,
        "d_current_day": zero, "d_current_week": zero,
        "d_current_month": zero, "d_current_quarter": zero,
        "d_current_year": zero}
    return {k: np.asarray(v).astype(np.int32) for k, v in cols.items()}


def item(n: int, rng: np.random.Generator) -> Columns:
    sk = np.arange(1, n + 1, dtype=np.int32)

    def ints(lo, hi):
        return rng.integers(lo, hi + 1, n).astype(np.int32)

    category = ints(1, 10)
    klass = ints(1, 16)
    brand = category * 1_000_000 + klass * 1_000 + ints(1, 10)
    manufact = ints(1, 1000)
    starts = np.array([julian(np.datetime64(d)) for d in
                       ("1997-10-27", "1999-10-28", "2000-10-27",
                        "2001-10-27")], np.int32)
    start = starts[rng.integers(0, 4, n)]
    price = _cents(rng.uniform(0.09, 99.99, n))
    return {
        "i_item_sk": sk, "i_item_id": (sk + 1) // 2,
        "i_rec_start_date": start,
        "i_rec_end_date": (start + 730).astype(np.int32),
        "i_item_desc": ints(1, 200_000),
        "i_current_price": price,
        "i_wholesale_cost": _cents(price * rng.uniform(0.2, 0.9, n)),
        "i_brand_id": brand, "i_brand": brand.copy(),
        "i_class_id": klass, "i_class": category * 100 + klass,
        "i_category_id": category, "i_category": category.copy(),
        "i_manufact_id": manufact, "i_manufact": manufact.copy(),
        "i_size": ints(1, 7), "i_formulation": ints(1, 50_000),
        "i_color": ints(1, 92), "i_units": ints(1, 21),
        "i_container": ints(1, 2), "i_manager_id": ints(1, 100),
        "i_product_name": ints(1, 50_000)}


def store_sales(n: int, n_item: int, rng: np.random.Generator) -> Columns:
    lines = rng.integers(8, 17, n // 8 + 1)
    tickets = int(np.searchsorted(np.cumsum(lines), n)) + 1
    ticket = np.repeat(np.arange(tickets), lines[:tickets])[:n]

    def per_ticket(lo, hi):
        return rng.integers(lo, hi + 1, tickets).astype(np.int32)[ticket]

    def ints(lo, hi):
        return rng.integers(lo, hi + 1, n).astype(np.int32)

    def uniform(lo, hi):
        return rng.uniform(lo, hi, n)

    date = per_ticket(julian(SALES_FIRST), julian(SALES_LAST))
    time_sk = per_ticket(28_800, 75_599)
    customer = per_ticket(1, 100_000)
    cdemo = per_ticket(1, 1_920_800)
    hdemo = per_ticket(1, 7_200)
    addr = per_ticket(1, 50_000)
    store = per_ticket(1, 12)
    qty = ints(1, 100)
    wholesale = _cents(uniform(1.0, 100.0)).astype(np.float64)
    list_price = np.round(wholesale * (1.0 + uniform(0.0, 2.0)), 2)
    sales_price = np.round(list_price * (1.0 - uniform(0.0, 1.0)), 2)
    ext_sales = qty * sales_price
    ext_wholesale = qty * wholesale
    coupon = np.where(uniform(0.0, 1.0) < 0.2,
                      np.round(ext_sales * uniform(0.0, 1.0), 2), 0.0)
    tax = np.round(ext_sales * uniform(0.0, 0.09), 2)
    net_paid = ext_sales - coupon
    return {
        "ss_sold_date_sk": date, "ss_sold_time_sk": time_sk,
        "ss_item_sk": ints(1, n_item), "ss_customer_sk": customer,
        "ss_cdemo_sk": cdemo, "ss_hdemo_sk": hdemo, "ss_addr_sk": addr,
        "ss_store_sk": store, "ss_promo_sk": ints(1, 300),
        "ss_ticket_number": (ticket + 1).astype(np.int32),
        "ss_quantity": qty,
        "ss_wholesale_cost": _cents(wholesale),
        "ss_list_price": _cents(list_price),
        "ss_sales_price": _cents(sales_price),
        "ss_ext_discount_amt": _cents(qty * (list_price - sales_price)),
        "ss_ext_sales_price": _cents(ext_sales),
        "ss_ext_wholesale_cost": _cents(ext_wholesale),
        "ss_ext_list_price": _cents(qty * list_price),
        "ss_ext_tax": _cents(tax),
        "ss_coupon_amt": _cents(coupon),
        "ss_net_paid": _cents(net_paid),
        "ss_net_paid_inc_tax": _cents(net_paid + tax),
        "ss_net_profit": _cents(net_paid - ext_wholesale)}


def make_tables(config: Mapping, seed: int, facts: bool = True
                ) -> Dict[str, Columns]:
    """Host columns of every table of the configuration; with
    ``facts=False`` the dimensions alone (drawn first, so the same)."""
    rows = config["rows"]
    rng = np.random.default_rng(seed)
    tables = {"date_dim": date_dim(int(rows["date_dim"])),
              "item": item(int(rows["item"]), rng)}
    if facts:
        tables["store_sales"] = store_sales(int(rows["store_sales"]),
                                            int(rows["item"]), rng)
    return tables


def key_domains(config: Mapping) -> Dict[str, float]:
    """Each key column's domain size: the rows of the dimension it names."""
    rows = config["rows"]
    out = {fk: float(rows[dim]) for fk, dim in FK_DIMENSIONS.items()}
    out.update({pk: float(rows[t]) for t, pk in PRIMARY_KEYS.items()})
    return out
