"""TPC-H tables from a seed, with the spec's column distributions.

Keys, numeric and date columns, and every small-domain categorical
column as an integer code, are made as clause 4.2.3 says:

- ORDERS: sparse keys (the first 8 of every 32), custkey uniform over
  the customers whose key is not a multiple of 3, orderdate uniform on
  [STARTDATE, ENDDATE - 151 days], orderpriority uniform over 5, clerk
  uniform on [1, SF * 1,000], orderstatus F, O or P from its lines'
  linestatus, and totalprice = sum of extendedprice * (1 + tax) *
  (1 - discount) of its lines.
- LINEITEM: 1..7 lines per order, partkey uniform, suppkey from the
  spec's formula over partkey, quantity 1..50, discount 0.00..0.10,
  tax 0.00..0.08, shipdate = orderdate + 1..121, commitdate =
  orderdate + 30..90, receiptdate = shipdate + 1..30, returnflag R or
  A at random when received by CURRENTDATE and N after it, linestatus
  O when shipped after CURRENTDATE and F before, shipinstruct uniform
  over 4 and shipmode over 7.
- PART: retailprice from the spec's formula over partkey, size 1..50,
  mfgr M uniform on 1..5, brand 10 * M + N with N uniform on 1..5,
  type uniform over the 150 syllable triples, container over the 40
  syllable pairs.
- SUPPLIER, CUSTOMER: acctbal uniform on [-999.99, 9999.99], nationkey
  uniform over 25; CUSTOMER mktsegment uniform over 5.
- NATION, REGION: the spec's 25 nations and their regions; n_name and
  r_name are each name's index in the spec's lists.

A code is the value's index in the spec's list (flags and statuses in
alphabetical order); dates are day numbers from 1992-01-01.  Free-text
columns (names, addresses, phones, comments) and the constant
o_shippriority are not made.  Row counts come from the configuration
file, and SF from the ORDERS rows (1,500,000 per unit).  Lines per
order are moved by one line at random orders until they sum to the
LINEITEM row count, so every seed gives the same shapes.  The label is
revenue, quantity * retailprice * (1 - discount).
"""
from __future__ import annotations

import numpy as np

DAYS = 2557                       # 1992-01-01 .. 1998-12-31
CURRENTDATE = 1263                # 1995-06-17
NATION_REGION = np.array([0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0,
                          1, 2, 3, 4, 2, 3, 3, 1], np.int32)


def order_keys(index: np.ndarray) -> np.ndarray:
    """Sparse ORDERS keys: the first 8 of every 32 (clause 4.2.3)."""
    index = np.asarray(index, np.int64)
    return ((index // 8) * 32 + index % 8 + 1).astype(np.int32)


def retail_price(partkey: np.ndarray) -> np.ndarray:
    pk = np.asarray(partkey, np.int64)
    return ((90000 + (pk // 10) % 20001 + 100 * (pk % 1000)) / 100.0)


def supp_key(partkey: np.ndarray, i: np.ndarray, n_supp: int) -> np.ndarray:
    pk = np.asarray(partkey, np.int64)
    s = n_supp
    return ((pk + i * (s // 4 + (pk - 1) // s)) % s + 1).astype(np.int32)


def _money(rng, n, lo, hi):
    return rng.integers(round(lo * 100), round(hi * 100) + 1, n) / 100.0


def lines_per_order(rng, n_orders: int, n_lines: int) -> np.ndarray:
    """Uniform 1..7 per order, moved by one line at a time at random
    orders until the total is ``n_lines`` (needs 1 <= mean <= 7)."""
    if not n_orders <= n_lines <= 7 * n_orders:
        raise ValueError(f"{n_lines} lines cannot spread over {n_orders} orders")
    cnt = rng.integers(1, 8, n_orders)
    while (diff := n_lines - int(cnt.sum())) != 0:
        room = np.flatnonzero(cnt < 7) if diff > 0 else np.flatnonzero(cnt > 1)
        pick = rng.choice(room, size=min(abs(diff), len(room)), replace=False)
        cnt[pick] += 1 if diff > 0 else -1
    return cnt


def _lines(rng, okeys, odates, cnt, n_part, n_supp):
    """LINEITEM columns for orders (keys, dates) with ``cnt`` lines each."""
    n = int(cnt.sum())
    starts = np.repeat(np.cumsum(cnt) - cnt, cnt)
    linenumber = np.arange(n) - starts + 1
    partkey = rng.integers(1, n_part + 1, n).astype(np.int32)
    quantity = rng.integers(1, 51, n).astype(np.float64)
    discount = rng.integers(0, 11, n) / 100.0
    tax = rng.integers(0, 9, n) / 100.0
    odate = np.repeat(odates, cnt)
    ship = odate + rng.integers(1, 122, n)
    commit = odate + rng.integers(30, 91, n)
    receipt = ship + rng.integers(1, 31, n)
    ext = quantity * retail_price(partkey)
    returned = rng.integers(0, 2, n) * 2              # A = 0, R = 2
    linestatus = (ship > CURRENTDATE).astype(np.int64)   # F = 0, O = 1
    cols = {
        "orderkey": np.repeat(okeys, cnt).astype(np.int32),
        "partkey": partkey,
        "suppkey": supp_key(partkey, rng.integers(0, 4, n), n_supp),
        "l_quantity": quantity, "l_discount": discount, "l_tax": tax,
        "l_linenumber": linenumber.astype(np.float64),
        "l_shipdate": ship.astype(np.float64),
        "l_commitdate": commit.astype(np.float64),
        "l_receiptdate": receipt.astype(np.float64),
        "l_returnflag": np.where(receipt <= CURRENTDATE, returned, 1).astype(np.float64),
        "l_linestatus": linestatus.astype(np.float64),
        "l_shipinstruct": rng.integers(0, 4, n).astype(np.float64),
        "l_shipmode": rng.integers(0, 7, n).astype(np.float64),
        "revenue": ext * (1.0 - discount),
    }
    price = ext * (1.0 + tax) * (1.0 - discount)
    order_of_line = np.repeat(np.arange(len(cnt)), cnt)
    totalprice = np.bincount(order_of_line, weights=price, minlength=len(cnt))
    open_lines = np.bincount(order_of_line, weights=linestatus, minlength=len(cnt))
    # F = 0 when every line is F, O = 1 when every line is O, else P = 2
    status = np.where(open_lines == 0, 0, np.where(open_lines == cnt, 1, 2))
    return cols, totalprice, status


def _orders(rng, n, n_cust):
    custs = np.arange(1, n_cust + 1)
    custs = custs[custs % 3 != 0]
    n_clerk = max(1, n * 1000 // 1_500_000)           # SF * 1,000
    return {
        "orderkey": order_keys(np.arange(n)),
        "custkey": rng.choice(custs, n).astype(np.int32),
        "o_orderdate": rng.integers(0, DAYS - 151, n).astype(np.float64),
        "o_orderpriority": rng.integers(0, 5, n).astype(np.float64),
        "o_clerk": rng.integers(1, n_clerk + 1, n).astype(np.float64),
    }


def generate(cfg: dict, seed: int, rows: dict) -> dict:
    """{table: {column: array}} for the tables the configuration names,
    at the row counts ``rows``.  Keys are int32, the rest float32."""
    rng = np.random.default_rng(seed)
    names = [t["name"] for t in cfg["tables"]]
    n_part = rows.get("part", 1)
    n_supp = rows.get("supplier", max(1, round(rows["part"] / 20)) if "part" in rows else 1)
    n_cust = rows.get("customer", max(3, rows["orders"] // 10))
    out = {}
    orders = _orders(rng, rows["orders"], n_cust)
    cnt = lines_per_order(rng, rows["orders"], rows["lineitem"])
    lines, totalprice, status = _lines(rng, orders["orderkey"], orders["o_orderdate"],
                                       cnt, n_part, n_supp)
    orders["o_totalprice"] = totalprice
    orders["o_orderstatus"] = status.astype(np.float64)
    out["lineitem"], out["orders"] = lines, orders
    if "part" in names:
        pk = np.arange(1, n_part + 1)
        mfgr = rng.integers(1, 6, n_part)
        out["part"] = {"partkey": pk.astype(np.int32),
                       "p_size": rng.integers(1, 51, n_part).astype(np.float64),
                       "p_retailprice": retail_price(pk),
                       "p_mfgr": mfgr.astype(np.float64),
                       "p_brand": (10 * mfgr + rng.integers(1, 6, n_part)).astype(np.float64),
                       "p_type": rng.integers(0, 150, n_part).astype(np.float64),
                       "p_container": rng.integers(0, 40, n_part).astype(np.float64)}
    if "supplier" in names:
        out["supplier"] = {"suppkey": np.arange(1, n_supp + 1, dtype=np.int32),
                           "s_acctbal": _money(rng, n_supp, -999.99, 9999.99),
                           "s_nationkey": rng.integers(0, 25, n_supp).astype(np.float64)}
    if "customer" in names:
        out["customer"] = {
            "custkey": np.arange(1, n_cust + 1, dtype=np.int32),
            "nationkey": rng.integers(0, 25, n_cust).astype(np.int32),
            "c_acctbal": _money(rng, n_cust, -999.99, 9999.99),
            "c_mktsegment": rng.integers(0, 5, n_cust).astype(np.float64)}
    if "nation" in names:
        out["nation"] = {"nationkey": np.arange(25, dtype=np.int32),
                         "regionkey": NATION_REGION.copy(),
                         "n_name": np.arange(25, dtype=np.float64)}
    if "region" in names:
        out["region"] = {"regionkey": np.arange(5, dtype=np.int32),
                         "r_name": np.arange(5, dtype=np.float64)}
    for t in cfg["tables"]:
        want = t["keys"] + t["features"] + (
            [cfg["label"][1]] if cfg["label"][0] == t["name"] else [])
        out[t["name"]] = {c: (out[t["name"]][c] if c in t["keys"]
                              else out[t["name"]][c].astype(np.float32))
                          for c in want}
    return {n: out[n] for n in names}

