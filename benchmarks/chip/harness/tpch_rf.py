"""TPC-H refresh functions RF1 and RF2 (clause 2.5) as a stream of
micro-batches over tables made by ``tpch.generate``.

- RF1 inserts new orders, each with 1..7 lines drawn as the initial
  population's are (``tpch._lines``).  Their orderkeys fill, in order,
  the key ranges the initial population leaves empty (it holds the
  first 8 of every 32), so every one is new; custkeys are uniform over
  the customers whose key is not a multiple of 3.
- RF2 deletes orders of the initial population, drawn without
  replacement, with all their lines.

A refresh pair is SF * 1,500 orders each way (SF from the ORDERS
rows: 1,500,000 per unit) and is split into ``per_pair`` micro-batches
of at least one order.  A deleted order is named twice: by its
orderkey (what the plain reference replays) and by its row, and its
lines' rows, in the initial tables (what the program is handed: a
row of the initial population keeps its slot until it is deleted).
Everything comes from ``seed``: the same seed gives the same stream,
and a longer stream begins with a shorter one's micro-batches.
"""
from __future__ import annotations

import numpy as np

from . import tpch
from .models import seed_rng


def gap_keys(index: np.ndarray) -> np.ndarray:
    """The orderkeys the initial population leaves empty: positions
    9..32 of every 32, in order."""
    index = np.asarray(index, np.int64)
    return ((index // 24) * 32 + 8 + index % 24 + 1).astype(np.int32)


def _columns(cfg: dict, name: str, cols: dict) -> dict:
    """The table's columns as ``tpch.generate`` keeps them: keys int32,
    the rest float32, the label on its table."""
    t = next(t for t in cfg["tables"] if t["name"] == name)
    want = t["keys"] + t["features"] + (
        [cfg["label"][1]] if cfg["label"][0] == name else [])
    return {c: (np.asarray(cols[c], np.int32) if c in t["keys"]
                else np.asarray(cols[c], np.float32)) for c in want}


def _offsets(counts) -> np.ndarray:
    return np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)


def stream(cfg: dict, data: dict, seed: int, n_batches: int, per_pair: int = 10,
           orders_per_pair: int | None = None) -> dict:
    """``n_batches`` micro-batches of RF1 inserts and RF2 deletes.

    Returns flat arrays with offsets: micro-batch ``j`` inserts
    ``orders[c][orders_at[j]:orders_at[j+1]]`` and
    ``lineitem[c][lineitem_at[j]:lineitem_at[j+1]]``, and deletes the
    initial orders at rows ``del_orders[del_orders_at[j]:...]``
    (orderkeys ``del_keys``, same offsets) with their lines at rows
    ``del_lines[del_lines_at[j]:...]``."""
    orders, lines = data["orders"], data["lineitem"]
    n_orders = len(orders["orderkey"])
    if orders_per_pair is None:
        orders_per_pair = round(n_orders * 1500 / 1_500_000)
    split = [len(a) for a in np.array_split(
        np.arange(max(per_pair, orders_per_pair)), per_pair)]
    n_pairs = -(-n_batches // per_pair)
    per_batch = np.tile(split, n_pairs)[:n_batches]
    total = int(per_batch.sum())
    if total > n_orders:
        raise ValueError(f"{n_batches} micro-batches delete {total} orders; "
                         f"the initial population has {n_orders}")

    # RF1: new orders and their lines, each pair from its own generator,
    # so a longer stream begins with the same micro-batches
    n_cust = len(data["customer"]["custkey"]) if "customer" in data else max(3, n_orders // 10)
    custs = np.arange(1, n_cust + 1)
    custs = custs[custs % 3 != 0]
    n_clerk = max(1, n_orders * 1000 // 1_500_000)          # SF * 1,000
    n_part = len(data["part"]["partkey"])
    n_supp = len(data["supplier"]["suppkey"])
    pairs = []
    for p in range(n_pairs):
        rng = seed_rng(seed, 15, p)
        k = sum(split)
        o = {"orderkey": gap_keys(np.arange(p * k, (p + 1) * k)),
             "custkey": rng.choice(custs, k).astype(np.int32),
             "o_orderdate": rng.integers(0, tpch.DAYS - 151, k).astype(np.float64),
             "o_orderpriority": rng.integers(0, 5, k).astype(np.float64),
             "o_clerk": rng.integers(1, n_clerk + 1, k).astype(np.float64)}
        cnt = rng.integers(1, 8, k)
        ln, o["o_totalprice"], status = tpch._lines(
            rng, o["orderkey"], o["o_orderdate"], cnt, n_part, n_supp)
        o["o_orderstatus"] = status.astype(np.float64)
        pairs.append((o, ln, cnt))
    cnt = np.concatenate([c for _, _, c in pairs])[:total]
    lines_at = _offsets(cnt)[_offsets(per_batch)]
    new = {c: np.concatenate([o[c] for o, _, _ in pairs])[:total] for c in pairs[0][0]}
    new_lines = {c: np.concatenate([ln[c] for _, ln, _ in pairs])[:lines_at[-1]]
                 for c in pairs[0][1]}

    # RF2: initial orders and the rows of their lines
    gone = seed_rng(seed, 17).permutation(n_orders)[:total]
    okey = np.asarray(orders["orderkey"])
    by_key = np.argsort(okey, kind="stable")
    order_of_line = by_key[np.searchsorted(okey, np.asarray(lines["orderkey"]), sorter=by_key)]
    batch_of_order = np.full(n_orders, -1, np.int64)
    batch_of_order[gone] = np.repeat(np.arange(n_batches), per_batch)
    batch_of_line = batch_of_order[order_of_line]
    del_lines = np.flatnonzero(batch_of_line >= 0)
    del_lines = del_lines[np.argsort(batch_of_line[del_lines], kind="stable")]
    return {
        "orders": _columns(cfg, "orders", new),
        "orders_at": _offsets(per_batch),
        "lineitem": _columns(cfg, "lineitem", new_lines),
        "lineitem_at": lines_at,
        "del_orders": gone.astype(np.int64),
        "del_keys": okey[gone].astype(np.int32),
        "del_orders_at": _offsets(per_batch),
        "del_lines": del_lines.astype(np.int64),
        "del_lines_at": np.searchsorted(batch_of_line[del_lines],
                                        np.arange(n_batches + 1)).astype(np.int64),
    }


def batch(s: dict, j: int, name: str, kind: str):
    """Micro-batch ``j``'s inserted rows of table ``name`` (``kind``
    "insert": {column: array}) or deleted initial rows ("delete")."""
    if kind == "insert":
        a, b = s[f"{name}_at"][j], s[f"{name}_at"][j + 1]
        return {c: v[a:b] for c, v in s[name].items()}
    rows = "del_orders" if name == "orders" else "del_lines"
    a, b = s[f"{rows}_at"][j], s[f"{rows}_at"][j + 1]
    return s[rows][a:b]
