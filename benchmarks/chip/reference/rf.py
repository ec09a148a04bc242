"""Plain replay of TPC-H refresh micro-batches onto dicts of arrays.

numpy only.  A deleted order goes by its orderkey: its ORDERS row and
every LINEITEM row with that key leave; inserted rows are appended.
RF2 deletes orders of the initial population only, so the tables after
the first ``n`` micro-batches are the initial rows whose orderkey no
micro-batch up to ``n`` deleted, then every row those micro-batches
inserted, in order.
"""
from __future__ import annotations

import numpy as np


def replay(tables: dict, s: dict, n: int) -> dict:
    """``tables`` (the initial population) after micro-batches
    ``0 .. n-1`` of the stream ``s`` (``harness.tpch_rf.stream``)."""
    gone = s["del_keys"][:s["del_orders_at"][n]]
    out = dict(tables)
    for name in ("orders", "lineitem"):
        keep = ~np.isin(np.asarray(tables[name]["orderkey"]), gone)
        end = s[f"{name}_at"][n]
        out[name] = {c: np.concatenate([np.asarray(v)[keep], s[name][c][:end]])
                     for c, v in tables[name].items()}
    return out


def row_keys(orderkey, linenumber) -> np.ndarray:
    """One int64 per LINEITEM row: (l_orderkey, l_linenumber), which
    the spec makes the table's primary key."""
    return np.asarray(orderkey, np.int64) * 8 + np.asarray(linenumber, np.int64)
