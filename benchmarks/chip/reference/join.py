"""The plain reference's join: numpy only, nothing of the program.

Every TPC-H join the configurations use is a foreign-key lookup from
the grouping table outwards (LINEITEM to ORDERS, ORDERS to CUSTOMER,
...), so the materialized join has one row per grouping-table row
whose keys all resolve.  Features come out in the configuration's
order (tables in order, each table's ``features`` in order), which is
the global feature order of a schema built from the same file.
"""
from __future__ import annotations

import numpy as np


def feature_order(cfg: dict) -> list[tuple[str, str]]:
    return [(t["name"], c) for t in cfg["tables"] for c in t["features"]]


def materialize(cfg: dict, tables: dict) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(X, y, root_rows): the join's features (n, d) as stored (float32),
    its label (float64) and, per join row, its row of the grouping
    table."""
    root = cfg["group_by"]
    n = len(next(iter(tables[root].values())))
    rows = {root: np.arange(n)}             # per joined table: row per join row
    keep = np.ones(n, bool)
    joined = [root]
    todo = [t["name"] for t in cfg["tables"] if t["name"] != root]
    keys = {t["name"]: set(t["keys"]) for t in cfg["tables"]}
    while todo:
        for name in todo:
            link = next(((j, k) for j in joined for k in sorted(keys[j] & keys[name])), None)
            if link is not None:
                break
        else:
            raise ValueError(f"tables {todo} do not join {joined}")
        src, key = link
        want = np.asarray(tables[src][key])[rows[src]]
        have = np.asarray(tables[name][key])
        order = np.argsort(have, kind="stable")
        if np.any(have[order][1:] == have[order][:-1]):
            raise ValueError(f"{name}.{key} is not unique")
        pos = np.clip(np.searchsorted(have, want, sorter=order), 0, len(have) - 1)
        hit = have[order][pos] == want
        keep &= hit
        rows[name] = order[pos]
        joined.append(name)
        todo.remove(name)
    X = np.stack([np.asarray(tables[t][c], np.float32)[rows[t][keep]]
                  for t, c in feature_order(cfg)], axis=1)
    lt, lc = cfg["label"]
    y = np.asarray(tables[lt][lc], np.float64)[rows[lt][keep]]
    return X, y, rows[root][keep]
