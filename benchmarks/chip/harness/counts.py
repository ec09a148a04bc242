"""Work the answer itself needs, fixed by the problem and not by how
the program computes it."""
from __future__ import annotations


def rescore_bytes(cfg: dict, data: dict) -> int:
    """Bytes one bulk rescore has to move at the least: every table's
    key and feature columns read once, four bytes each as the schema
    holds them (int32 keys, float32 features), and two float32 (Σŷ and
    the count) written per grouping-table row."""
    total = 0
    for t in cfg["tables"]:
        n = len(next(iter(data[t["name"]].values())))
        total += 4 * n * (len(t["keys"]) + len(t["features"]))
    n_group = len(next(iter(data[cfg["group_by"]].values())))
    return total + 2 * 4 * n_group
