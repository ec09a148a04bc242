"""Bytes a fresh-scored micro-batch has to move, fixed by the problem
and not by how the program computes it."""
from __future__ import annotations


def micro_batch_bytes(cfg: dict, inserted: dict, deleted: dict, live_rows):
    """The inserted rows' key and feature columns read once (four bytes
    each, as the schema holds them: int32 keys, float32 features), four
    bytes per deleted row's key, and two float32 (Σŷ and the count)
    written for every live row of the grouping table.  ``inserted`` and
    ``deleted`` give rows per table; numbers or arrays of them, one per
    micro-batch."""
    width = {t["name"]: len(t["keys"]) + len(t["features"]) for t in cfg["tables"]}
    return (sum(4 * n * width[t] for t, n in inserted.items())
            + sum(4 * n for n in deleted.values()) + 2 * 4 * live_rows)
