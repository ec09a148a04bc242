"""Data and models made from a seed: the inputs every driver hands the
program and the reference alike."""
from __future__ import annotations

import numpy as np

from . import tpch


def seed_rng(seed: int, *salt: int) -> np.random.Generator:
    """A numpy generator for ``seed`` (any non-negative integer, also
    past 32 bits) and a salt naming what it makes."""
    return np.random.default_rng([int(seed) % (1 << 63), *salt])


def tables(cfg: dict, seed: int, rows: dict, shrink: int = 1) -> dict:
    """The configuration's tables at ``rows`` (each cut by ``shrink``
    for a rehearsal on the CPU)."""
    if shrink > 1:
        rows = {t: max(5, n // shrink) for t, n in rows.items()}
        rows["lineitem"] = max(rows["lineitem"], rows["orders"])
    return tpch.generate(cfg, int(seed) % (1 << 63), rows)


def random_trees(cfg: dict, data: dict, seed: int, model: int,
                 n_trees: int = 8, depth: int = 4) -> list[dict]:
    """An ensemble of ``n_trees`` complete trees: at each internal node a
    feature drawn uniformly and, as its threshold, that feature's value
    at a random row of its table; leaf values N(0, 1000^2), float32."""
    rng = seed_rng(seed, 7, model)
    feats = [(t["name"], c) for t in cfg["tables"] for c in t["features"]]
    out = []
    for _ in range(n_trees):
        f = rng.integers(0, len(feats), 2 ** depth - 1).astype(np.int32)
        thr = np.empty(len(f), np.float32)
        for i, g in enumerate(f):
            col = data[feats[g][0]][feats[g][1]]
            thr[i] = col[rng.integers(0, len(col))]
        leaf = (1000.0 * rng.standard_normal(2 ** depth)).astype(np.float32)
        out.append({"feat": f, "thr": thr, "leaf": leaf})
    return out
