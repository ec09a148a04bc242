"""Bulk rescoring of the grouping table after each model publish.

Set-up makes the tables at ``rows`` and ``models`` ensembles from the
seed (``harness.models.random_trees``: all of one shape), and rescores
with ``warmup`` of them.  The window loops over the ensembles in turn:
``compile_ensemble`` (the stacked leaf-mask factors) then
``score_grouped`` over the grouping table, ended by
``block_until_ready``, until ``--seconds`` have passed.
``rescore_rows_per_s`` is the grouping table's rows times the rescores
completed, over the window's seconds.  In a traced run the factors are
also waited for on their own, so ``bench.factor`` and ``bench.score``
time the two layers apart.

Judged on the host once the window has closed: each ensemble's last
rescore, every row, against the materialized-join reference (Σŷ and
counts per grouping row, ``reference/trees.py`` and ``join.py``).
"""
from __future__ import annotations

import time

import numpy as np

from harness import counts, models, program


def setup(ctx):
    import jax
    from repro.serving import compile_ensemble, score_grouped

    cfg, tr = ctx.config, ctx.traffic
    data = models.tables(cfg, ctx.seed, cfg["rows"], ctx.shrink)
    schema = program.schema_of(cfg, data)
    ens_in = [models.random_trees(cfg, data, ctx.seed, m, tr["n_trees"], tr["depth"])
              for m in range(tr["models"])]
    trees = [[program.tree_arrays(t) for t in e] for e in ens_in]
    g = cfg["group_by"]
    for m in range(tr["warmup"]):
        with ctx.spans("bench.warmup"):
            ens = compile_ensemble(schema, trees[m % len(trees)])
            jax.block_until_ready(ens.factors)
            jax.block_until_ready(score_grouped(ens, g))
    ctx.extra["bytes"] = counts.rescore_bytes(cfg, data)
    return {"data": data, "schema": schema, "models": ens_in, "trees": trees,
            "group": g}


def window(ctx, st):
    import jax
    from repro.serving import compile_ensemble, score_grouped

    schema, trees, g = st["schema"], st["trees"], st["group"]
    kept, done, t0 = {}, 0, time.perf_counter()
    while True:
        m = done % len(trees)
        with ctx.spans("bench.rescore"):
            if ctx.traced:
                with ctx.spans("bench.factor"):
                    ens = compile_ensemble(schema, trees[m])
                    jax.block_until_ready(ens.factors)
                with ctx.spans("bench.score"):
                    out = jax.block_until_ready(score_grouped(ens, g))
            else:
                ens = compile_ensemble(schema, trees[m])
                out = jax.block_until_ready(score_grouped(ens, g))
        kept[m] = out
        done += 1
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    elapsed = time.perf_counter() - t0
    n_rows = len(next(iter(st["data"][g].values())))
    ctx.extra["rescores"] = done
    st["kept"] = kept
    return {"rescore_rows_per_s": n_rows * done / elapsed,
            "_attempted": done, "_failed": 0}


def collect(ctx, st):
    return {"data": st["data"], "models": st["models"],
            "scores": {m: (np.asarray(t), np.asarray(c))
                       for m, (t, c) in st["kept"].items()}}


def readings(ctx, out, q=None) -> dict:
    """Widest relative gap of Σŷ and largest count error over every row
    of every ensemble scored; with ``q`` the reference computes the
    scores in the program's place, rounding as ``q`` does (the control)."""
    from reference import join, trees as ref

    cfg = ctx.config
    X, _, root = join.materialize(cfg, out["data"])
    n = len(next(iter(out["data"][cfg["group_by"]].values())))
    want_cnt = np.bincount(root, minlength=n)
    worst = {"score_rel": 0.0, "count_err": 0.0,
             "models": float(len(out["models"]) - len(out["scores"]))}
    for m, model in enumerate(out["models"]):
        want = np.bincount(root, weights=ref.predict(model, X), minlength=n)
        if q is None:
            if m not in out["scores"]:
                continue
            tot, cnt = out["scores"][m]
        else:
            Xq = q(X).astype(np.float32)
            tot = np.bincount(root, weights=ref.predict(ref.rounded(model, q), Xq, q),
                              minlength=n)
            cnt = want_cnt
        scale = np.maximum(np.abs(want), np.median(np.abs(want)))
        worst["score_rel"] = max(worst["score_rel"],
                                 float(np.max(np.abs(tot - want) / scale)))
        worst["count_err"] = max(worst["count_err"],
                                 float(np.max(np.abs(cnt - want_cnt))))
    return worst

