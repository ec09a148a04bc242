"""One boosting round on a frozen prefix, repeated.

Set-up makes the training tables at ``train_rows``, builds one
``Booster`` with the traffic file's ``BoostConfig`` and its sketch
hashes from the seed, grows the ``prefix_trees`` prefix with
``boost([], n)`` and runs one untimed round on it.  The window calls
``boost(prefix, 1)`` until ``--seconds`` have passed: the same round
every time (the refit ``IncrementalBooster`` runs at ``max_trees``),
the same level programs.  ``train_round_s`` is the window's seconds
over the rounds it started, each ended by ``block_until_ready``.  A
traced run traces one round, which holds every level program, so that
the whole run stays well inside its time limit.

Judged by the reference (``reference/trees.py``) on the materialized
join: every prefix tree and the window's last tree, each on the
residuals of the trees before it, for split gains, leaf values and the
LINEITEM-grouped node SSRs (a LINEITEM row joins one tuple, so that
grouping's sketch is exact).
"""
from __future__ import annotations

import time

import numpy as np

from harness import models, program


def config_of(traffic: dict):
    from repro.core import BoostConfig

    return BoostConfig(**traffic["boost"])


def setup(ctx):
    import jax
    from repro.core import Booster

    cfg = ctx.config
    data = models.tables(cfg, ctx.seed, cfg["train_rows"], ctx.shrink)
    schema = program.schema_of(cfg, data)
    bcfg = config_of(ctx.traffic)
    # the seed reaches the program as an array, so one compiled key
    # derivation serves every seed
    key = jax.random.PRNGKey(jax.numpy.asarray(int(ctx.seed) % (1 << 32), "uint32"))
    booster = Booster(schema, bcfg, key=key)
    with ctx.spans("bench.prefix"):
        prefix, ptrace = booster.boost([], ctx.traffic["prefix_trees"])
        jax.block_until_ready([(t.feat, t.thr, t.leaf) for t in prefix])
    with ctx.spans("bench.warmup"):
        trees, _ = booster.boost(prefix, 1)
        jax.block_until_ready(trees[-1].leaf)
    return {"data": data, "booster": booster, "prefix": prefix,
            "ptrace": ptrace, "group": cfg["group_by"]}


def window(ctx, st):
    import jax
    from repro import obs

    booster, prefix = st["booster"], st["prefix"]
    level = obs.get_registry().histogram("train.level_ms")
    n0, s0 = level.count, level.sum
    if ctx.traced:                       # the trainer's level timer blocks
        obs.enable_tracing(jax_annotations=False)
    rounds, t0 = 0, time.perf_counter()
    try:
        while True:
            with ctx.spans("bench.round"):
                trees, trace = booster.boost(prefix, 1)
                jax.block_until_ready([(t.feat, t.thr, t.leaf) for t in trees])
            rounds += 1
            if time.perf_counter() - t0 >= (0 if ctx.traced else ctx.seconds):
                break
        elapsed = time.perf_counter() - t0
    finally:
        if ctx.traced:
            obs.disable_tracing()
    if level.count > n0:
        ctx.extra["level_ms"] = (level.sum - s0) / (level.count - n0)
    st["last"], st["last_trace"] = trees, trace
    return {"train_round_s": elapsed / rounds, "_attempted": rounds, "_failed": 0}


def collect(ctx, st):
    g = st["group"]
    trees = [program.tree_dict(t) for t in st["last"]]
    n_prefix = len(st["prefix"])
    depth = len(trees[0]["leaf"]).bit_length() - 1 if trees else 0

    def ssr(trace, tree_index):
        return [np.asarray(trace.node_ssr[tree_index * depth + lv][g])
                for lv in range(depth)]

    return {
        "data": st["data"], "lr": st["booster"].cfg.lr,
        "n_prefix": n_prefix, "trees": trees,
        "ssr": [ssr(st["ptrace"], i) for i in range(n_prefix)]
               + ([ssr(st["last_trace"], 0)] if len(trees) > n_prefix else []),
        "peak": ctx.device.get("memory_peak_bytes"),
    }


def readings(ctx, out, q=None) -> dict:
    """The compared numbers: the worst over every tree judged.  With
    ``q`` the reference itself grows the window's round in the
    program's place, rounding as ``q`` does (the control)."""
    from reference import join, trees as ref

    X, y, _ = join.materialize(ctx.config, out["data"])
    trees, lr = out["trees"], out["lr"]
    worst = {"gain_gap": 0.0, "leaf_rel": 0.0, "ssr_rel": 0.0,
             "trees": float(abs(len(trees) - out["n_prefix"] - 1))}
    judged = range(len(trees)) if q is None else [len(trees) - 1]
    for i in judged:
        r = y - ref.predict(trees[:i], X)
        if q is None:
            tree = trees[i]
            ssr = out["ssr"][i] if i < len(out["ssr"]) else None
        else:
            depth = len(trees[i]["leaf"]).bit_length() - 1
            tree, ssr = ref.grow(X, r, depth, lr=lr, q=q)
        got = ref.judge(X, r, tree, ssr=ssr, lr=lr)
        for k, v in got.items():
            worst[k] = max(worst[k], v)
    return worst

