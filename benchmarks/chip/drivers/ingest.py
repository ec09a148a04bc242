"""TPC-H RF1/RF2 micro-batches applied through the maintained scorer,
with every live LINEITEM row's score read back fresh after each.

Set-up makes the tables at ``rows`` and one ensemble from the seed
(``harness.models.random_trees``), compiles it, hands it to a
``MaintainedScorer`` (which keeps its own capacity-padded copy; the
compiled ensemble is dropped) and generates ``micro_batches`` RF
micro-batches (``harness.tpch_rf``, ``per_pair`` to a refresh pair).
It scores LINEITEM once (the full message pass) and then applies and
scores ``warmup`` micro-batches.

The window takes the next micro-batch, closed loop: one
``MaintainedScorer.apply`` with four ``TableDelta``s (RF1's ORDERS and
LINEITEM inserts, then RF2's LINEITEM and ORDERS deletes by slot), then
``score_grouped(ms, "lineitem")`` ended by ``block_until_ready``, until
``--seconds`` have passed.  A stream that runs out fails the run; it
never wraps.  ``rescore_rows_per_s`` is the live LINEITEM rows summed
over the window's micro-batches (each read scores them all), over the
window's seconds.  A traced run turns the program's tracing on, so
that its ``ivm.apply_ms`` and ``ivm.refresh_ms`` timers wait for the
device.

Judged on the host after the window, at two versions: after the
window's micro-batch ``m`` (``m`` drawn from the seed below
``mid_window``; its scores stay on the device until the window
closes) and after the last.  The reference (``reference/rf.py``)
replays the same micro-batches by orderkey onto the initial tables,
materializes the join (``reference/join.py``) and predicts every row
(``reference/trees.py``); rows are matched to the program's live slots
by (l_orderkey, l_linenumber).
"""
from __future__ import annotations

import sys
import time

import numpy as np

from harness import ingest_counts, models, program, tpch_rf


def _deltas(s: dict, j: int) -> list:
    from repro.incremental import TableDelta

    return [TableDelta("orders", inserts=tpch_rf.batch(s, j, "orders", "insert")),
            TableDelta("lineitem", inserts=tpch_rf.batch(s, j, "lineitem", "insert")),
            TableDelta("lineitem", deletes=tpch_rf.batch(s, j, "lineitem", "delete")),
            TableDelta("orders", deletes=tpch_rf.batch(s, j, "orders", "delete"))]


def _programs() -> float | None:
    from repro.obs import get_registry

    reg = get_registry()
    if "ivm.refresh_programs" not in reg.names():
        return None
    return float(reg.counter("ivm.refresh_programs").value)


def _step(ms, deltas, g):
    """One micro-batch: apply, then every row's fresh score."""
    import jax
    import repro.serving as serving

    ms.apply(deltas)
    return jax.block_until_ready(serving.score_grouped(ms, g))


def _version(ms, g, scores, applied: int) -> dict:
    """What the reference needs of one version: the scores, and the
    live slots with their (orderkey, linenumber) as they stand now."""
    dt = ms.tables[g]
    slots = dt.live_slots()
    return {"applied": applied, "steps": ms.data_version, "slots": slots,
            "okey": dt.columns["orderkey"][slots], "lnum": dt.columns["l_linenumber"][slots],
            "scores": scores}


def setup(ctx):
    import jax
    import repro.serving as serving
    from repro.incremental import MaintainedScorer

    cfg, tr = ctx.config, ctx.traffic
    g = cfg["group_by"]
    data = models.tables(cfg, ctx.seed, cfg["rows"], ctx.shrink)
    schema = program.schema_of(cfg, data)
    model = models.random_trees(cfg, data, ctx.seed, 0, tr["n_trees"], tr["depth"])
    with ctx.spans("bench.build"):
        ms = MaintainedScorer(serving.compile_ensemble(
            schema, [program.tree_arrays(t) for t in model]))
    s = tpch_rf.stream(cfg, data, ctx.seed, tr["micro_batches"], tr["per_pair"])
    deltas = [_deltas(s, j) for j in range(tr["micro_batches"])]
    net = np.diff(s["lineitem_at"]) - np.diff(s["del_lines_at"])
    live = len(data[g]["orderkey"]) + np.cumsum(net)
    with ctx.spans("bench.warmup"):
        jax.block_until_ready(serving.score_grouped(ms, g))
        for j in range(tr["warmup"]):
            _step(ms, deltas[j], g)
    st = {"data": data, "model": model, "ms": ms, "stream": s, "deltas": deltas,
          "live": live, "next": tr["warmup"], "group": g,
          "mid": int(models.seed_rng(ctx.seed, 16).integers(0, tr["mid_window"]))}
    print(f"ingest: refresh_programs after set-up {_programs()}", file=sys.stderr, flush=True)
    return st


def window(ctx, st):
    from repro import obs

    ms, deltas, g = st["ms"], st["deltas"], st["group"]
    first, done = st["next"], 0
    if ctx.traced:
        obs.enable_tracing()
    try:
        t0 = time.perf_counter()
        while True:
            j = first + done
            if j >= len(deltas):
                raise RuntimeError(f"the stream of {len(deltas)} micro-batches ran out; "
                                   f"traffic 'micro_batches' is too small for the window")
            with ctx.spans("bench.micro_batch"):
                scores = _step(ms, deltas[j], g)
            if done == st["mid"]:
                st["kept"] = _version(ms, g, scores, j + 1)
            done += 1
            if time.perf_counter() - t0 >= ctx.seconds:
                break
        elapsed = time.perf_counter() - t0
    finally:
        if ctx.traced:
            obs.disable_tracing()
    st["next"] = first + done
    st["last"] = _version(ms, g, scores, first + done)
    s, cfg = st["stream"], ctx.config
    span = slice(first, first + done)
    ctx.extra["micro_batches"] = done
    ctx.extra["bytes"] = float(np.mean(ingest_counts.micro_batch_bytes(
        cfg, {t: np.diff(s[f"{t}_at"])[span] for t in ("orders", "lineitem")},
        {"orders": np.diff(s["del_orders_at"])[span],
         "lineitem": np.diff(s["del_lines_at"])[span]}, st["live"][span])))
    print(f"ingest: micro_batches={done} refresh_programs after window {_programs()}",
          file=sys.stderr, flush=True)
    return {"rescore_rows_per_s": float(st["live"][span].sum()) / elapsed,
            "_attempted": done, "_failed": 0}


def collect(ctx, st):
    def host(v):
        tot, cnt = v["scores"]
        return dict(v, scores=(np.asarray(tot)[v["slots"]], np.asarray(cnt)[v["slots"]]))

    versions = [host(st["last"])] + ([host(st.pop("kept"))] if "kept" in st else [])
    return {"data": st["data"], "stream": st["stream"], "model": st["model"],
            "versions": versions}


def readings(ctx, out, q=None) -> dict:
    """At each kept version: the widest relative gap of Σŷ and the
    largest count error over the matched live rows, the rows on one
    side only, and micro-batches applied less scorer versions; with
    ``q`` the reference computes the scores in the program's place,
    rounding as ``q`` does (the control)."""
    from reference import join, rf, trees as ref

    cfg = ctx.config
    worst = {"score_rel": 0.0, "count_err": 0.0, "rows_err": 0.0, "versions": 0.0}
    for v in out["versions"]:
        tables = rf.replay(out["data"], out["stream"], v["applied"])
        X, _, root = join.materialize(cfg, tables)
        li = tables[cfg["group_by"]]
        n = len(li["orderkey"])
        want_cnt = np.bincount(root, minlength=n)
        want = np.bincount(root, weights=ref.predict(out["model"], X), minlength=n)
        keys = rf.row_keys(li["orderkey"], li["l_linenumber"])
        got = rf.row_keys(v["okey"], v["lnum"])
        order = np.argsort(keys, kind="stable")
        row = order[np.clip(np.searchsorted(keys, got, sorter=order), 0, n - 1)]
        hit = keys[row] == got
        row = row[hit]
        worst["rows_err"] = max(worst["rows_err"],
                                float((~hit).sum() + n - len(np.unique(row))))
        tot, cnt = (a[hit] for a in v["scores"])
        if q is not None:
            Xq = q(X).astype(np.float32)
            tot = np.bincount(root, weights=ref.predict(ref.rounded(out["model"], q), Xq, q),
                              minlength=n)[row]
            cnt = want_cnt[row]
        scale = np.maximum(np.abs(want[row]), np.median(np.abs(want)))
        worst["score_rel"] = max(worst["score_rel"],
                                 float(np.max(np.abs(tot - want[row]) / scale, initial=0.0)))
        worst["count_err"] = max(worst["count_err"],
                                 float(np.max(np.abs(cnt - want_cnt[row]), initial=0.0)))
        worst["versions"] = max(worst["versions"], float(abs(v["applied"] - v["steps"])))
    return worst
