"""The ingest cell: its RF stream keeps to TPC-H's refresh functions,
and the comparison that decides ``correct`` fails what it must.

Driven on the CPU at 1/256 (``--rehearse``), as ``test_faults.py``
drives the other cells: a sound run is correct and its bfloat16 control
is not; a skipped micro-batch's deletes, scores a version stale, and
one altered row each read ``correct`` false.  A minute or two.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness import core, models, tpch_rf
from test_faults import SHRINK, Cell, fails_control

SEED = 2 ** 34 + 9


@pytest.fixture(scope="module")
def data():
    cfg = core.config_of("tpch_snow")
    return cfg, models.tables(cfg, SEED, cfg["rows"], SHRINK)


def test_rf_stream_keeps_to_the_refresh_functions(data):
    cfg, d = data
    s = tpch_rf.stream(cfg, d, SEED, 200)
    okey, lkey = d["orders"]["orderkey"], d["lineitem"]["orderkey"]
    new = s["orders"]["orderkey"]
    # RF1: new orderkeys only, each once, with 1..7 lines each
    assert len(np.unique(new)) == len(new) and not np.isin(new, okey).any()
    lines = np.bincount(np.searchsorted(new, s["lineitem"]["orderkey"]), minlength=len(new))
    assert np.all(np.sort(new) == new) and lines.min() >= 1 and lines.max() <= 7
    assert np.array_equal(np.diff(s["lineitem_at"]),
                          np.add.reduceat(lines, s["orders_at"][:-1]))
    # RF2: initial orders, each once, and exactly the rows of their lines
    gone = s["del_orders"]
    assert len(np.unique(gone)) == len(gone) and gone.max() < len(okey)
    assert np.array_equal(s["del_keys"], okey[gone])
    for j in (0, 57, 199):
        rows = tpch_rf.batch(s, j, "lineitem", "delete")
        keys = okey[tpch_rf.batch(s, j, "orders", "delete")]
        assert np.array_equal(np.sort(rows), np.flatnonzero(np.isin(lkey, keys)))
    assert np.all(np.diff(s["orders_at"]) >= 1)


def test_rf_stream_is_deterministic_per_seed_and_prefix_stable(data):
    cfg, d = data
    a, b = (tpch_rf.stream(cfg, d, SEED, 30) for _ in range(2))
    c = tpch_rf.stream(cfg, d, SEED + 1, 30)
    for k in ("del_orders", "del_lines", "lineitem_at"):
        assert np.array_equal(a[k], b[k])
    assert all(np.array_equal(a["lineitem"][col], b["lineitem"][col]) for col in a["lineitem"])
    assert not np.array_equal(a["del_orders"], c["del_orders"])
    longer = tpch_rf.stream(cfg, d, SEED, 45)
    for j in (0, 13, 29):
        for name in ("orders", "lineitem"):
            for kind in ("insert", "delete"):
                x, y = (tpch_rf.batch(t, j, name, kind) for t in (a, longer))
                x, y = (x, y) if kind == "delete" else (x["orderkey"], y["orderkey"])
                assert np.array_equal(x, y)


def test_rf_stream_longer_than_the_population_is_refused(data):
    cfg, d = data
    with pytest.raises(ValueError, match="initial population"):
        tpch_rf.stream(cfg, d, SEED, len(d["orders"]["orderkey"]) + 1)


@pytest.fixture(scope="module")
def ingest():
    return Cell("tpch_snow", "ingest")


def test_ingest_sound_run_is_correct_and_control_fails(ingest):
    checks, out = ingest.drive(seconds=4.0)
    assert checks.ok, checks.items
    # the mid-window version is judged whenever the window reached it
    assert len(out["versions"]) == 1 + (ingest.ctx.extra["micro_batches"] > ingest.st["mid"])
    assert "score_rel" in fails_control(ingest, out)


def test_ingest_skipped_deletes(ingest, monkeypatch):
    ms = ingest.st["ms"]
    apply, skipped = ms.apply, []

    def drop_deletes(deltas):
        if not skipped:
            skipped.append(1)
            deltas = [d for d in deltas if d.deletes is None]
        return apply(deltas)

    monkeypatch.setattr(ms, "apply", drop_deletes)
    checks, _ = ingest.drive(seconds=1.0)
    assert not checks.ok and checks.items["rows_err"]["value"] > 0


def _patch_scores(monkeypatch, fn):
    import repro.serving as serving

    score = serving.score_grouped
    monkeypatch.setattr(serving, "score_grouped", lambda ms, g: fn(ms, *score(ms, g)))


def test_ingest_scores_a_version_stale(ingest, monkeypatch):
    prev = []

    def stale(ms, tot, cnt):
        prev.append((tot, cnt))
        return prev[-2] if len(prev) > 1 else prev[-1]

    _patch_scores(monkeypatch, stale)
    checks, _ = ingest.drive(seconds=1.0)
    assert not checks.ok


def test_ingest_one_altered_row(ingest, monkeypatch):
    _patch_scores(monkeypatch,
                  lambda ms, tot, cnt: (tot.at[int(ms.live_rows("lineitem")[3])].add(1.0), cnt))
    checks, _ = ingest.drive(seconds=1.0)
    assert not checks.ok and checks.items["score_rel"]["value"] > 1e-4


def test_run_rehearses_the_cell_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, str(core.BENCH / "run.py"), "--workload", "tpch_snow.ingest",
         "--seed", str(SEED), "--seconds", "2", "--rehearse", str(SHRINK)],
        capture_output=True, text=True, env=env, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "rescore_rows_per_s"}
