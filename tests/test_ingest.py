"""The maintained scorer under TPC-H refresh micro-batches (RF1 order
inserts, RF2 order deletes) on the snowflake LINEITEM → ORDERS →
CUSTOMER → NATION → REGION, PART, SUPPLIER, at 1/256 of the chip
benchmark's ``tpch_snow`` deployment: bit-equal to the full-recompute
oracle, within float32 rounding of the benchmark's plain numpy
reference, snapshots pinned, and no compilation once the programs a
stream's shapes need are built."""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

from harness import core, models, program, tpch_rf  # noqa: E402
from reference import join, rf, trees as ref  # noqa: E402

from repro.incremental import MaintainedScorer, TableDelta  # noqa: E402
from repro.incremental.maintain import key_bucket  # noqa: E402
from repro.obs import get_registry  # noqa: E402
from repro.serving import compile_ensemble  # noqa: E402

SHRINK = 256
SEED = 2 ** 35 + 11
G = "lineitem"


def _deltas(s, j):
    return [TableDelta("orders", inserts=tpch_rf.batch(s, j, "orders", "insert")),
            TableDelta("lineitem", inserts=tpch_rf.batch(s, j, "lineitem", "insert")),
            TableDelta("lineitem", deletes=tpch_rf.batch(s, j, "lineitem", "delete")),
            TableDelta("orders", deletes=tpch_rf.batch(s, j, "orders", "delete"))]


def _scorer(n_batches, **stream_kw):
    cfg = core.config_of("tpch_snow")
    data = models.tables(cfg, SEED, cfg["rows"], SHRINK)
    model = models.random_trees(cfg, data, SEED, 0)
    ms = MaintainedScorer(compile_ensemble(
        program.schema_of(cfg, data), [program.tree_arrays(t) for t in model]))
    s = tpch_rf.stream(cfg, data, SEED, n_batches, **stream_kw)
    return cfg, data, model, ms, s


def _assert_oracle(scorer, scores):
    tot_o, cnt_o = scorer.recompute_oracle(G)
    np.testing.assert_array_equal(np.asarray(scores[1]), np.asarray(cnt_o))
    np.testing.assert_array_equal(np.asarray(scores[0]), np.asarray(tot_o))


def test_rf_stream_matches_oracle_and_plain_reference():
    """Every micro-batch's fresh scores: bit-equal to a fresh compile of
    the live tables; against the numpy replay (by orderkey) of the same
    micro-batches, the same rows, exact counts and Σŷ within 1e-5."""
    cfg, data, model, ms, s = _scorer(4)
    ms.score_grouped(G)
    for j in range(4):
        assert ms.apply(_deltas(s, j)) == j + 1
        scores = ms.score_grouped(G)
        if j in (0, 3):
            _assert_oracle(ms, scores)
    tables = rf.replay(data, s, 4)
    X, _, root = join.materialize(cfg, tables)
    li = tables[G]
    n = len(li["orderkey"])
    want = np.bincount(root, weights=ref.predict(model, X), minlength=n)
    slots = ms.live_rows(G)
    got = rf.row_keys(ms.tables[G].columns["orderkey"][slots],
                      ms.tables[G].columns["l_linenumber"][slots])
    keys = rf.row_keys(li["orderkey"], li["l_linenumber"])
    assert len(slots) == n and np.array_equal(np.sort(got), np.sort(keys))
    row = np.argsort(keys)[np.searchsorted(keys, got, sorter=np.argsort(keys))]
    tot, cnt = (np.asarray(a)[slots] for a in scores)
    np.testing.assert_array_equal(cnt, np.bincount(root, minlength=n)[row])
    scale = np.maximum(np.abs(want[row]), np.median(np.abs(want)))
    assert np.max(np.abs(tot - want[row]) / scale) <= 1e-5


def test_no_compilation_after_warmup():
    """Once two micro-batches have built the programs, further ones
    compile nothing, though every one grows the ORDERS key domain and
    their delta lengths vary: the refresh is keyed by shapes, and key
    domains and slot lists are bucketed."""
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **_: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    _, _, _, ms, s = _scorer(8, orders_per_pair=240)
    ms.score_grouped(G)
    for j in range(2):
        ms.apply(_deltas(s, j))
        jax.block_until_ready(ms.score_grouped(G))
    programs = get_registry().counter("ivm.refresh_programs").value
    edge = ms.edges[frozenset(("lineitem", "orders"))]
    keys0, n0 = edge.n_keys, len(compiles)
    for j in range(2, 8):
        ms.apply(_deltas(s, j))
        jax.block_until_ready(ms.score_grouped(G))
    assert len(compiles) == n0
    assert get_registry().counter("ivm.refresh_programs").value == programs
    lines = np.diff(s["lineitem_at"])[2:8]
    assert edge.n_keys > keys0 and key_bucket(edge.n_keys) == key_bucket(keys0)
    assert len(set(lines)) > 1          # the lengths varied, within one bucket


def test_snapshot_pinned_before_a_micro_batch_reads_its_version():
    _, _, _, ms, s = _scorer(2)
    ms.score_grouped(G)
    ms.apply(_deltas(s, 0))
    before = ms.score_grouped(G)
    snap = ms.snapshot(roots=(G,), pin_oracle=True)
    ms.apply(_deltas(s, 1))
    after = ms.score_grouped(G)
    old = snap.score_grouped(G)
    for a, b in zip(old, before):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(after[1]), np.asarray(before[1]))
    tot_o, cnt_o = snap.recompute_oracle(G)
    np.testing.assert_array_equal(np.asarray(old[0]), np.asarray(tot_o))
    np.testing.assert_array_equal(np.asarray(old[1]), np.asarray(cnt_o))
    _assert_oracle(ms, after)


@pytest.mark.parametrize("n", [1, 8, 9, 12, 13, 25, 104_858, 2 ** 20, 2 ** 20 + 1])
def test_key_bucket_is_a_geometric_ladder(n):
    b = key_bucket(n)
    assert b >= n and b >= 8
    assert b & (b - 1) == 0 or (b // 3) & (b // 3 - 1) == 0   # 2^k or 1.5 · 2^k
    assert b <= max(8, 1.5 * n)
    assert key_bucket(b) == b
