"""Serving subsystem: compiled one-pass scorer vs the seed per-leaf loop
AND the materialized-join oracle on star/chain/snowflake schemas; Pallas
kernel routing; interactive entry points; micro-batching service
(coalescing, LRU cache, versioned hot swap); pipeline integration."""
import asyncio

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _trees import random_trees
from repro.core import BoostConfig, Booster, QueryCounter, predict_rows
from repro.core.schema import Schema, Table
from repro.core.tree import leaf_masks
from repro.relational.generators import star_schema
from repro.serving import (
    LRUCache, ModelRegistry, RelationalScoringService, compile_ensemble,
    score_fresh, score_grouped, score_grouped_reference, score_mean_rows,
    score_rows, stack_table_factor,
)


def _fit(sch, n_trees=3, depth=2):
    b = Booster(sch, BoostConfig(n_trees=n_trees, depth=depth,
                                 mode="sketch", ssr_mode="off"))
    trees, _ = b.fit()
    return trees


@pytest.fixture(scope="module")
def star_trees(star):
    """One shared 3-tree fit on the star schema; tests needing fewer
    trees slice it (a sliced list is a valid smaller ensemble)."""
    return _fit(star[0])


def _oracle(sch, J, X, trees, group):
    rows = np.asarray(J["__rows__" + group])
    preds = np.asarray(predict_rows(trees, X))
    n = sch.table(group).n_rows
    return (np.bincount(rows, weights=preds, minlength=n),
            np.bincount(rows, minlength=n))


@pytest.mark.parametrize("fixture", ["star", "chain", "snowflake"])
def test_score_grouped_matches_reference_and_oracle(fixture, request):
    sch, J, X, y = request.getfixturevalue(fixture)
    trees = (request.getfixturevalue("star_trees") if fixture == "star"
             else _fit(sch, n_trees=2))
    group = sch.label_table

    c_old, c_new = QueryCounter(), QueryCounter()
    tot_ref, cnt_ref = score_grouped_reference(sch, trees, group, counter=c_old)
    ens = compile_ensemble(sch, trees, counter=c_new)
    tot, cnt = score_grouped(ens, group)

    want_tot, want_cnt = _oracle(sch, J, X, trees, group)
    np.testing.assert_allclose(np.asarray(tot), want_tot, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(cnt), want_cnt, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(tot), np.asarray(tot_ref),
                               rtol=1e-3, atol=1e-3)
    # one stacked pass replaces the n_trees·L + 1 per-leaf passes
    assert c_new.count == 1
    assert c_old.count == sum(int(t.leaf.shape[0]) for t in trees) + 1
    assert c_old.count / c_new.count >= 5


def test_score_grouped_every_table(star, star_trees):
    """Grouping by dimension tables must match the oracle too."""
    sch, J, X, y = star
    trees = star_trees
    ens = compile_ensemble(sch, trees)
    for t in sch.tables:
        tot, cnt = score_grouped(ens, t.name)
        want_tot, want_cnt = _oracle(sch, J, X, trees, t.name)
        np.testing.assert_allclose(np.asarray(tot), want_tot, rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(cnt), want_cnt, rtol=1e-5)


def test_dead_node_routes_left(star):
    """A dead internal node (feat -1, thr +inf) sends every row to its
    left child, as predict_rows does: its rows count once, not in both
    children's leaves."""
    from repro.core.tree import TreeArrays

    sch, J, X, y = star
    tree = TreeArrays(feat=jnp.asarray([0, -1, 2], jnp.int32),
                      thr=jnp.asarray([0.0, np.inf, 0.0], jnp.float32),
                      leaf=jnp.asarray([1.0, 2.0, 3.0, 4.0], jnp.float32))
    tot, cnt = score_grouped(compile_ensemble(sch, [tree]), "fact")
    want_tot, want_cnt = _oracle(sch, J, X, [tree], "fact")
    np.testing.assert_allclose(np.asarray(cnt), want_cnt, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(tot), want_tot, rtol=1e-3, atol=1e-3)


def eager_factor(sch, trees, table, featmat=None, dtype=jnp.float32):
    """A table's factor built op by op, the reference for the factor
    program: per-tree ``leaf_masks``, concatenated, transposed, cast."""
    per_tree = [leaf_masks(sch, table, t, featmat=featmat) for t in trees]
    return jnp.concatenate(per_tree, axis=0).T.astype(dtype)


def same_bits(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and a.tobytes() == b.tobytes()


def _star_with_bare_table():
    """A star whose second dimension has no feature: its factor has no
    column to read, and every split is foreign to it."""
    base = star_schema(seed=8, n_fact=90, n_dim=11)
    tables = []
    for t in base.tables:
        tab = Table(t.name, dict(t.columns), feature_columns=t.feature_columns)
        if t.name == "dim1":
            tab.feature_columns = ()
        tables.append(tab)
    return Schema(tables, label=("fact", "y"))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("fixture", ["star", "chain", "snowflake", "bare_table"])
def test_factor_program_matches_eager_masks_bit_for_bit(fixture, dtype, request):
    """The shape-keyed factor program gives the eager build's factors bit
    for bit: over a whole table, over a subset of its rows (as
    maintenance evaluates a delta, alone and under its own jit), with
    dead nodes, mixed depths and splits foreign to the table."""
    sch = (_star_with_bare_table() if fixture == "bare_table"
           else request.getfixturevalue(fixture)[0])
    trees = random_trees(sch, depths=(3, 2, 2), seed=14)
    ens = compile_ensemble(sch, trees, factor_dtype=dtype)
    rng = np.random.default_rng(14)
    for t in sch.tables:
        want = eager_factor(sch, trees, t.name, dtype=dtype)
        assert same_bits(ens.factors[t.name], want), t.name
        rows = rng.choice(t.n_rows, size=min(6, t.n_rows), replace=False)
        sub = sch.featmat[t.name][rows]
        want_sub = eager_factor(sch, trees, t.name, featmat=sub, dtype=dtype)
        assert same_bits(want_sub, want[rows]), t.name
        assert same_bits(stack_table_factor(sch, trees, t.name, featmat=sub,
                                            dtype=dtype), want_sub), t.name
        masks = jax.jit(lambda fm, name=t.name: stack_table_factor(
            sch, trees, name, featmat=fm, dtype=dtype))
        assert same_bits(masks(sub), want_sub), t.name
    assert same_bits(ens.leaf_values, jnp.concatenate([t.leaf for t in trees]))
    # the feature map as the eager build made it, one scatter per feature
    for ti, t in enumerate(sch.tables):
        g2l = -jnp.ones((max(sch.n_features, 1),), jnp.int32)
        for g, (tg, li) in enumerate(sch.feat_global):
            if tg == ti:
                g2l = g2l.at[g].set(li)
        assert same_bits(sch.local_feature_ids[t.name], g2l), t.name


def test_kernel_routed_scoring_matches(star, star_trees):
    sch, J, X, y = star
    trees = star_trees[:2]
    tot, cnt = score_grouped(compile_ensemble(sch, trees), "fact")
    tot_k, cnt_k = score_grouped(compile_ensemble(sch, trees, use_kernel=True), "fact")
    np.testing.assert_allclose(np.asarray(tot_k), np.asarray(tot), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cnt_k), np.asarray(cnt), rtol=1e-5)


def test_score_rows_and_fresh(star, star_trees):
    sch, J, X, y = star
    trees = star_trees
    ens = compile_ensemble(sch, trees)
    tot, cnt = score_grouped(ens, "fact")
    ids = np.asarray([0, 3, 3, 17, 299])
    t2, c2 = score_rows(ens, "fact", ids)
    np.testing.assert_allclose(np.asarray(t2), np.asarray(tot)[ids])
    np.testing.assert_allclose(np.asarray(c2), np.asarray(cnt)[ids])
    mean = score_mean_rows(ens, "fact", ids)
    np.testing.assert_allclose(
        np.asarray(mean),
        np.asarray(tot)[ids] / np.maximum(np.asarray(cnt)[ids], 1.0),
        rtol=1e-6,
    )
    # fresh rows == materialized-path predictions
    feats = {c: np.asarray(J[c])[:8] for (_, c) in sch.features}
    np.testing.assert_allclose(
        np.asarray(score_fresh(ens, feats)),
        np.asarray(predict_rows(trees, X))[:8], rtol=1e-5, atol=1e-6,
    )
    with pytest.raises(KeyError):
        score_fresh(ens, {"x0": np.zeros(2)})
    # out-of-range ids must be rejected, not silently clamped by jnp.take
    for bad in ([-1], [sch.table("fact").n_rows]):
        with pytest.raises(IndexError):
            score_rows(ens, "fact", bad)


def test_booster_predict_grouped_rewired(star):
    """Booster.predict_grouped must go through the compiled scorer and
    keep the seed semantics (regression for the rewiring)."""
    sch, J, X, y = star
    b = Booster(sch, BoostConfig(n_trees=2, depth=2, mode="sketch", ssr_mode="off"))
    trees, _ = b.fit()
    tot, cnt = b.predict_grouped(trees, "fact")
    want_tot, want_cnt = _oracle(sch, J, X, trees, "fact")
    np.testing.assert_allclose(np.asarray(tot), want_tot, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(cnt), want_cnt)


# ---------------------------------------------------------------- service --

def test_lru_cache_eviction_and_stats():
    c = LRUCache(2)
    assert c.get("a") is None
    c.put("a", 1.0)
    c.put("b", 2.0)
    assert c.get("a") == 1.0         # refreshes "a"
    c.put("c", 3.0)                  # evicts "b" (LRU)
    assert c.get("b") is None
    assert c.get("a") == 1.0 and c.get("c") == 3.0
    assert c.hits == 3 and c.misses == 2 and len(c) == 2


def test_registry_versions(star, star_trees):
    sch, J, X, y = star
    reg = ModelRegistry()
    with pytest.raises(LookupError):
        reg.latest_version()
    e1 = compile_ensemble(sch, star_trees[:1])
    e2 = compile_ensemble(sch, star_trees[:2])
    v1, v2 = reg.publish(e1), reg.publish(e2)
    assert v2 > v1 and reg.latest_version() == v2
    assert reg.get()[1] is e2 and reg.get(v1)[1] is e1
    assert reg.versions() == [v1, v2]
    # bounded retention: oldest versions evict past max_versions
    small = ModelRegistry(max_versions=1)
    w1, w2 = small.publish(e1), small.publish(e2)
    assert small.versions() == [w2]
    with pytest.raises(KeyError):
        small.get(w1)


def test_service_microbatching_and_hot_swap(star, star_trees):
    sch, J, X, y = star
    trees1 = star_trees[:1]
    trees2 = star_trees
    reg = ModelRegistry()
    reg.publish(compile_ensemble(sch, trees1))
    svc = RelationalScoringService(reg, "fact", max_batch=32, max_wait_ms=5.0,
                                   cache_size=64)
    ens = compile_ensemble(sch, trees1)
    tot, cnt = score_grouped(ens, "fact")
    want = np.asarray(tot) / np.maximum(np.asarray(cnt), 1.0)

    async def run():
        with pytest.raises(RuntimeError):      # not started yet
            await svc.score(0)
        await svc.start()
        with pytest.raises(IndexError):        # bad id fails only its caller
            await svc.score(10_000)
        got = await svc.score_many(range(40))
        np.testing.assert_allclose(np.asarray(got), want[:40], rtol=1e-5)
        # second wave repeats 20 rows → pure cache hits
        rep = await svc.score_many(range(20))
        np.testing.assert_allclose(np.asarray(rep), want[:20], rtol=1e-5)

        # hot swap: v2 published mid-traffic; new requests use it
        v2 = reg.publish(compile_ensemble(sch, trees2))
        tot2, cnt2 = score_grouped(compile_ensemble(sch, trees2), "fact")
        want2 = np.asarray(tot2) / np.maximum(np.asarray(cnt2), 1.0)
        got2 = await svc.score_many(range(10))
        np.testing.assert_allclose(np.asarray(got2), want2[:10], rtol=1e-5)
        # pinned-version requests still hit v1
        got1 = await svc.score(5, version=v2 - 1)
        np.testing.assert_allclose(got1, want[5], rtol=1e-5)
        await svc.stop()
        with pytest.raises(RuntimeError):      # stopped → no silent hang
            await svc.score(0)

    asyncio.run(run())
    st = svc.stats
    assert st.requests == 71
    assert st.cache_hits >= 20                   # the repeated ids
    assert st.batches < st.requests - st.cache_hits   # coalescing happened
    assert st.mean_batch > 1.0


def test_pipeline_importance_sampling_applied():
    """Regression for the dead-code `keep` bug: one-hot weights must pin
    every produced row to the selected corpus doc, deterministically."""
    from repro.data.pipeline import TokenPipeline

    w = np.zeros(50, np.float64)
    w[7] = 1.0
    p1 = TokenPipeline(vocab=97, global_batch=4, seq_len=16, seed=3,
                       example_weights=w)
    b1 = next(p1)
    p1.stop()
    assert "doc_ids" in b1 and np.all(b1["doc_ids"] == 7)
    # same doc → same synthesized row, and the stream is reproducible
    np.testing.assert_array_equal(b1["tokens"][0], b1["tokens"][1])
    p2 = TokenPipeline(vocab=97, global_batch=4, seq_len=16, seed=3,
                       example_weights=w)
    b2 = next(p2)
    p2.stop()
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])

    # non-degenerate weights: selection follows the distribution
    w2 = np.ones(50, np.float64)
    p3 = TokenPipeline(vocab=97, global_batch=32, seq_len=8, seed=3,
                       example_weights=w2)
    b3 = next(p3)
    p3.stop()
    assert len(np.unique(b3["doc_ids"])) > 1


# ------------------------------------------------------- transient dispatch

class _FlakySnapshotProvider:
    """Maintained-scorer stand-in whose MVCC snapshot fails the first
    ``fail_times`` dispatches (a transient tear), then heals."""

    def __init__(self, inner, fail_times=1):
        self._inner = inner
        self.fails_left = fail_times
        self.snapshot_calls = 0

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def snapshot(self, roots=()):
        self.snapshot_calls += 1
        if self.fails_left > 0:
            self.fails_left -= 1
            raise RuntimeError("transient snapshot tear (injected)")
        return self._inner.snapshot(roots=roots)


def _flaky_service(sch, trees, fail_times):
    from repro.incremental import MaintainedScorer

    ms = MaintainedScorer(compile_ensemble(sch, trees))
    ms.grouped_cached(sch.label_table)
    flaky = _FlakySnapshotProvider(ms, fail_times=fail_times)
    reg = ModelRegistry()
    reg.publish(flaky)
    svc = RelationalScoringService(reg, sch.label_table, max_batch=64,
                                   max_wait_ms=2.0, cache_size=64)
    return ms, flaky, svc


def test_service_retries_once_failing_dispatch(star, star_trees):
    """A once-failing version dispatch is re-driven after a jittered
    backoff: callers see scores, not the transient error."""
    sch, J, X, y = star
    ms, flaky, svc = _flaky_service(sch, star_trees[:2], fail_times=1)
    tot, cnt = ms.grouped_cached(sch.label_table)
    want = np.asarray(tot) / np.maximum(np.asarray(cnt), 1.0)

    async def run():
        await svc.start()
        got = await svc.score_many(range(12))
        await svc.stop()
        return got

    got = asyncio.run(run())
    np.testing.assert_allclose(np.asarray(got), want[:12], rtol=1e-5)
    assert flaky.fails_left == 0 and flaky.snapshot_calls >= 2
    assert svc.stats.retries >= 1
    assert svc.stats.errors == 0


def test_service_persistent_failure_still_errors(star, star_trees):
    """One retry, not infinite: a dispatch that keeps failing surfaces
    the error to its callers and counts in service.errors."""
    sch, J, X, y = star
    _, flaky, svc = _flaky_service(sch, star_trees[:2], fail_times=10_000)

    async def run():
        await svc.start()
        with pytest.raises(RuntimeError, match="transient snapshot tear"):
            await svc.score(0)
        await svc.stop()

    asyncio.run(run())
    assert svc.stats.errors >= 1
    assert svc.stats.retries >= 1          # it did try again first


def test_service_retry_disabled_fails_fast(star, star_trees):
    sch, J, X, y = star
    ms, flaky, _ = _flaky_service(sch, star_trees[:2], fail_times=1)
    reg = ModelRegistry()
    reg.publish(flaky)
    svc = RelationalScoringService(reg, sch.label_table, max_batch=64,
                                   max_wait_ms=2.0, retry_transient=False)

    async def run():
        await svc.start()
        with pytest.raises(RuntimeError, match="transient snapshot tear"):
            await svc.score(0)
        await svc.stop()

    asyncio.run(run())
    assert svc.stats.retries == 0
    assert svc.stats.errors >= 1
