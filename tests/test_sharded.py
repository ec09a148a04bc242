"""Sharded-vs-single-device bit-equality properties.

The mesh-sharded SumProd must be a pure *placement* change: scores,
trees, and delta-refreshed results bit-equal to the single-device run,
and the host-side query/edge accounting untouched.  The compiled
factors carry integer-valued counts, and the training properties pin
labels to a dyadic grid (multiples of 1/16), so every cross-shard ⊕
re-association is exact in f32 — bit-equality is the spec here, not a
tolerance.

Single-device identity properties always run (tier-1).  The
multi-device properties need forced host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m pytest tests/test_sharded.py
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.schema as S
from repro.core import BoostConfig, Booster, QueryCounter
from repro.distributed import spmd
from repro.incremental import MaintainedScorer
from repro.incremental.retrain import IncrementalBooster
from repro.launch.mesh import make_data_mesh
from repro.relational import generators
from repro.serving import compile_ensemble
from repro.serving.scorer import score_grouped

multidevice = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs forced host devices: "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8",
)


def _quantize_labels(sch):
    """Snap labels to multiples of 1/16 so the label/label² sums the
    trainer reduces are exactly representable — cross-shard ⊕ becomes
    associative in f32 and bit-equality is well-defined."""
    lt, lc = sch.label_table, sch.label_column
    tabs = []
    for t in sch.tables:
        cols = dict(t.columns)
        if t.name == lt:
            cols[lc] = np.round(np.asarray(cols[lc]) * 16.0) / 16.0
        tabs.append(S.Table(t.name, cols))
    return S.Schema(tabs, label=(lt, lc))


def _quantize_delta(sch, batch):
    """Same 1/16 grid for labels arriving THROUGH the delta stream —
    an inserted/updated row with an arbitrary float label would break
    the dyadic exactness the bit-equality property rests on."""
    from repro.incremental import TableDelta
    lt, lc = sch.label_table, sch.label_column
    out = []
    for d in batch:
        if d.table != lt:
            out.append(d)
            continue
        ins, upd = d.inserts, d.updates
        if ins and lc in ins:
            ins = dict(ins)
            ins[lc] = np.round(np.asarray(ins[lc]) * 16.0) / 16.0
        if upd and lc in upd[1]:
            cols = dict(upd[1])
            cols[lc] = np.round(np.asarray(cols[lc]) * 16.0) / 16.0
            upd = (upd[0], cols)
        out.append(TableDelta(d.table, inserts=ins, deletes=d.deletes,
                              updates=upd))
    return out


def _schema(kind):
    if kind == "star":          # n_fact % 8 == 0 → factors really shard
        return generators.star_schema(seed=3, n_fact=512, n_dim=24)
    if kind == "chain":
        return generators.chain_schema(seed=9, n_rows=256)
    return generators.snowflake_schema(seed=7, n_fact=256, n_dim=16)


def _trees_equal(ts1, ts2):
    return len(ts1) == len(ts2) and all(
        jnp.array_equal(a.feat, b.feat) and jnp.array_equal(a.thr, b.thr)
        and jnp.array_equal(a.leaf, b.leaf)
        for a, b in zip(ts1, ts2))


# ---------------------------------------------------------------- identity

def test_no_mesh_helpers_are_identity():
    x = jnp.arange(24.0).reshape(8, 3)
    assert spmd.current_data_mesh() is None
    assert spmd.data_axis_size() == 1
    assert spmd.mesh_fingerprint() is None
    assert spmd.shard_rows(x) is x
    assert spmd.psum_message(x) is x
    assert spmd.replicate(x) is x
    assert spmd.constrain_rows(x) is x


def test_mesh_of_one_resolves_to_no_mesh():
    mesh = make_data_mesh(1)
    with spmd.use_data_mesh(mesh):
        assert spmd.data_axis_size() == 1
        x = jnp.ones((8, 2))
        assert spmd.shard_rows(x) is x


def test_single_device_scoring_unchanged_under_mesh_context():
    sch = _schema("star")
    cfg = BoostConfig(n_trees=2, depth=2, mode="sketch", ssr_mode="off")
    trees, _ = Booster(sch, cfg).fit()
    t1, n1 = score_grouped(compile_ensemble(sch, trees), sch.label_table)
    with spmd.use_data_mesh(make_data_mesh(1)):
        ens = compile_ensemble(sch, trees)
    t2, n2 = score_grouped(ens, sch.label_table)
    assert jnp.array_equal(t1, t2) and jnp.array_equal(n1, n2)


def test_mesh_of_one_shares_the_single_device_scorer():
    """A size-1 mesh is the single-device program: an ensemble compiled
    under it takes the scorer built without a mesh, builds none."""
    from _trees import random_trees
    from repro.obs import get_registry

    programs = get_registry().counter("serve.scorer_programs")
    sch = generators.star_schema(seed=4, n_fact=56, n_dim=12)  # shapes of this test only
    trees = random_trees(sch, (2, 2), seed=1)
    ens1 = compile_ensemble(sch, trees)
    t1, n1 = score_grouped(ens1, "fact")
    n0 = programs.value
    with spmd.use_data_mesh(make_data_mesh(1)):
        ens = compile_ensemble(sch, trees)
    t2, n2 = score_grouped(ens, "fact")
    assert programs.value == n0
    assert ens._score_fn("fact") is ens1._score_fn("fact")
    assert jnp.array_equal(t1, t2) and jnp.array_equal(n1, n2)


# ------------------------------------------------------------ multi-device

@multidevice
@pytest.mark.parametrize("kind", ["star", "chain", "snowflake"])
def test_sharded_grouped_scores_bit_equal(kind):
    sch = _schema(kind)
    group = sch.label_table
    cfg = BoostConfig(n_trees=3, depth=3, mode="sketch", ssr_mode="off",
                      seed=0)
    trees, _ = Booster(sch, cfg).fit()

    c1 = QueryCounter()
    t1, n1 = score_grouped(compile_ensemble(sch, trees, counter=c1), group)

    mesh = make_data_mesh()
    cN = QueryCounter()
    with spmd.use_data_mesh(mesh):
        ensN = compile_ensemble(sch, trees, counter=cN)
    if kind == "star":          # 512 % 8 == 0: placement must be real
        assert spmd.is_row_sharded(ensN.factors["fact"], mesh)
    tN, nN = score_grouped(ensN, group)

    assert jnp.array_equal(t1, tN) and jnp.array_equal(n1, nN)
    assert c1.edges == cN.edges and c1.count == cN.count


@multidevice
@pytest.mark.parametrize("kind", ["star", "chain", "snowflake"])
def test_mesh_and_single_device_scorers_are_separate_programs(kind):
    """The scorer program is shared by structure, and the data mesh is
    part of it: a mesh-compiled and a single-device ensemble over the
    same shapes build one program each, and score the same bits."""
    from _trees import random_trees
    from repro.obs import get_registry

    programs = get_registry().counter("serve.scorer_programs")
    sch = _schema(kind)
    group = sch.label_table
    trees = random_trees(sch, (3, 2), seed=11)
    ens1 = compile_ensemble(sch, trees)
    t1, n1 = score_grouped(ens1, group)
    n0 = programs.value
    with spmd.use_data_mesh(make_data_mesh()):
        ensN = compile_ensemble(sch, trees)
    tN, nN = score_grouped(ensN, group)
    assert programs.value == n0 + 1
    assert ensN._score_fn(group) is not ens1._score_fn(group)
    assert jnp.array_equal(t1, tN) and jnp.array_equal(n1, nN)


@multidevice
@pytest.mark.parametrize("kind", ["star", "chain", "snowflake"])
def test_sharded_training_trees_bit_equal(kind):
    sch = _quantize_labels(_schema(kind))
    cfg = BoostConfig(n_trees=3, depth=3, mode="exact", ssr_mode="per_table",
                      seed=0)

    b1 = Booster(sch, cfg)
    trees1, _ = b1.fit()

    with spmd.use_data_mesh(make_data_mesh()):
        bN = Booster(sch, cfg)
        treesN, _ = bN.fit()

    assert _trees_equal(trees1, treesN)
    assert b1.counter.edges == bN.counter.edges


@multidevice
@pytest.mark.parametrize("kind", ["star", "snowflake"])
def test_sharded_delta_refresh_bit_equal(kind):
    """Insert/delete/update stream through MaintainedScorer: the
    path-restricted refresh must stay bit-equal shard-by-shard."""
    sch = _quantize_labels(_schema(kind))
    group = sch.label_table
    cfg = BoostConfig(n_trees=3, depth=3, mode="sketch", ssr_mode="off",
                      seed=0)
    trees, _ = Booster(sch, cfg).fit()

    def run(mesh):
        with spmd.use_data_mesh(mesh):
            c = QueryCounter()
            ms = MaintainedScorer(compile_ensemble(sch, trees), counter=c)
        outs = [ms.grouped_cached(group)]
        # regenerated per run: both scorers' live-row states evolve
        # identically, so the same seed yields the same stream
        for batch in generators.delta_stream(sch, ms.live_rows, seed=4,
                                             n_batches=6, ops_per_batch=8):
            ms.apply(batch)
            outs.append(ms.grouped_cached(group))
        return outs, c.edges

    o1, e1 = run(None)
    oN, eN = run(make_data_mesh())
    for (t1, n1), (tN, nN) in zip(o1, oN):
        assert jnp.array_equal(t1, tN) and jnp.array_equal(n1, nN)
    assert e1 == eN


@multidevice
@pytest.mark.parametrize("kind", ["star", "snowflake"])
def test_sharded_snapshot_reads_bit_equal(kind):
    """MVCC snapshots under a data mesh: reads served from a pinned
    ``Snapshot`` (lazy path-restricted refresh + write-back) must be
    bit-equal to the single-device run at every data_version, and each
    must match its own pinned single-device recompute oracle — snapshot
    isolation is a concurrency feature, not a numerics fork."""
    sch = _quantize_labels(_schema(kind))
    group = sch.label_table
    cfg = BoostConfig(n_trees=3, depth=3, mode="sketch", ssr_mode="off",
                      seed=0)
    trees, _ = Booster(sch, cfg).fit()

    def run(mesh):
        with spmd.use_data_mesh(mesh):
            ms = MaintainedScorer(compile_ensemble(sch, trees))
        outs = []
        snap = ms.snapshot(roots=(group,), pin_oracle=True)
        outs.append((snap.score_grouped(group), snap.recompute_oracle(group)))
        for batch in generators.delta_stream(sch, ms.live_rows, seed=4,
                                             n_batches=4, ops_per_batch=8):
            ms.apply(batch)
            snap = ms.snapshot(roots=(group,), pin_oracle=True)
            outs.append((snap.score_grouped(group),
                         snap.recompute_oracle(group)))
        return outs

    o1 = run(None)
    oN = run(make_data_mesh())
    for ((t1, n1), (ot1, on1)), ((tN, nN), (otN, onN)) in zip(o1, oN):
        assert jnp.array_equal(t1, tN) and jnp.array_equal(n1, nN)
        # the oracle is pinned single-device inside _oracle_from, so it
        # must agree across runs AND with the snapshot reads themselves
        assert jnp.array_equal(ot1, otN) and jnp.array_equal(on1, onN)
        assert jnp.array_equal(t1, ot1) and jnp.array_equal(n1, on1)


@multidevice
def test_sharded_warm_start_refit_bit_equal():
    sch = _quantize_labels(_schema("star"))
    cfg = BoostConfig(n_trees=3, depth=3, mode="sketch", ssr_mode="off",
                      seed=0)

    def run(mesh):
        with spmd.use_data_mesh(mesh):
            ib = IncrementalBooster(sch, cfg)
        ib.fit()
        for batch in generators.delta_stream(sch, ib.live_rows, seed=11,
                                             n_batches=3, ops_per_batch=6):
            ib.refit(deltas=_quantize_delta(sch, batch), n_new_trees=1,
                     drift_threshold=-1.0)
        return ib.trees, ib.counter.edges

    t1, e1 = run(None)
    tN, eN = run(make_data_mesh())
    assert _trees_equal(t1, tN)
    assert e1 == eN
