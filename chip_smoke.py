"""Drive the relational main path once on a TPU and check every answer.

    python chip_smoke.py              # one chip: train → compile + bulk
                                      # score → serve → maintain → kernel
    python chip_smoke.py --chips 4    # only the data mesh over four chips
                                      # against device 0 alone

Data is a star schema made from ``--seed`` by
``relational.generators.star_schema``: a fact table of 2^20 rows (the
order of TPC-H SF1's 1.5M-row ``orders``) joined to two 65,536-row
dimension tables.  Training alone runs on a fact table of 2^17 rows
(``--n-fact-train``): the paper config's deepest level holds sketch
queries of shape (8 nodes, fact rows, 129) complex64, whose 129 lanes
pad to 256 on the chip, and the level program's footprint for a v5e
(a compile against a described chip) is 58.56 GiB at 2^20 fact rows,
26.86 GiB at 2^19, 20.79 GiB at 2^18 and 11.02 GiB at 2^17, against
15.75 GiB of HBM.  Phases, in order, each ending in
``block_until_ready``:

1. train: ``Booster(schema, configs.paper_rbrt.CONFIG).fit()`` on the
   2^17-row fact table; the trees it grows score the full-size schema;
2. compile + bulk score: ``compile_ensemble`` + ``score_grouped`` over
   the fact table against the materialized-join oracle
   (``materialize_join`` + ``predict_rows`` + ``np.bincount``);
3. serve: ``RelationalScoringService.score_many`` over Zipf row ids,
   each answer against the grouped score of its row;
4. maintain: a ``MaintainedScorer`` with a ``WalWriter`` attached applies
   ``delta_stream`` batches, each checked against ``recompute_oracle``;
5. kernel route: ``score_grouped`` with ``use_kernel=True`` against
   phase 2, and its compiled program must hold a ``tpu_custom_call``.

Any failure ends the run non-zero.  Without a TPU the script exits
non-zero before any phase, naming the platform it found.  Earlier lines
give per phase its sizes, compile and steady seconds and the largest
oracle error; the last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.launch._devices import configure_compile_cache  # noqa: E402  (stdlib only)

configure_compile_cache()       # before the first jax import

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import BoostConfig, Booster, materialize_join, predict_rows  # noqa: E402
from repro.configs import paper_rbrt  # noqa: E402
from repro.core.schema import Schema, Table  # noqa: E402
from repro.distributed import spmd  # noqa: E402
from repro.incremental import MaintainedScorer, WalWriter  # noqa: E402
from repro.incremental.wal import read_records, wal_path  # noqa: E402
from repro.launch.mesh import make_data_mesh  # noqa: E402
from repro.relational import generators  # noqa: E402
from repro.serving import (  # noqa: E402
    ModelRegistry, RelationalScoringService, compile_ensemble, score_grouped)

# tolerances of tests/test_boosting.py (the fact-grouped sketch SSR
# against the exact one) and tests/test_serving.py: grouped sums against
# the oracle, integer counts, and the kernel route against the dense route
SSR_TOL = dict(rtol=2e-3, atol=1e-2)
TOT_TOL = dict(rtol=1e-3, atol=1e-3)
CNT_TOL = dict(rtol=1e-5)
KERNEL_TOL = dict(rtol=1e-5, atol=1e-5)
SERVE_TOL = dict(rtol=1e-5)

class CompileClock:
    """Seconds this process has spent compiling, or loading compiled
    programs from the persistent cache, summed from JAX's backend-compile
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def _ready(tree):
    return jax.block_until_ready(tree)


class Phase:
    """Times one phase: wall seconds, and the part of them XLA spent
    compiling."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock

    def __enter__(self):
        self.c0, self.t0 = self.clock.seconds, time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.wall_s = time.perf_counter() - self.t0
        self.compile_s = self.clock.seconds - self.c0
        return False

    def report(self, **fields):
        parts = [f"phase={self.name}", f"wall_s={self.wall_s:.3f}",
                 f"compile_s={self.compile_s:.3f}"]
        parts += [f"{k}={v}" for k, v in fields.items()]
        print(" ".join(parts), flush=True)


def _check(ok: bool, what: str) -> None:
    """A failed check ends the run, with or without ``python -O``."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _max_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want))) if got.size else 0.0


def _require_tpu(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found platform "
                 f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX found {len(devs)}")
    return devs[0]


def _schema(args, n_fact: int) -> Schema:
    return generators.star_schema(seed=args.seed, n_fact=n_fact,
                                  n_dim=args.n_dim)


def _sizes(schema: Schema) -> str:
    return " ".join(f"{t.name}={t.n_rows}" for t in schema.tables)


def _join_features(schema: Schema):
    J = materialize_join(schema)
    return J, jnp.stack([jnp.asarray(J[c]) for (_, c) in schema.features],
                        axis=1)


def _train_errors(schema: Schema, cfg, trees, trace) -> list:
    """Relative errors of the fact-grouped SSR the trainer reports.

    A fact row joins exactly one tuple, so grouped by the fact table the
    sketch is exact, and the level's nodes partition the join (dead nodes
    route every row left): at every level of tree t the nodes' SSRs must
    sum to Σ_x (y_x − F_{t−1}(x))² over the materialized join, at the
    tolerance of tests/test_boosting.py::test_fact_grouping_ssr_exact."""
    J, X = _join_features(schema)
    y = np.asarray(J[schema.label_column], np.float64)
    pred = np.zeros_like(y)
    errs = []
    for t, tree in enumerate(trees):
        want = float(np.sum(np.square(y - pred)))
        for level in range(cfg.depth):
            got = float(np.sum(np.asarray(
                trace.node_ssr[t * cfg.depth + level]["fact"], np.float64)))
            np.testing.assert_allclose(got, want, **SSR_TOL)
            errs.append(abs(got - want) / want)
        pred += np.asarray(predict_rows([tree], X), np.float64)
    return errs


def _oracle(schema: Schema, trees, group: str):
    """Materialized-join oracle: per-row (Σŷ, count) of ``group``."""
    J, X = _join_features(schema)
    preds = np.asarray(predict_rows(trees, X), np.float64)
    rows = np.asarray(J["__rows__" + group])
    n = schema.table(group).n_rows
    return (np.bincount(rows, weights=preds, minlength=n),
            np.bincount(rows, minlength=n))


# ------------------------------------------------------------ one chip --

def run_one_chip(args, clock: CompileClock) -> None:
    train_schema = _schema(args, args.n_fact_train)
    schema = _schema(args, args.n_fact)
    group = schema.label_table
    print(f"data: star schema seed={args.seed} train: {_sizes(train_schema)}"
          f" score/serve/maintain: {_sizes(schema)}", flush=True)

    cfg = paper_rbrt.CONFIG
    with Phase("train", clock) as ph:
        booster = Booster(train_schema, cfg)
        trees, trace = booster.fit()
        _ready([(t.feat, t.thr, t.leaf) for t in trees])
    errs = _train_errors(train_schema, cfg, trees, trace)
    ph.report(rows=train_schema.table(group).n_rows, trees=cfg.n_trees,
              depth=cfg.depth, sketch_k=cfg.sketch_k, ssr_mode=cfg.ssr_mode,
              queries=trace.queries,
              wall_minus_compile_s=f"{ph.wall_s - ph.compile_s:.3f}",
              max_ssr_rel_err=max(errs))

    want_tot, want_cnt = _oracle(schema, trees, group)
    with Phase("score", clock) as ph:
        ens = compile_ensemble(schema, trees)
        tot, cnt = _ready(score_grouped(ens, group))
    t0 = time.perf_counter()
    _ready(ens.score_grouped(group))
    steady = time.perf_counter() - t0
    np.testing.assert_allclose(np.asarray(tot), want_tot, **TOT_TOL)
    np.testing.assert_allclose(np.asarray(cnt), want_cnt, **CNT_TOL)
    ph.report(rows=schema.table(group).n_rows, leaves=ens.total_leaves,
              steady_s=f"{steady:.4f}",
              max_err=_max_err(tot, want_tot),
              max_cnt_err=_max_err(cnt, want_cnt))

    want_mean = np.asarray(tot) / np.maximum(np.asarray(cnt), 1.0)
    rng = np.random.default_rng(args.seed)
    ids = np.minimum(rng.zipf(1.3, args.requests) - 1,
                     schema.table(group).n_rows - 1)
    registry = ModelRegistry()
    registry.publish(ens)
    service = RelationalScoringService(registry, group, max_batch=64,
                                       max_wait_ms=1.0)

    async def drive():
        await service.start()
        out = []
        for chunk in np.array_split(ids, max(1, len(ids) // 64)):
            out += await service.score_many(chunk.tolist())
        await service.stop()
        return np.asarray(out)

    with Phase("serve", clock) as ph:
        got = asyncio.run(drive())
    np.testing.assert_allclose(got, want_mean[ids], **SERVE_TOL)
    snap = service.stats_snapshot()
    ph.report(requests=len(ids), batches=snap["batches"],
              cache_hit_rate=f"{snap['cache_hit_rate']:.3f}",
              p50_ms=f"{snap['latency_ms']['p50']:.3f}",
              p99_ms=f"{snap['latency_ms']['p99']:.3f}",
              max_err=_max_err(got, want_mean[ids]))

    with Phase("maintain", clock) as ph, tempfile.TemporaryDirectory() as wal_dir:
        ms = MaintainedScorer(ens)
        wal = WalWriter(wal_dir).attach(ms.state)
        _ready(ms.grouped_cached(group))
        errs, deltas = [], 0
        for batch in generators.delta_stream(
                schema, ms.live_rows, seed=args.seed,
                n_batches=args.delta_batches, ops_per_batch=32):
            ms.apply(batch)
            deltas += len(batch)
            m_tot, m_cnt = _ready(ms.grouped_cached(group))
            o_tot, o_cnt = _ready(ms.recompute_oracle(group))
            np.testing.assert_array_equal(np.asarray(m_tot), np.asarray(o_tot))
            np.testing.assert_array_equal(np.asarray(m_cnt), np.asarray(o_cnt))
            errs.append(_max_err(m_tot, o_tot))
        wal.close()
        lsns = [r[0] for r in read_records(wal_path(wal_dir))]
        _check(lsns == list(range(1, ms.data_version + 1)),
               f"WAL LSNs {lsns} for data_version {ms.data_version}")
    ph.report(batches=len(errs), table_deltas=deltas,
              data_version=ms.data_version, wal_records=len(lsns),
              max_err=max(errs))

    with Phase("kernel", clock) as ph:
        ens_k = compile_ensemble(schema, trees, use_kernel=True)
        k_tot, k_cnt = _ready(score_grouped(ens_k, group))
    t0 = time.perf_counter()
    _ready(ens_k.score_grouped(group))
    steady = time.perf_counter() - t0
    np.testing.assert_allclose(np.asarray(k_tot), np.asarray(tot), **KERNEL_TOL)
    np.testing.assert_allclose(np.asarray(k_cnt), np.asarray(cnt), **KERNEL_TOL)
    hlo = ens_k._score_fn(group).lower(ens_k.factors, ens_k.leaf_values) \
        .compile().as_text()
    _check("tpu_custom_call" in hlo, "kernel route compiled without the kernel")
    ph.report(steady_s=f"{steady:.4f}", tpu_custom_call=True,
              max_err=_max_err(k_tot, tot))


# ---------------------------------------------------------- four chips --

def _dyadic_labels(schema: Schema) -> Schema:
    """Labels snapped to multiples of 1/16, so every label sum the
    trainer reduces is exact in f32 in any order: the sharded and the
    single-device fit must then agree bit for bit (tests/test_sharded.py)."""
    lt, lc = schema.label_table, schema.label_column
    tables = []
    for t in schema.tables:
        cols = dict(t.columns)
        if t.name == lt:
            cols[lc] = np.round(np.asarray(cols[lc]) * 16.0) / 16.0
        tables.append(Table(t.name, cols, feature_columns=t.feature_columns))
    return Schema(tables, label=(lt, lc))


def run_mesh(args, n: int, clock: CompileClock) -> None:
    schema = _dyadic_labels(_schema(args, args.n_fact))
    group = schema.label_table
    cfg = BoostConfig(n_trees=2, depth=2, mode="exact", ssr_mode="per_table")
    mesh = make_data_mesh(n)
    out = {}
    for name, m in (("device0", None), (f"mesh{n}", mesh)):
        with Phase(f"fit_{name}", clock) as ph, spmd.use_data_mesh(m):
            booster = Booster(schema, cfg)
            trees, _ = booster.fit()
            _ready([(t.feat, t.thr, t.leaf) for t in trees])
        ph.report(trees=cfg.n_trees, depth=cfg.depth, mode=cfg.mode)
        with Phase(f"score_{name}", clock) as ph:
            with spmd.use_data_mesh(m):
                ens = compile_ensemble(schema, trees)
            res = _ready(score_grouped(ens, group))
        t0 = time.perf_counter()
        _ready(ens.score_grouped(group))
        ph.report(rows=schema.table(group).n_rows,
                  steady_s=f"{time.perf_counter() - t0:.4f}")
        out[name] = (trees, res, ens, booster.counter.edges)

    (t1, (tot1, cnt1), _, e1), (tN, (totN, cntN), ensN, eN) = out.values()
    same_trees = len(t1) == len(tN) and all(
        np.array_equal(a.feat, b.feat) and np.array_equal(a.thr, b.thr)
        and np.array_equal(a.leaf, b.leaf) for a, b in zip(t1, tN))
    _check(same_trees, "mesh fit grew different trees")
    np.testing.assert_array_equal(np.asarray(totN), np.asarray(tot1))
    np.testing.assert_array_equal(np.asarray(cntN), np.asarray(cnt1))
    _check(e1 == eN, f"edge counts {e1} (device 0) vs {eN} (mesh)")
    fact = ensN.factors["fact"]
    _check(spmd.is_row_sharded(fact, mesh), "fact factor is not row-sharded")
    shard_devs = {s.device for s in fact.addressable_shards}
    _check(len(shard_devs) == n, f"fact factor on {len(shard_devs)} device(s)")
    print(f"mesh: trees bit-equal, scores bit-equal, edges {eN}, fact factor "
          f"row-sharded over {len(shard_devs)} devices "
          f"(shard rows {fact.addressable_shards[0].data.shape[0]})",
          flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-fact", type=int, default=1 << 20)
    ap.add_argument("--n-fact-train", type=int, default=1 << 17)
    ap.add_argument("--n-dim", type=int, default=1 << 16)
    ap.add_argument("--requests", type=int, default=512)
    ap.add_argument("--delta-batches", type=int, default=3)
    args = ap.parse_args(argv)

    dev = _require_tpu(args.chips)
    clock = CompileClock()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())} "
          f"jax {jax.__version__} cache "
          f"{os.environ['JAX_COMPILATION_CACHE_DIR']}", flush=True)
    if args.chips == 1:
        run_one_chip(args, clock)
    else:
        run_mesh(args, args.chips, clock)
    stats = dev.memory_stats() or {}
    print(f"device_kind={dev.device_kind} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
