"""Relational greedy boosted regression trees (paper Algorithms 1–3).

Faithful structure:
- Trees grow level-by-level in BFS order (paper §2.1); each level's split
  statistics come from SumProd queries *grouped by* every table T_i,
  vmapped over the level's nodes (TPU adaptation of the paper's
  query-per-node loop).
- Node statistics (n, Σy, Σy²) fuse into one Channels(3) query.
- Boosted residuals (paper §2.2):
    Σ r_x       — exact, O(mL) count queries per (node, table),
    Σ r_x²      — EXACT mode: O(m²L²) pair queries per (node, table)
                  (the paper's bottleneck, Thm 2.4),
                  SKETCH mode: O(mL) polynomial-semiring queries
                  (paper §3, Thm 3.1) with ‖·‖² via Parseval.
- Split ranking uses the paper's final MSE form; after dropping
  node-constant terms the ranking reduces to argmax(S_L²/n_L + S_R²/n_R)
  over *exact* sums — so exact and sketched training provably select
  identical splits, matching (strengthening) the paper's "similar model
  parameters" claim.  The SSR values (what the sketch accelerates) are the
  per-node losses used for reporting/stopping; tests validate their
  (1±ε) accuracy per grouping table (Thm 3.4).

Paper errata implemented correctly (see DESIGN.md §3):
- Eq.(2) label-cross term uses per-leaf label sums (the text's
  "product of sums" shortcut is not an identity);
- the final MSE line is the weighted (SSE/n_v) form.

Performance: each tree level is one jitted program (masks in, split
decision out); shapes are keyed by (level, #prev-leaves) so compiled
steps are reused across trees and runs.  SumProd query counts are
accounted *analytically* (the jit caches would otherwise undercount).

Query execution is delegated to an injectable :class:`QueryEngine`
(engine.py): the default :class:`DirectEngine` runs one vmapped SumProd
pass per query family (the paper's model, jitted); the maintained
engine (incremental/retrain.py) answers the same queries from cached
per-edge messages kept fresh under table deltas, running the level loop
eagerly so message signatures can hash concrete masks.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..obs import fence, get_registry, scope, span, tracing_enabled
from .engine import DirectEngine, QueryEngine
from .hist import build_hist_plans, refresh_hist_plans
from .schema import Schema
from .semiring import Channels, PolyCoeff, PolyFreq
from .sketch import TableHashes
from .splits import SplitResult, best_split_for_table, build_split_plans, merge_table_results
from .sumprod import QueryCounter, SumProd
from .tree import TreeArrays, descend_masks_level, leaf_masks, root_masks


@dataclasses.dataclass(frozen=True)
class BoostConfig:
    n_trees: int = 5
    depth: int = 3
    lr: float = 1.0                  # shrinkage (paper: 1.0)
    mode: str = "exact"              # "exact" (Alg 2) | "sketch" (Alg 3)
    sketch_k: int = 64               # k = O((2+3^τ)/(ε²δ)), power of two
    sketch_domain: str = "freq"      # "freq" (beyond-paper) | "coeff" (faithful FFT)
    min_gain: float = 1e-7
    ssr_mode: str = "per_table"      # "per_table" (faithful) | "once" | "off"
    split_mode: str = "exact"        # "exact" (paper) | "hist" (quantile bins)
    hist_bins: int = 256             # B: quantile bins per feature (hist mode)
    hist_edge_tol: float = 0.25      # re-quantize a table's bin edges once this
    #                                  fraction of its rows re-binned (0 = always)
    hist_route: str = "auto"         # histogram accumulation: "auto" |
    #                                  "gather" | "scatter" | "kernel" (Pallas)
    seed: int = 0


def _jit_hoisting_consts(fn):
    """``jax.jit(fn)``, except that every array ``fn`` closes over
    reaches the compiled program as an argument instead of being baked
    into it as an HLO constant.  The level step closes over the engine's
    base factors (a 2^20-row sketch factor alone is 1 GB) and one program
    compiles per (level, #prev-leaves): embedded, each program would
    hold its own copy of every factor in device memory.

    Each program is named ``level_step`` and built ahead of time in a
    ``boost.level_build`` scope; the gauge ``train.level_program_bytes``
    holds the largest device footprint (temporaries, arguments and
    outputs less aliases, from ``memory_analysis``) of those built."""
    cache = {}
    largest = 0

    def call(*args):
        nonlocal largest
        flat, tree = jax.tree.flatten(args)
        key = (tree, tuple((a.shape, a.dtype, getattr(a, "sharding", None))
                           for a in flat))
        if key not in cache:
            with scope("boost.level_build"):
                closed, out = jax.make_jaxpr(fn, return_shape=True)(*args)

                def level_step(consts, *flat):
                    return jax.core.eval_jaxpr(closed.jaxpr, consts, *flat)

                run = jax.jit(level_step).lower(closed.consts, *flat).compile()
            mem = run.memory_analysis()
            if mem is not None:
                largest = max(largest, mem.temp_size_in_bytes
                              + mem.argument_size_in_bytes
                              + mem.output_size_in_bytes - mem.alias_size_in_bytes)
                get_registry().gauge("train.level_program_bytes").set(largest)
            cache[key] = (run, closed.consts, jax.tree.structure(out))
        run, consts, out_tree = cache[key]
        return jax.tree.unflatten(out_tree, run(consts, *flat))

    return call


@dataclasses.dataclass
class FitTrace:
    """Everything tests/benchmarks need to validate the paper's claims."""

    queries: int = 0
    node_ssr: List[Dict[str, jnp.ndarray]] = dataclasses.field(default_factory=list)
    node_counts: List[jnp.ndarray] = dataclasses.field(default_factory=list)


class Booster:
    """Trains boosted regression trees directly on a relational schema."""

    def __init__(self, schema: Schema, cfg: BoostConfig,
                 key: Optional[jax.Array] = None,
                 engine: Optional[QueryEngine] = None):
        self.schema = schema
        self.cfg = cfg
        self.counter = QueryCounter()
        self.sp = SumProd(schema)            # counting done analytically below
        key = key if key is not None else jax.random.PRNGKey(cfg.seed)
        self.hashes = TableHashes.make(key, schema, cfg.sketch_k)
        self.sem = (
            PolyFreq(cfg.sketch_k) if cfg.sketch_domain == "freq" else PolyCoeff(cfg.sketch_k)
        )
        self.c3 = Channels(3)
        if cfg.split_mode not in ("exact", "hist"):
            raise ValueError(f"split_mode {cfg.split_mode!r}")
        if cfg.hist_route not in ("auto", "gather", "scatter", "kernel"):
            raise ValueError(f"hist_route {cfg.hist_route!r}")
        self.engine = engine if engine is not None else DirectEngine()
        self.engine.bind(self)
        self.plans = self._build_plans()
        if self.engine.jittable:
            self._level_step = _jit_hoisting_consts(self._level_step_impl)
            self._leaf_masks = jax.jit(self._leaf_masks_impl)
        else:                                # host-side caching engines hash
            self._level_step = self._level_step_impl   # concrete mask bytes
            self._leaf_masks = self._leaf_masks_impl

    def _build_plans(self):
        featmats = self.engine.plan_featmats()
        if self.cfg.split_mode == "hist":
            return build_hist_plans(self.schema, featmats=featmats,
                                    n_bins=self.cfg.hist_bins,
                                    route=self.cfg.hist_route)
        return build_split_plans(self.schema, featmats=featmats)

    def refresh_plans(self):
        """Refresh split plans against the engine's current feature
        matrices (maintained engines call this per delta-epoch).  Exact
        mode rebuilds every table's argsort order wholesale; hist mode
        consumes the engine's ``plan_delta`` and re-bins only
        delta-touched rows against frozen quantile edges (re-quantizing
        a table's edges only past ``cfg.hist_edge_tol`` drift) —
        O(|delta|) plan maintenance instead of O(n log n)."""
        t0 = time.perf_counter()
        dirty = self.engine.plan_delta()   # always consumed: a full rebuild
        #                                    below covers anything accumulated
        if self.cfg.split_mode == "hist" and dirty is not None:
            with span("plan.refresh", mode="hist",
                      tables=len(dirty), rows=sum(len(s) for s, _ in dirty.values())):
                self.plans = refresh_hist_plans(
                    self.plans, dirty,
                    n_rows_fn=self.engine.n_rows,
                    featmat_fn=self.engine.plan_featmat,
                    n_bins=self.cfg.hist_bins,
                    edge_tol=self.cfg.hist_edge_tol,
                )
        else:
            with span("plan.refresh", mode=self.cfg.split_mode, full_rebuild=True):
                self.plans = self._build_plans()
        get_registry().histogram("train.plan_refresh_ms").observe(
            (time.perf_counter() - t0) * 1e3)

    # ------------------------------------------------------------- queries --
    def _grouped_c3(self, table, masks, extra=None):
        """(K, n_t, 3): (count, Σy, Σy²) grouped by `table`, batched over
        nodes.  `extra`: optional conjunctive per-table masks (prev-tree
        leaf).  Delegates to the injected engine."""
        return self.engine.grouped_c3(table, masks, extra)

    def _grouped_count_pair(self, table, masks, extra_a, extra_b):
        return self.engine.grouped_count_pair(table, masks, extra_a, extra_b)

    def _grouped_sketch(self, table, masks, extra=None, labeled=False):
        return self.engine.grouped_sketch(table, masks, extra, labeled)

    def _loop(self, n, body, init):
        """fori_loop under jit; a plain Python loop for eager engines
        (lax.fori_loop would trace the body, defeating host-side mask
        hashing and concrete indexing)."""
        if self.engine.jittable:
            return jax.lax.fori_loop(0, n, body, init)
        acc = init
        for i in range(n):
            acc = body(i, acc)
        return acc

    # ------------------------------------------------------ residual stats --
    def _table_stats(self, table, masks, prev_masks, prev_vals, want_ssr: bool):
        """(n, sum_r, node_ssr) per (node, row-of-table) at one tree level."""
        base = self._grouped_c3(table, masks)          # (K, n_t, 3)
        n, sy, uy = base[..., 0], base[..., 1], base[..., 2]
        M = prev_vals.shape[0]
        if M == 0:
            return n, sy, (jnp.sum(uy, axis=1) if want_ssr else None)

        def leaf_body(a, acc):
            sum_r, cross = acc
            extra = {tn: prev_masks[tn][a] for tn in prev_masks}
            st = self._grouped_c3(table, masks, extra=extra)
            d = prev_vals[a]
            return (sum_r - d * st[..., 0], cross + d * st[..., 1])

        sum_r, cross = self._loop(M, leaf_body, (sy, jnp.zeros_like(sy)))
        if not want_ssr:
            return n, sum_r, None

        if self.cfg.mode == "exact":
            # pair term Σ_{a,b} d_a d_b |J^{(a)} ∩ J^{(b)} ∩ J^{(v)} ∩ ρ⋈·|
            def pair_body(i, acc):
                a, b = i // M, i % M
                ea = {tn: prev_masks[tn][a] for tn in prev_masks}
                eb = {tn: prev_masks[tn][b] for tn in prev_masks}
                cnt = self._grouped_count_pair(table, masks, ea, eb)
                return acc + prev_vals[a] * prev_vals[b] * cnt

            pair = self._loop(M * M, pair_body, jnp.zeros_like(sy))
            ssr_rho = uy - 2.0 * cross + pair
        elif self.cfg.mode == "sketch":
            def sk_body(a, acc):
                extra = {tn: prev_masks[tn][a] for tn in prev_masks}
                s = self._grouped_sketch(table, masks, extra=extra)
                return acc - self.sem.scale(s, jnp.zeros(()) + prev_vals[a])

            with scope("boost.sketch", table=table):
                resid = self._grouped_sketch(table, masks, labeled=True)  # (K,n_t,kc)
                resid = self._loop(M, sk_body, resid)
                ssr_rho = self.sem.norm_sq(resid)
        else:
            raise ValueError(self.cfg.mode)
        return n, sum_r, jnp.sum(ssr_rho, axis=1)

    # --------------------------------------------------------- level step --
    def _level_step_impl(self, masks, prev_masks, prev_vals, node_mean):
        """One BFS level: queries → split choice → mask descent.  Jitted;
        shape signature (K, M) keys the compile cache."""
        cfg = self.cfg
        results, ssr_out = [], {}
        node_n = None
        for i, tn in enumerate(self.plans):
            want_ssr = cfg.ssr_mode == "per_table" or (cfg.ssr_mode == "once" and i == 0)
            with scope("boost.stats", table=tn):
                n, s, ssr = self._table_stats(tn, masks, prev_masks, prev_vals, want_ssr)
            if i == 0:
                node_n = jnp.sum(n, axis=1)
            if ssr is not None:
                ssr_out[tn] = ssr
            with scope("boost.sweep", table=tn, mode=cfg.split_mode):
                results.append(fence(best_split_for_table(self.plans[tn], n, s)))
        best: SplitResult = merge_table_results(results)

        valid = jnp.isfinite(best.score) & (best.score > cfg.min_gain)
        feat = jnp.where(valid, best.feature, -1).astype(jnp.int32)
        thr = jnp.where(valid, best.threshold, jnp.inf).astype(jnp.float32)
        lm = jnp.where(valid, best.left_sum / jnp.maximum(best.left_cnt, 1e-9), node_mean)
        rm = jnp.where(valid, best.right_sum / jnp.maximum(best.right_cnt, 1e-9), node_mean)
        new_mean = jnp.stack([lm, rm], axis=1).reshape(-1)
        with scope("boost.descend"):
            new_masks = {
                tn: descend_masks_level(self.schema, tn, feat, thr, masks[tn],
                                        featmat=self.engine.mask_featmat(tn))
                for tn in masks
            }
        return feat, thr, new_mean, new_masks, ssr_out, node_n

    def _leaf_masks_impl(self, tree: TreeArrays):
        return {
            t.name: leaf_masks(self.schema, t.name, tree,
                               featmat=self.engine.mask_featmat(t.name))
            for t in self.schema.tables
        }

    # -------------------------------------------------- query accounting --
    def _count_level_queries(self, M: int) -> int:
        """Analytic SumProd counts per level (validates Thms 2.4/3.1)."""
        tau = len(self.plans)
        per_table = 1 + M                                  # c3 + per-leaf stats
        if self.cfg.ssr_mode != "off":
            if self.cfg.mode == "exact":
                per_table += M * M                         # leaf-pair counts
            else:
                per_table += 1 + M                         # Y' + per-leaf sketches
        return per_table * tau

    def _count_level_edges(self, M: int) -> int:
        """Analytic segment-⊕ emissions per level for the direct engine:
        every query family re-emits each join-tree edge (τ_all − 1 for an
        acyclic schema, any root) — the per-query baseline the maintained
        engine's real emission counts are benchmarked against."""
        return self._count_level_queries(M) * max(self.schema.n_tables - 1, 0)

    # -------------------------------------------------------------- fitting --
    def _fit_tree(self, prev_trees: List[TreeArrays], trace: FitTrace) -> TreeArrays:
        cfg, schema = self.cfg, self.schema
        if prev_trees:
            with scope("boost.prev_masks", trees=len(prev_trees)):
                per_tree = [self._leaf_masks(pt) for pt in prev_trees]
                prev_masks = {
                    t.name: jnp.concatenate([pm[t.name] for pm in per_tree])
                    for t in schema.tables
                }
                prev_vals = jnp.concatenate([pt.leaf for pt in prev_trees])
        else:
            prev_masks = {
                t.name: jnp.zeros((0, self.engine.n_rows(t.name)), jnp.bool_)
                for t in schema.tables
            }
            prev_vals = jnp.zeros((0,), jnp.float32)

        tree = TreeArrays.empty(cfg.depth)
        masks = {
            t.name: root_masks(schema, t.name, n_rows=self.engine.n_rows(t.name))
            for t in schema.tables
        }
        node_mean = jnp.zeros((1,), jnp.float32)
        M = int(prev_vals.shape[0])

        for level in range(cfg.depth):
            t0 = time.perf_counter()
            fenced = tracing_enabled()
            with scope("boost.level", level=level, prev_leaves=M):
                feat, thr, node_mean, masks, ssr, node_n = self._level_step(
                    masks, prev_masks, prev_vals, node_mean
                )
                fence((feat, thr, node_mean))
            if fenced:      # unfenced, the timer would read dispatch time
                get_registry().histogram("train.level_ms").observe(
                    (time.perf_counter() - t0) * 1e3)
            start = 2 ** level - 1
            tree = TreeArrays(
                feat=jax.lax.dynamic_update_slice_in_dim(tree.feat, feat, start, 0),
                thr=jax.lax.dynamic_update_slice_in_dim(tree.thr, thr, start, 0),
                leaf=tree.leaf,
            )
            self.counter.bump(self._count_level_queries(M))
            if self.engine.analytic_edges:
                self.counter.bump_edges(self._count_level_edges(M))
            if ssr:
                trace.node_ssr.append(ssr)
                trace.node_counts.append(node_n)

        return TreeArrays(feat=tree.feat, thr=tree.thr, leaf=cfg.lr * node_mean)

    def boost(
        self,
        trees: List[TreeArrays],
        n_trees: int,
        trace: Optional[FitTrace] = None,
    ) -> Tuple[List[TreeArrays], FitTrace]:
        """Warm start: append ``n_trees`` new trees fitted on the residuals
        of ``trees`` (which are left untouched).  ``fit()`` is
        ``boost([], cfg.n_trees)``; incremental retraining boosts on top
        of a frozen prefix after applying table deltas.  The returned
        trace reports THIS call's query cost (the lifetime total lives
        on ``self.counter``)."""
        trace = trace if trace is not None else FitTrace()
        reg = get_registry()
        q0 = self.counter.count
        trees = list(trees)
        for _ in range(n_trees):
            t0 = time.perf_counter()
            rq, re = self.counter.count, self.counter.edges
            with span("boost.round", round=len(trees),
                      mode=self.cfg.mode, split_mode=self.cfg.split_mode):
                trees.append(self._fit_tree(trees, trace))
            # per-round training telemetry: wall time, query volume, and
            # segment-⊕ emissions (real or analytic per the engine)
            reg.histogram("train.round_ms").observe(
                (time.perf_counter() - t0) * 1e3)
            reg.histogram("train.round_queries").observe(
                self.counter.count - rq)
            reg.histogram("train.round_edges").observe(
                self.counter.edges - re)
            reg.counter("train.rounds").inc()
        trace.queries = self.counter.count - q0
        return trees, trace

    def fit(self) -> Tuple[List[TreeArrays], FitTrace]:
        return self.boost([], self.cfg.n_trees)

    # ------------------------------------------------------------ serving --
    def predict_grouped(self, trees: List[TreeArrays], group_by: str):
        """Per-row-of-`group_by` (Σ ŷ(x), count) over x ∈ ρ⋈J — relational
        scoring without materializing J.  Delegates to the serving
        subsystem's compiled one-pass scorer (serving/compile.py); the
        seed per-leaf loop survives as serving.score_grouped_reference."""
        from ..serving import compile_ensemble, score_grouped

        # compile-once cache: the held tuple keeps strong refs to the
        # trees, so the id-based key cannot be reused by a different
        # (garbage-collected-then-reallocated) ensemble
        key = tuple(id(t) for t in trees)
        cached = getattr(self, "_compiled", None)
        if cached is None or cached[0] != key:
            ens = compile_ensemble(self.schema, trees, counter=self.counter)
            self._compiled = cached = (key, tuple(trees), ens)
        return score_grouped(cached[2], group_by)
