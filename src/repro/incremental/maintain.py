"""Delta-driven maintenance of compiled ensembles and memoized scores.

:class:`MaintainedScorer` turns the one-shot :class:`CompiledEnsemble`
into a continuously maintainable view (the static/dynamic factorization
of Kara et al.): typed table deltas update (a) the per-table stacked
leaf-mask factors — only the changed rows' mask slices are re-evaluated
and scattered in — and (b) the memoized grouped counts/scores, by
re-emitting segment-⊕ messages only along the changed tables' paths to
the root and ⊗-combining them with the cached clean messages.  A full
inside-out recompute costs one segment-⊕ per join-tree edge; a
single-table delta costs one per edge on that table's root path —
O(depth) instead of O(τ−1).

The mutable substrate (capacity-padded stores, append-only key
dictionaries, maintained join trees) lives in
:class:`~repro.incremental.state.DynamicState`, shared with the
incremental retraining engine (retrain.py); this module owns only the
serving-specific state: stacked leaf-mask factors and message caches.

The path-restricted refresh itself is JITTED: one compiled program per
(root, dirty-set signature, shape fingerprint), re-emitting exactly the
edges :func:`~repro.core.sumprod.refresh_plan` marks.  The emission
count is bumped eagerly from the same plan, so ``QueryCounter.edges``
accounting is identical to the eager :meth:`SumProd.refresh_messages`
route — the IVM benchmarks' ratios are compile-cache independent.

The scorer duck-types the slice of :class:`CompiledEnsemble` the serving
layer uses (``factors`` / ``leaf_values`` / ``grouped_cached`` /
``n_rows``), so it can be published to a :class:`ModelRegistry` and
served by the micro-batcher unchanged; every applied delta bumps
``data_version``, which the service folds into its result-cache key so
stale scores are unreachable.  Row ids are slots in the capacity-padded
store: live rows keep their ids across deltas, dead slots score as
(0, 0) — count 0 marks "row not in the join", same as a live row whose
key matches nothing.

For CONCURRENT ingest + serve the scorer publishes MVCC
:class:`Snapshot` views (:meth:`MaintainedScorer.snapshot`): an
immutable pin of factors + cached messages + join trees at one
``data_version``, captured under ``state.lock`` and served lock-free
while ``apply`` builds the next version.  Torn reads are impossible by
construction — a snapshot never aliases mutable state — and refreshed
messages flow back to the live scorer when versions still agree, so
the isolation is free of duplicate message emissions.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Set, Tuple

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import get_registry, span
from ..core.schema import Schema
from ..core.sumprod import QueryCounter, SumProd, refresh_plan
from ..distributed import spmd
from ..serving.compile import (
    CompiledEnsemble, compile_ensemble, contract_leaves, stack_table_factor)
from .deltas import DynamicEdge, DynamicTable, TableDelta
from .state import DynamicState, StateView


class MaintainedScorer:
    """A compiled ensemble plus the dynamic state that keeps it fresh.

    Sharding: inherits the source ensemble's data mesh (or the ambient
    `spmd` context).  Capacity-padded factors are placed row-sharded
    when the capacity divides the data axis (capacities are slack-padded
    and growth-doubled, so tables fall back to replicated whenever they
    don't — correct either way under the divisibility drop rule);
    message (re-)emission inside the cached/jitted refresh is the
    collective point, and grouped counts are replicated before the final
    contraction so served scores are bit-equal to single-device.
    """

    def __init__(self, ens: CompiledEnsemble, slack: float = 0.25,
                 counter: Optional[QueryCounter] = None,
                 served_window_s: float = 30.0,
                 snapshot_retention: int = 4):
        sch = ens.schema
        self.schema = sch
        self.source = ens
        self.trees = ens.trees
        self.leaf_values = ens.leaf_values
        self.tree0_leaves = ens.tree0_leaves
        self.total_leaves = ens.total_leaves
        self.counter = counter if counter is not None else ens.counter
        self._sem = ens._sem
        self._sp = SumProd(sch, counter=self.counter)
        self.factor_dtype = ens.factor_dtype
        self.data_version = 0
        self.mesh = ens.mesh if ens.mesh is not None else spmd.current_data_mesh()

        self.state = DynamicState(sch, slack=slack)
        self.tables: Dict[str, DynamicTable] = self.state.tables
        self.edges: Dict[frozenset, DynamicEdge] = self.state.edges

        # capacity-padded factors: source rows verbatim, dead slots ⊕-zero
        self.factors: Dict[str, jnp.ndarray] = {}
        for t in sch.tables:
            dt = self.tables[t.name]
            pad = dt.capacity - t.n_rows
            self.factors[t.name] = spmd.shard_factor(jnp.concatenate([
                ens.factors[t.name],
                jnp.zeros((pad, self.total_leaves), self.factor_dtype),
            ]), self.mesh)
        self.leaf_values = spmd.replicate_put(self.leaf_values, self.mesh)

        # jitted per-table delta-row mask evaluation (compile-once per
        # (table, delta-rows) shape — the apply() hot path)
        self._mask_fns: Dict[str, callable] = {}
        # jitted path-restricted refresh programs, keyed by
        # (root, dirty-set, jt version, message/factor shapes)
        self._refresh_fns: Dict[tuple, tuple] = {}

        # per-root cached state (created lazily on first score)
        self._msgs: Dict[str, List[jnp.ndarray]] = {}
        self._dirty: Dict[str, Set[int]] = {}
        self._grouped: Dict[str, Tuple[jnp.ndarray, jnp.ndarray]] = {}
        # wall-clock instant of the oldest applied-but-unrefreshed delta,
        # PER ROOT (absent = that root's served view is fully caught up)
        # — the data-staleness signal the SLO monitor burns against.  A
        # root only counts toward the aggregate gauge while it is being
        # served (queried within `served_window_s`): a root abandoned by
        # traffic must not pin the staleness objective forever.
        self._stale_since: Dict[str, float] = {}
        self._last_query: Dict[str, float] = {}
        self.served_window_s = served_window_s
        # recently published MVCC snapshots, keyed by data_version.  The
        # cache retains at most `snapshot_retention` versions (GC on
        # every apply/publish): evicted snapshots keep serving for
        # whoever still references them — the scorer just stops pinning
        # their factors/messages against collection.  The gauges let
        # /metricsz watch pin pressure (a long-pinned old version shows
        # up as oldest_pin_age_s growing without bound).
        self.snapshot_retention = max(1, int(snapshot_retention))
        self._snaps: Dict[int, "Snapshot"] = {}

    # ------------------------------------------------------------- queries --
    def n_rows(self, table: str) -> int:
        return self.tables[table].capacity

    def live_rows(self, table: str) -> np.ndarray:
        return self.state.live_rows(table)

    def effective_schema(self) -> Schema:
        """A fresh static Schema over the live rows (slot order) — the
        full-recompute oracle the maintained scores must match."""
        return self.state.effective_schema()

    # -------------------------------------------------------------- deltas --
    def apply(self, deltas: Sequence[TableDelta]) -> int:
        """Apply a delta batch; returns the new ``data_version``.

        Per table: mutate the dynamic store (via ``DynamicState``),
        re-evaluate leaf-mask factor rows for just the changed slots, and
        mark the table dirty in every cached root's message state.
        Nothing global is recomputed here — the path-restricted refresh
        happens lazily at the next score."""
        if isinstance(deltas, TableDelta):
            deltas = [deltas]
        t0 = time.perf_counter()
        # the state lock makes the whole batch one atomic version step:
        # a concurrent snapshot() observes either none or all of it, and
        # never a factor scatter without its data_version bump
        with self.state.lock, span("ivm.apply", n_deltas=len(deltas)):
            for ch in self.state.apply(deltas):
                if ch.grew:
                    cur = self.factors[ch.table]
                    cap = self.tables[ch.table].capacity
                    # re-place after growth: the new capacity may (not)
                    # divide the data axis — shard_factor re-resolves
                    self.factors[ch.table] = spmd.shard_factor(jnp.concatenate([
                        cur,
                        jnp.zeros((cap - cur.shape[0], cur.shape[1]), cur.dtype),
                    ]), self.mesh)
                # zero deleted slots BEFORE scattering fresh rows: an insert in
                # this same delta may have reused a just-deleted slot
                if len(ch.deleted):
                    gone = jnp.asarray(ch.deleted, jnp.int32)
                    self.factors[ch.table] = self.factors[ch.table].at[gone].set(0)
                if len(ch.changed):
                    self._refresh_factor_rows(ch.table, ch.changed)
                if len(ch.changed) or len(ch.deleted):
                    ti = self.schema.index[ch.table]
                    now = time.perf_counter()
                    for root in self._msgs:
                        self._dirty.setdefault(root, set()).add(ti)
                        self._stale_since.setdefault(root, now)
            self._grouped.clear()
            self.data_version += 1
            self._gc_snapshots()
        reg = get_registry()
        reg.counter("ivm.deltas").inc(len(deltas))
        reg.histogram("ivm.apply_ms").observe((time.perf_counter() - t0) * 1e3)
        return self.data_version

    def staleness_s(self, root: Optional[str] = None) -> float:
        """Wall-clock lag of the served view behind applied deltas.

        With ``root``: 0.0 when that root's cached messages reflect the
        current ``data_version``, else seconds since its oldest
        unrefreshed delta landed.  Without: the max over *served* roots
        — those queried within ``served_window_s`` — so a root traffic
        has abandoned cannot pin the gauge (and trip the SLO staleness
        objective) forever.  Before any root has been queried, all
        stale roots count.  The serving batcher mirrors its group-by
        root's reading into the ``service.staleness_s`` gauge."""
        now = time.perf_counter()
        if root is not None:
            t = self._stale_since.get(root)
            return max(0.0, now - t) if t is not None else 0.0
        if not self._stale_since:
            return 0.0
        if self._last_query:
            candidates = [t for r, t in self._stale_since.items()
                          if now - self._last_query.get(r, -np.inf)
                          <= self.served_window_s]
        else:
            candidates = list(self._stale_since.values())
        if not candidates:
            return 0.0
        return max(0.0, now - min(candidates))

    def _note_fresh(self, root: str) -> None:
        """Record that ``root``'s served view just caught up: observe
        how long its resolved deltas sat unserved (the delta lag) and
        re-sample the aggregate staleness gauge."""
        t = self._stale_since.pop(root, None)
        reg = get_registry()
        if t is not None:
            reg.histogram("ivm.refresh_lag_s").observe(time.perf_counter() - t)
        reg.gauge("ivm.staleness_s").set(self.staleness_s())

    def _refresh_factor_rows(self, table: str, slots: np.ndarray):
        """Re-evaluate the stacked leaf masks for ``slots`` and scatter
        them into the live factor (elementwise per-row ops — identical
        bits to a full-table recompute of the same rows)."""
        dt = self.tables[table]
        cols = self.schema.feat_cols[table]
        k = len(slots)
        if cols:
            rows = np.stack(
                [dt.columns[c][slots].astype(np.float32) for c in cols], axis=1
            )
        else:
            rows = np.zeros((k, 0), np.float32)
        sl = jnp.asarray(slots, jnp.int32)
        if table not in self._mask_fns:
            sch, trees, dt_ = self.schema, self.trees, self.factor_dtype

            def masks(featmat, table=table):
                return stack_table_factor(sch, trees, table,
                                          featmat=featmat, dtype=dt_)

            self._mask_fns[table] = jax.jit(masks)
        # bucket the delta size to the next power of two so arbitrary
        # stream shapes hit at most log(k) jit compilations per table
        k_pad = 1 << (max(k, 1) - 1).bit_length()
        if k_pad > k:
            rows = np.concatenate(
                [rows, np.zeros((k_pad - k, rows.shape[1]), np.float32)]
            )
        frows = self._mask_fns[table](jnp.asarray(rows))
        self.factors[table] = self.factors[table].at[sl].set(frows[:k])

    # ------------------------------------------------------------- scoring --
    def _refresh_fn(self, root: str, dirty: frozenset, jt, msgs,
                    jt_version: int, factors):
        """Compiled path-restricted refresh for one (root, dirty-set,
        shape fingerprint); returns (jitted fn, #edges it re-emits).
        The plan is computed ONCE from :func:`refresh_plan` — the same
        source of truth the eager route uses — so the cached program
        re-emits exactly the edges the eager route would, and the edge
        accounting (bumped eagerly by the caller) cannot drift.
        ``jt``/``msgs``/``jt_version``/``factors`` are explicit so MVCC
        snapshots pinned at an older version share this compile cache:
        a snapshot's shapes fingerprint alongside the live scorer's."""
        fingerprint = (
            root, dirty, jt_version,
            tuple(m.shape for m in msgs),
            tuple((tn, factors[tn].shape) for tn in sorted(factors)),
        )
        hit = self._refresh_fns.get(fingerprint)
        if hit is not None:
            return hit
        sem, sp = self._sem, self._sp                # node_factor never bumps
        mesh = self.mesh
        plan = refresh_plan(jt, dirty)
        pads = [max(0, e.n_keys - msgs[i].shape[0])
                for i, e in enumerate(jt.edges)]

        def run(factors, msgs):
            new = list(msgs)
            for i, e in enumerate(jt.edges):
                if pads[i]:                          # key domain grew: ⊕-pad
                    new[i] = jnp.concatenate(
                        [new[i], sem.zeros((pads[i],))], axis=0
                    )
                if plan[i]:
                    cf = sp.node_factor(sem, factors, jt, e.child, new)
                    new[i] = spmd.psum_message(
                        sem.segment_add(cf, e.child_ids, e.n_keys), mesh)
            return new

        out = (jax.jit(run), sum(plan))
        if len(self._refresh_fns) > 128:             # bound compile cache
            self._refresh_fns.clear()
        self._refresh_fns[fingerprint] = out
        return out

    def _counts(self, group_by: str) -> jnp.ndarray:
        """Grouped leaf counts via cached messages + jitted path refresh."""
        jt = self.state.jt(group_by)
        sem, sp = self._sem, self._sp
        dirty = self._dirty.get(group_by)
        if group_by not in self._msgs:
            with spmd.use_data_mesh(self.mesh):
                self._msgs[group_by] = sp.messages(sem, self.factors, jt=jt)
        elif dirty:
            t0 = time.perf_counter()
            with span("ivm.refresh", root=group_by, dirty=len(dirty)):
                run, n_emit = self._refresh_fn(
                    group_by, frozenset(dirty), jt, self._msgs[group_by],
                    self.state.jt_version, self.factors)
                self._msgs[group_by] = run(self.factors, self._msgs[group_by])
            if self.counter is not None:
                self.counter.bump_edges(n_emit)
            get_registry().histogram("ivm.refresh_ms").observe(
                (time.perf_counter() - t0) * 1e3)
        self._dirty[group_by] = set()
        self._last_query[group_by] = time.perf_counter()
        self._note_fresh(group_by)
        # replicate before the serving contraction (see score_grouped)
        return spmd.replicate(
            sp.node_factor(sem, self.factors, jt, jt.root, self._msgs[group_by]),
            self.mesh)

    def score_grouped(self, group_by: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(Σŷ, |ρ⋈J|) per slot of ``group_by`` — maintained counts, same
        contraction as the compiled scorer.  Dead slots read (0, 0)."""
        if self.counter is not None:
            self.counter.bump(1)
        return contract_leaves(self._counts(group_by), self.leaf_values,
                               self.tree0_leaves)

    def grouped_cached(self, group_by: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
        if group_by not in self._grouped:
            self._grouped[group_by] = self.score_grouped(group_by)
        return self._grouped[group_by]

    def recompute_oracle(self, group_by: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Ground-truth full recompute: a fresh static compile over the
        effective live tables (new key dictionaries, no cached state),
        evaluated through an eager message pass.  Returned arrays are
        capacity-shaped (live slots filled, dead slots 0) so they compare
        bit-for-bit against the maintained grouped output: the leaf
        counts are integer-exact either way, and the final contraction
        is the same per-row FMA chain (`contract_leaves`), so no
        float-reassociation freedom remains."""
        with self.state.lock:
            eff = self.effective_schema()
            live = self.live_rows(group_by)
            cap = self.tables[group_by].capacity
        return self._oracle_from(eff, group_by, live, cap)

    def _oracle_from(self, eff: Schema, group_by: str, live, capacity: int
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """The recompute oracle over an EXPLICIT effective schema /
        live-slot / capacity pin — shared by :meth:`recompute_oracle`
        (current state) and :meth:`Snapshot.recompute_oracle` (a frozen
        historical version)."""
        # the oracle is pinned single-device (use_data_mesh(None) clears
        # any ambient mesh): ground truth must not depend on sharding
        with spmd.use_data_mesh(None):
            fresh = compile_ensemble(eff, self.trees,
                                     factor_dtype=self.factor_dtype)
            sp = SumProd(eff)
            jt = eff.join_tree(group_by)
            msgs = sp.messages(fresh._sem, fresh.factors, jt=jt)
            counts = sp.node_factor(fresh._sem, fresh.factors, jt, jt.root, msgs)
        full = jnp.zeros(
            (capacity, counts.shape[1]), counts.dtype
        ).at[jnp.asarray(live, jnp.int32)].set(counts)
        return contract_leaves(full, fresh.leaf_values, fresh.tree0_leaves)

    # ----------------------------------------------------------- snapshots --
    def snapshot(self, roots: Optional[Sequence[str]] = None,
                 pin_oracle: bool = False) -> "Snapshot":
        """Publish an immutable MVCC :class:`Snapshot` of the current
        ``data_version``.

        Cheap: jax arrays are immutable (``apply`` rebinds new arrays,
        never writes through old ones), so the factor dict and cached
        message lists are captured by reference; the only real work is
        join-tree materialization, cached per ``jt_version``.  The
        result is cached until the next ``apply``, so concurrent
        batches at one version share one snapshot.

        ``roots`` limits which roots the snapshot can serve (default:
        every table); ``pin_oracle=True`` additionally freezes the
        effective schema + live slots so :meth:`Snapshot.recompute_oracle`
        stays bit-exact after the live state has moved on.
        """
        names = (tuple(sorted(roots)) if roots is not None
                 else tuple(t.name for t in self.schema.tables))
        with self.state.lock:
            snap = self._snaps.get(self.data_version)
            if (snap is not None
                    and set(names) <= set(snap.view.jts)
                    and (not pin_oracle or snap.view.schema is not None)):
                return snap
            view = self.state.snapshot(names, pin_oracle=pin_oracle)
            snap = Snapshot(
                owner=self, view=view, data_version=self.data_version,
                factors=dict(self.factors), leaf_values=self.leaf_values,
                msgs={r: list(self._msgs[r]) for r in names
                      if r in self._msgs},
                dirty={r: frozenset(self._dirty.get(r, ())) for r in names},
            )
            self._snaps[self.data_version] = snap
            self._gc_snapshots()
            return snap

    def _gc_snapshots(self) -> None:
        """Evict cached snapshot versions beyond the retention window
        and republish the pin-pressure gauges.  Called under
        ``state.lock`` (from ``apply`` and ``snapshot``)."""
        floor = self.data_version - self.snapshot_retention
        for v in [v for v in self._snaps if v <= floor]:
            del self._snaps[v]
        reg = get_registry()
        reg.gauge("snapshot.pinned_versions").set(len(self._snaps))
        oldest = min((s.t_created for s in self._snaps.values()),
                     default=None)
        reg.gauge("snapshot.oldest_pin_age_s").set(
            0.0 if oldest is None else max(0.0, time.time() - oldest))

    def adopt_state(self, state: DynamicState) -> None:
        """Replace the dynamic substrate with a RECOVERED state (a
        checkpoint load — see :mod:`repro.incremental.recover`).

        The stacked leaf-mask factors are re-evaluated for every live
        slot of the adopted state; factor rows are pure per-row
        functions of current column values, so the result is
        bit-identical to having maintained them through the original
        delta stream.  All cached messages, memoized scores, staleness
        markers and snapshots are dropped (they referred to the old
        substrate), and ``data_version`` adopts the recovered LSN."""
        with state.lock:
            self.state = state
            self.tables = state.tables
            self.edges = state.edges
            self.factors = {}
            for t in self.schema.tables:
                dt = self.tables[t.name]
                self.factors[t.name] = spmd.shard_factor(
                    jnp.zeros((dt.capacity, self.total_leaves),
                              self.factor_dtype), self.mesh)
                live = dt.live_slots()
                if len(live):
                    self._refresh_factor_rows(t.name, live)
            self._msgs.clear()
            self._dirty.clear()
            self._grouped.clear()
            self._stale_since.clear()
            self._last_query.clear()
            self._snaps.clear()
            self.data_version = state.data_version

    def _absorb(self, root: str, data_version: int, msgs) -> None:
        """Adopt a snapshot's refreshed messages iff the live scorer is
        still at the snapshot's ``data_version`` — at the same version
        the snapshot and the live scorer share one dirty set (both only
        change under ``state.lock``), so its refresh IS the live
        refresh: serving through snapshots stays exactly as incremental
        as serving the scorer directly.  After the version has moved
        on, the refresh only served that snapshot; drop it."""
        with self.state.lock:
            if self.data_version != data_version:
                return
            self._msgs[root] = list(msgs)
            self._dirty[root] = set()
            self._last_query[root] = time.perf_counter()
            self._note_fresh(root)

    def score_full(self, group_by: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Full-recompute reference over the SAME maintained state (every
        edge re-emitted) — the benchmark baseline for the edge-count and
        latency ratios.  Does not touch the cached messages."""
        jt = self.state.jt(group_by)
        with spmd.use_data_mesh(self.mesh):
            msgs = self._sp.messages(self._sem, self.factors, jt=jt)
        counts = spmd.replicate(
            self._sp.node_factor(self._sem, self.factors, jt, jt.root, msgs),
            self.mesh)
        return contract_leaves(counts, self.leaf_values, self.tree0_leaves)


class Snapshot:
    """An immutable MVCC view of a :class:`MaintainedScorer`, pinned at
    one ``data_version``.

    Duck-types the serving surface (``n_rows`` / ``score_grouped`` /
    ``grouped_cached`` / ``data_version`` / ``mesh``), so the
    micro-batcher dispatches against it unchanged while the owner
    applies the next version concurrently — reads never observe a
    half-applied delta because everything here is frozen: the factor
    dict and message lists were captured under ``state.lock`` and jax
    arrays are immutable, the join trees were materialized to jnp at
    capture.

    Snapshots are *lazily consistent*: one captured with pending dirty
    tables resolves them on first score through the owner's jitted
    path-refresh compile cache (same :func:`refresh_plan`, same edge
    accounting), then writes the refreshed messages back to the owner
    iff it is still at this version (:meth:`MaintainedScorer._absorb`)
    — so snapshot serving costs no extra message emissions over serving
    the live scorer.  Scoring a root outside the pinned set raises
    ``KeyError``.
    """

    def __init__(self, owner: MaintainedScorer, view: StateView,
                 data_version: int, factors, leaf_values, msgs, dirty):
        self._owner = owner
        self.view = view
        self.data_version = data_version
        self.t_created = time.time()
        self.jt_version = view.jt_version
        self.factors = factors
        self.leaf_values = leaf_values
        self.mesh = owner.mesh
        self._msgs = msgs           # root → message list (None until scored)
        self._dirty = dirty         # root → frozenset of dirty table idx
        self._grouped: Dict[str, Tuple[jnp.ndarray, jnp.ndarray]] = {}
        # serializes lazy refresh within ONE snapshot; never held while
        # taking state.lock (write-back happens after release)
        self._lock = threading.Lock()

    # ------------------------------------------------------------- surface --
    def roots(self) -> Tuple[str, ...]:
        return tuple(sorted(self.view.jts))

    def n_rows(self, table: str) -> int:
        return self.view.capacities[table]

    def _counts(self, group_by: str) -> jnp.ndarray:
        jt = self.view.jt(group_by)              # KeyError if not pinned
        o = self._owner
        sem, sp = o._sem, o._sp
        with self._lock:
            msgs = self._msgs.get(group_by)
            dirty = self._dirty.get(group_by, frozenset())
            if msgs is None:
                with spmd.use_data_mesh(self.mesh):
                    msgs = sp.messages(sem, self.factors, jt=jt)
            elif dirty:
                t0 = time.perf_counter()
                with span("ivm.refresh", root=group_by, dirty=len(dirty)):
                    run, n_emit = o._refresh_fn(
                        group_by, dirty, jt, msgs, self.jt_version,
                        self.factors)
                    msgs = run(self.factors, msgs)
                if o.counter is not None:
                    o.counter.bump_edges(n_emit)
                get_registry().histogram("ivm.refresh_ms").observe(
                    (time.perf_counter() - t0) * 1e3)
            self._msgs[group_by] = msgs
            self._dirty[group_by] = frozenset()
        o._absorb(group_by, self.data_version, msgs)
        return spmd.replicate(
            sp.node_factor(sem, self.factors, jt, jt.root, msgs), self.mesh)

    def score_grouped(self, group_by: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(Σŷ, |ρ⋈J|) per slot at this snapshot's pinned version —
        identical contraction (and bits) to the owner at this version."""
        o = self._owner
        if o.counter is not None:
            o.counter.bump(1)
        return contract_leaves(self._counts(group_by), self.leaf_values,
                               o.tree0_leaves)

    def grouped_cached(self, group_by: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
        with self._lock:
            hit = self._grouped.get(group_by)
        if hit is None:
            hit = self.score_grouped(group_by)
            with self._lock:
                hit = self._grouped.setdefault(group_by, hit)
        return hit

    def recompute_oracle(self, group_by: str
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Ground-truth full recompute AT THIS PINNED VERSION — works
        even after the live state has moved on.  Requires the snapshot
        to have been taken with ``pin_oracle=True``."""
        if self.view.schema is None:
            raise ValueError(
                "snapshot was not captured with pin_oracle=True; "
                "no frozen effective schema to recompute from")
        return self._owner._oracle_from(
            self.view.schema, group_by,
            self.view.live[group_by], self.view.capacities[group_by])
