"""Inside-out evaluation of SumProd queries (paper §1.1.1, Lemma 1.1).

The evaluator is a vectorized message-passing pass over a rooted join
tree.  Each table contributes a *factor*: one semiring value per row
(``⊗`` of that table's q_f terms, with J^{(v)}-constraint masks already
applied as semiring zeros).  An edge child→parent sends

    msg[key] = ⊕_{rows r of child : key(r)=key} factor_child[r]
    factor_parent[r'] ⊗= msg[key(r')]

computed as one ``segment-⊕`` (dense key dictionary, built statically by
the Schema) plus one gather.  After all edges, the root's factor holds,
per root row ρ, exactly ``⊕_{x ∈ ρ ⋈ J} ⊗_f q_f(x_f)`` — the paper's
*grouped-by* query.  The ungrouped query is one more ⊕-reduce.

TPU adaptation (DESIGN.md §3): the paper runs one inside-out pass per
query; we batch query families (tree nodes, leaves, leaf pairs) with
``vmap`` over the factor arrays — the plan (segment ids) is static.

Distribution: rows shard over the data axes; ``segment-⊕`` runs
per-shard and key-domain message vectors are ⊕-combined across the axis
at emission time.  The combine is ``spmd.psum_message`` — a replicated
sharding constraint that GSPMD lowers to the cross-shard all-reduce —
applied inside :meth:`SumProd.messages` / :meth:`refresh_messages` /
:meth:`messages_memo`, so every caller (serving, boosting, IVM) gets the
same collective point.  With no active data mesh the constraint is an
identity and the single-device program is bit-unchanged.  Edge/query
accounting is host-side and therefore invariant under sharding: a mesh
moves bytes, never work.  (``distributed/collectives.py`` keeps the
explicit shard_map+psum prototype as a reference.)
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, Hashable, Iterable, List, Optional, Set

import jax
import jax.numpy as jnp

from ..distributed import spmd as _spmd
from ..obs import metrics as _metrics
from ..obs.trace import scope as _scope
from .schema import Schema, JoinTree
from .semiring import Semiring


class QueryCounter:
    """Counts SumProd evaluations — used by benchmarks to verify the
    paper's query-complexity claims (O(m²L²τ) exact vs O(mLτ) sketched).

    ``edges`` separately counts segment-⊕ message emissions: a full
    inside-out pass emits one per join-tree edge, while an incremental
    refresh (see :meth:`SumProd.refresh_messages`) emits only along the
    changed tables' root paths — the ratio the IVM benchmarks report.

    Back-compat shim over :mod:`repro.obs.metrics`: bumps come from
    jitted callbacks and benchmark threads, so each instance owns
    thread-safe :class:`~repro.obs.metrics.Counter`s and additionally
    mirrors into the process registry's ``sumprod.queries`` /
    ``sumprod.edges`` series (the aggregate the launch CLIs report).
    ``count``/``edges`` read exactly what this instance accumulated —
    per-counter accounting (the IVM ratios) is unchanged.
    """

    def __init__(self):
        self._count = _metrics.Counter("sumprod.queries")
        self._edges = _metrics.Counter("sumprod.edges")
        reg = _metrics.get_registry()
        self._g_count = reg.counter("sumprod.queries")
        self._g_edges = reg.counter("sumprod.edges")

    @property
    def count(self) -> int:
        return self._count.value

    @property
    def edges(self) -> int:
        return self._edges.value

    def bump(self, n: int = 1):
        self._count.inc(n)
        self._g_count.inc(n)

    def bump_edges(self, n: int = 1):
        self._edges.inc(n)
        self._g_edges.inc(n)


def refresh_plan(jt: JoinTree, dirty: Iterable[int]) -> List[bool]:
    """Static plan of a path-restricted refresh: which edges (leaf-first
    order, aligned with ``jt.edges``) must re-emit their segment-⊕ when
    the tables in ``dirty`` changed.  Dirtiness propagates child→parent,
    so the plan covers the union of the dirty tables' root paths.  Shared
    by the eager :meth:`SumProd.refresh_messages` and the jitted refresh
    cached per (root, dirty-set, shapes) in incremental/maintain.py —
    both must re-emit exactly these edges so ``QueryCounter.edges``
    accounting is route-independent."""
    live: Set[int] = set(dirty)
    plan: List[bool] = []
    for e in jt.edges:
        hit = e.child in live
        plan.append(hit)
        if hit:
            live.add(e.parent)
    return plan


class MessageCache:
    """Signature-keyed memo of per-edge segment-⊕ messages.

    Key: (join-tree root, edge index, subtree signature).  The subtree
    signature combines, bottom-up, the factor signatures of every table
    in the edge's child subtree — two queries whose factors agree on that
    whole subtree share the message, so boosting's per-node/per-leaf
    query families reuse unchanged-subtree messages across tree levels,
    across trees, and across deltas.  Entries are LRU-bounded per edge;
    a cached message whose key domain grew since emission is ⊕-identity
    padded on retrieval (a new key has no child rows yet).
    """

    def __init__(self, max_per_edge: int = 64):
        self.max_per_edge = max_per_edge
        self._store: Dict[tuple, "OrderedDict[Hashable, jnp.ndarray]"] = {}
        self.hits = 0
        self.misses = 0
        reg = _metrics.get_registry()
        self._g_hits = reg.counter("msgcache.hits")
        self._g_misses = reg.counter("msgcache.misses")

    def get(self, root: int, edge: int, sig: Hashable):
        slot = self._store.get((root, edge))
        if slot is None or sig not in slot:
            self.misses += 1
            self._g_misses.inc()
            return None
        slot.move_to_end(sig)
        self.hits += 1
        self._g_hits.inc()
        return slot[sig]

    def put(self, root: int, edge: int, sig: Hashable, msg: jnp.ndarray):
        slot = self._store.setdefault((root, edge), OrderedDict())
        slot[sig] = msg
        slot.move_to_end(sig)
        while len(slot) > self.max_per_edge:
            slot.popitem(last=False)

    def clear(self):
        self._store.clear()

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0


class SumProd:
    """Executable SumProd program for one schema."""

    def __init__(self, schema: Schema, counter: Optional[QueryCounter] = None):
        self.schema = schema
        self.counter = counter

    def ones_factors(self, sem: Semiring, batch_shape=()) -> Dict[str, jnp.ndarray]:
        """Factor dict with ⊗-identity everywhere (q_f ≡ 1)."""
        return {
            t.name: sem.ones(tuple(batch_shape) + (t.n_rows,))
            for t in self.schema.tables
        }

    # ------------------------------------------------------- message pass --
    def node_factor(
        self,
        sem: Semiring,
        factors: Dict[str, jnp.ndarray],
        jt: JoinTree,
        node: int,
        msgs: List[Optional[jnp.ndarray]],
    ) -> jnp.ndarray:
        """Combined factor at ``node``: base factor ⊗ gathered messages
        from every child edge whose message is already available.  The
        gather axis is derived from each message's rank, so factors and
        messages may carry leading batch dims (broadcast under ⊗)."""
        f = factors[self.schema.names[node]]
        for i, e in enumerate(jt.edges):
            if e.parent == node and msgs[i] is not None:
                m = msgs[i]
                ax = m.ndim - 1 - len(sem.value_shape)
                f = sem.mul(f, jnp.take(m, e.parent_ids, axis=ax))
        return f

    @staticmethod
    def _segment_add_any(sem: Semiring, vals, segment_ids, num_segments):
        """segment-⊕ with an optional leading batch dim (vmapped)."""
        if vals.ndim == 1 + len(sem.value_shape):
            return sem.segment_add(vals, segment_ids, num_segments)
        return jax.vmap(
            lambda v: sem.segment_add(v, segment_ids, num_segments)
        )(vals)

    def messages(
        self,
        sem: Semiring,
        factors: Dict[str, jnp.ndarray],
        root: Optional[str] = None,
        jt: Optional[JoinTree] = None,
    ) -> List[jnp.ndarray]:
        """Full inside-out pass, returning the per-edge segment-⊕ messages
        (leaf-first order, aligned with ``jt.edges``) instead of consuming
        them inline — the cacheable state incremental maintenance reuses."""
        if jt is None:
            jt = self.schema.join_tree(root)
        msgs: List[Optional[jnp.ndarray]] = [None] * len(jt.edges)
        with _scope("sumprod.messages", n_edges=len(jt.edges)):
            for i, e in enumerate(jt.edges):
                with _scope("sumprod.emit", edge=i, child=e.child,
                            parent=e.parent, n_keys=e.n_keys):
                    cf = self.node_factor(sem, factors, jt, e.child, msgs)
                    msgs[i] = _spmd.psum_message(
                        sem.segment_add(cf, e.child_ids, e.n_keys))
        if self.counter is not None:
            self.counter.bump_edges(len(jt.edges))
        return msgs  # type: ignore[return-value]

    def refresh_messages(
        self,
        sem: Semiring,
        factors: Dict[str, jnp.ndarray],
        msgs: List[jnp.ndarray],
        dirty: Iterable[int],
        jt: JoinTree,
    ) -> List[jnp.ndarray]:
        """Path-restricted re-emission: recompute messages only on edges
        whose child subtree contains a changed table, reusing every cached
        clean message.  ``dirty``: indices of tables whose factors changed.
        Cached messages whose key domain grew since they were emitted are
        ⊕-identity-padded (a previously unseen key has no child rows yet).
        Cost: one segment-⊕ per edge on the union of the dirty tables'
        root paths — O(path) instead of O(τ−1).
        """
        plan = refresh_plan(jt, dirty)
        new = list(msgs)
        with _scope("sumprod.refresh", n_edges=sum(plan)):
            for i, e in enumerate(jt.edges):
                if new[i].shape[0] < e.n_keys:
                    pad = sem.zeros((e.n_keys - new[i].shape[0],))
                    new[i] = jnp.concatenate([new[i], pad], axis=0)
                if plan[i]:
                    with _scope("sumprod.emit", edge=i, child=e.child,
                                parent=e.parent, n_keys=e.n_keys):
                        cf = self.node_factor(sem, factors, jt, e.child, new)
                        new[i] = _spmd.psum_message(
                            sem.segment_add(cf, e.child_ids, e.n_keys))
        if self.counter is not None:
            self.counter.bump_edges(sum(plan))
        return new

    def messages_memo(
        self,
        sem: Semiring,
        factors: Dict[str, jnp.ndarray],
        jt: JoinTree,
        sigs: Dict[str, Hashable],
        cache: MessageCache,
    ) -> List[jnp.ndarray]:
        """Inside-out message pass through a signature-keyed cache.

        ``factors``: per-table arrays with ONE leading batch dim
        ((B_t, n_rows, *value_shape), B_t ∈ {1, K}) — a query family may
        batch node-uniform tables as a single row and broadcast.
        ``sigs``: per-table hashable factor signatures (content version +
        mask digest + batch width).  An edge whose whole child subtree
        matches a cached signature reuses the cached message and emits
        nothing; only misses run a segment-⊕ (and bump
        ``QueryCounter.edges``) — the maintained-retraining win the
        benchmarks audit.
        """
        names = self.schema.names
        msgs: List[Optional[jnp.ndarray]] = [None] * len(jt.edges)
        subsig: List[Hashable] = [None] * len(jt.edges)
        recomputed = 0
        for i, e in enumerate(jt.edges):
            incoming = [j for j in range(i) if jt.edges[j].parent == e.child]
            sig = (sigs[names[e.child]], tuple(subsig[j] for j in incoming))
            subsig[i] = sig
            hit = cache.get(jt.root, i, sig)
            if hit is not None:
                ax = hit.ndim - 1 - len(sem.value_shape)
                if hit.shape[ax] < e.n_keys:      # key domain grew: ⊕-pad
                    pad_batch = hit.shape[:ax] + (e.n_keys - hit.shape[ax],)
                    hit = jnp.concatenate(
                        [hit, sem.zeros(pad_batch)], axis=ax
                    )
                    cache.put(jt.root, i, sig, hit)
                msgs[i] = hit
                continue
            with _scope("sumprod.emit", edge=i, child=e.child,
                        parent=e.parent, n_keys=e.n_keys):
                cf = self.node_factor(sem, factors, jt, e.child, msgs)
                msgs[i] = _spmd.psum_message(
                    self._segment_add_any(sem, cf, e.child_ids, e.n_keys))
            cache.put(jt.root, i, sig, msgs[i])
            recomputed += 1
        if self.counter is not None:
            self.counter.bump_edges(recomputed)
        return msgs  # type: ignore[return-value]

    def __call__(
        self,
        sem: Semiring,
        factors: Dict[str, jnp.ndarray],
        group_by: Optional[str] = None,
        root: Optional[str] = None,
        n_queries: int = 1,
    ):
        """Evaluate the query.

        factors: per-table arrays (n_rows, *value_shape).  Leading batch
        dims are NOT allowed here — use jax.vmap around this call (the
        static plan is shared).
        group_by: if set, return per-row results for that table (the tree
        is rooted there).  Otherwise reduce to a single semiring value.
        """
        root_name = group_by or root or self.schema.names[0]
        jt: JoinTree = self.schema.join_tree(root_name)
        if self.counter is not None:
            self.counter.bump(n_queries)

        msgs = self.messages(sem, factors, jt=jt)
        out = self.node_factor(sem, factors, jt, jt.root, msgs)
        if group_by is not None:
            return out
        return _spmd.replicate(sem.reduce_add(out, axis=0))


def materialize_join(schema: Schema) -> Dict[str, jnp.ndarray]:
    """Materialize J = T_1 ⋈ … ⋈ T_τ (bag semantics) — tests/baseline ONLY.

    Returns {column_name: (|J|,) array} plus per-table row indices
    ``__rows__<table>`` so tests can cross-check grouped queries.
    """
    import numpy as np

    tables = schema.tables
    # start from the first table
    cur_cols = {c: np.asarray(v) for c, v in tables[0].columns.items()}
    cur_rows = {tables[0].name: np.arange(tables[0].n_rows)}
    done = {tables[0].name}
    pending = [t for t in tables[1:]]
    while pending:
        progress = False
        for t in list(pending):
            shared = [c for c in t.columns if c in cur_cols]
            if not shared:
                continue
            # hash-join on shared columns
            left_key = np.stack([cur_cols[c] for c in shared], 1)
            right_key = np.stack([t.col(c) for c in shared], 1)
            uni, li = np.unique(
                np.concatenate([left_key, right_key]), axis=0, return_inverse=True
            )
            lk, rk = li[: len(left_key)], li[len(left_key):]
            # build index lists per key for the right side
            order = np.argsort(rk, kind="stable")
            rk_sorted = rk[order]
            starts = np.searchsorted(rk_sorted, np.arange(len(uni)))
            ends = np.searchsorted(rk_sorted, np.arange(len(uni)), side="right")
            li_out, ri_out = [], []
            for i, key in enumerate(lk):
                for j in order[starts[key]:ends[key]]:
                    li_out.append(i)
                    ri_out.append(j)
            li_out = np.asarray(li_out, np.int64)
            ri_out = np.asarray(ri_out, np.int64)
            cur_cols = {c: v[li_out] for c, v in cur_cols.items()}
            for c in t.columns:
                if c not in cur_cols:
                    cur_cols[c] = t.col(c)[ri_out]
            cur_rows = {k: v[li_out] for k, v in cur_rows.items()}
            cur_rows[t.name] = ri_out
            done.add(t.name)
            pending.remove(t)
            progress = True
        if not progress:
            raise ValueError("disconnected join graph")
    out = {c: jnp.asarray(v) for c, v in cur_cols.items()}
    for k, v in cur_rows.items():
        out["__rows__" + k] = jnp.asarray(v, jnp.int32)
    return out
