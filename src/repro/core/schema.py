"""Relational schema: tables, join hypergraph, GYO acyclicity, join trees.

A dataset with d features is stored in τ tables; the design matrix
``J = T_1 ⋈ … ⋈ T_τ`` (natural join, bag semantics) is *never*
materialized outside tests.  Schema construction is host-side (numpy-ish,
static): it builds, once, everything the jitted SumProd passes need —
rooted join trees and per-edge dense join-key dictionaries.

Acyclicity is decided by the GYO ear decomposition (paper Def. A.4); the
ear-witness edges *are* the join tree.  For acyclic joins fhtw = 1
(Observation 1) and inside-out runs in O(n) semiring ops per query after
the static key dictionaries replace the paper's per-query O(n log n) sort.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax.numpy as jnp


class NotAcyclicError(ValueError):
    """Raised when the join hypergraph has no GYO ear decomposition."""


@dataclasses.dataclass
class Table:
    """A named relation.  All columns are 1-D, equal length.

    ``feature_columns``: the columns on which tree splits may be proposed
    (the paper's features; join keys may be features too).  Join keys are
    inferred by natural-join semantics: any column name appearing in more
    than one table.  Key columns must be integer-typed.
    """

    name: str
    columns: Dict[str, np.ndarray]
    feature_columns: Tuple[str, ...] = ()

    def __post_init__(self):
        lens = {len(v) for v in self.columns.values()}
        if len(lens) != 1:
            raise ValueError(f"table {self.name}: ragged columns {lens}")
        if not self.feature_columns:
            self.feature_columns = tuple(self.columns.keys())
        for c in self.feature_columns:
            if c not in self.columns:
                raise ValueError(f"table {self.name}: unknown feature column {c}")

    @property
    def n_rows(self) -> int:
        return len(next(iter(self.columns.values())))

    def col(self, name: str) -> np.ndarray:
        return np.asarray(self.columns[name])


@dataclasses.dataclass(frozen=True)
class TreeEdge:
    """Directed join-tree edge child → parent with a dense key dictionary."""

    child: int                 # table index
    parent: int                # table index
    key_cols: Tuple[str, ...]  # shared columns (the join key of this edge)
    child_ids: jnp.ndarray     # (n_child,)  dense key id per child row
    parent_ids: jnp.ndarray    # (n_parent,) dense key id per parent row
    n_keys: int                # key-domain size


@dataclasses.dataclass(frozen=True)
class JoinTree:
    """Leaf→root elimination order for one root table."""

    root: int
    edges: Tuple[TreeEdge, ...]   # in elimination (leaf-first) order


def traced_tree(jt: JoinTree, child_ids, parent_ids, sizes) -> JoinTree:
    """``jt``'s shape with the given key ids and key-domain sizes: what a
    program sees of a join tree, so that none of its ids are constants."""
    return JoinTree(root=jt.root, edges=tuple(
        dataclasses.replace(e, child_ids=c, parent_ids=p, n_keys=n)
        for e, c, p, n in zip(jt.edges, child_ids, parent_ids, sizes)))


def _key_dict(ta: Table, tb: Table, cols: Sequence[str]):
    """Dense dictionary over the union of both tables' key tuples."""
    ka = np.stack([ta.col(c) for c in cols], axis=1)
    kb = np.stack([tb.col(c) for c in cols], axis=1)
    both = np.concatenate([ka, kb], axis=0)
    # one key column sorts as a plain array, far faster than as rows
    _, inv = (np.unique(both[:, 0], return_inverse=True) if len(cols) == 1
              else np.unique(both, axis=0, return_inverse=True))
    inv = inv.reshape(-1)
    n = int(inv.max()) + 1 if len(inv) else 0
    return (
        jnp.asarray(inv[: len(ka)], jnp.int32),
        jnp.asarray(inv[len(ka):], jnp.int32),
        n,
    )


class Schema:
    """An acyclic relational schema plus all static query-plan artifacts."""

    def __init__(self, tables: Sequence[Table], label: Tuple[str, str]):
        self.tables: List[Table] = list(tables)
        self.names = [t.name for t in self.tables]
        if len(set(self.names)) != len(self.names):
            raise ValueError("duplicate table names")
        self.index = {n: i for i, n in enumerate(self.names)}
        self.label_table, self.label_column = label
        if self.label_table not in self.index:
            raise ValueError(f"label table {self.label_table} not in schema")

        # --- feature ownership: first table containing a column owns it ---
        # (the paper's E_t assignment; used by sketching and split search)
        self.owner: Dict[str, str] = {}
        for t in self.tables:
            for c in t.columns:
                self.owner.setdefault(c, t.name)
        # global feature list: every ownable column except the label
        self.features: List[Tuple[str, str]] = []  # (table, column), owner only
        for t in self.tables:
            for c in t.feature_columns:
                if self.owner[c] == t.name and not (
                    t.name == self.label_table and c == self.label_column
                ):
                    self.features.append((t.name, c))

        # --- hypergraph + GYO -------------------------------------------------
        self._undirected_edges = self._gyo()   # list[(a, b, key_cols)] a-b adjacency
        self._tree_cache: Dict[int, JoinTree] = {}
        # per undirected edge: ({table: key ids}, n_keys), shared by the
        # join trees of every root
        self._key_ids: Dict[frozenset, tuple] = {}
        for n in self.names:                   # eager: jit-safe + one-time cost
            self._build_join_tree(n)

        # --- per-table device-resident feature matrices ----------------------
        self.feat_cols: Dict[str, List[str]] = {
            t.name: [c for (tn, c) in self.features if tn == t.name] for t in self.tables
        }
        self.featmat: Dict[str, jnp.ndarray] = {}
        for t in self.tables:
            cols = self.feat_cols[t.name]
            if cols:
                self.featmat[t.name] = jnp.asarray(
                    np.stack([t.col(c).astype(np.float32) for c in cols], axis=1)
                )
            else:
                self.featmat[t.name] = jnp.zeros((t.n_rows, 0), jnp.float32)
        # global feature id → (table idx, local idx)
        self.feat_global: List[Tuple[int, int]] = []
        for ti, t in enumerate(self.tables):
            for li, _ in enumerate(self.feat_cols[t.name]):
                self.feat_global.append((ti, li))
        self.n_features = len(self.feat_global)
        # per table, global feature id → local column (-1 if foreign): a
        # host constant, so mask descent bakes it in instead of building it
        self.local_feature_ids: Dict[str, np.ndarray] = {}
        for ti, t in enumerate(self.tables):
            g2l = np.full((max(self.n_features, 1),), -1, np.int32)
            for g, (tg, li) in enumerate(self.feat_global):
                if tg == ti:
                    g2l[g] = li
            self.local_feature_ids[t.name] = g2l

        self.labels = jnp.asarray(
            self.tables[self.index[self.label_table]].col(self.label_column).astype(np.float32)
        )

        # --- sketch projection dictionaries (paper §3: w_t(x), |D_t|) -------
        # D_t = distinct projections of T_t onto its *owned* columns.
        self.w_ids: Dict[str, jnp.ndarray] = {}
        self.domain_sizes: Dict[str, int] = {}
        for t in self.tables:
            owned = [c for c in t.columns if self.owner[c] == t.name]
            if owned:
                proj = np.stack([t.col(c) for c in owned], axis=1)
                _, inv = np.unique(proj, axis=0, return_inverse=True)
                self.w_ids[t.name] = jnp.asarray(inv, jnp.int32)
                self.domain_sizes[t.name] = int(inv.max()) + 1
            else:
                self.w_ids[t.name] = jnp.zeros((t.n_rows,), jnp.int32)
                self.domain_sizes[t.name] = 1

    # ------------------------------------------------------------------ GYO --
    def _gyo(self):
        """GYO ear decomposition.  Returns undirected join-tree edges;
        raises NotAcyclicError if the hypergraph is cyclic."""
        cols = {t.name: set(t.columns) for t in self.tables}
        alive = set(self.names)
        edges: List[Tuple[str, str, Tuple[str, ...]]] = []
        while len(alive) > 1:
            progress = False
            for a in sorted(alive):
                others = [b for b in alive if b != a]
                # columns of a shared with any other living table
                shared = {
                    c for c in cols[a] if any(c in cols[b] for b in others)
                }
                witness = next(
                    (b for b in sorted(others) if shared <= cols[b]), None
                )
                if witness is not None:
                    edges.append((a, witness, tuple(sorted(shared))))
                    alive.remove(a)
                    progress = True
                    break
            if not progress:
                raise NotAcyclicError(
                    f"join hypergraph is cyclic (stuck with {sorted(alive)}); "
                    "fhtw > 1 is out of scope (paper handles acyclic joins)"
                )
        return edges

    # ------------------------------------------------------------- join tree --
    def join_tree(self, root: str) -> JoinTree:
        """Rooted join tree (precomputed in __init__; jit-safe lookup)."""
        return self._tree_cache[self.index[root]]

    def _build_join_tree(self, root: str) -> JoinTree:
        ri = self.index[root]
        if ri in self._tree_cache:
            return self._tree_cache[ri]
        adj: Dict[str, List[Tuple[str, Tuple[str, ...]]]] = {n: [] for n in self.names}
        for a, b, key in self._undirected_edges:
            adj[a].append((b, key))
            adj[b].append((a, key))
        # BFS from root to get parent pointers
        parent: Dict[str, Tuple[str, Tuple[str, ...]]] = {}
        seen = {root}
        frontier = [root]
        order = [root]
        while frontier:
            nxt = []
            for u in frontier:
                for v, key in adj[u]:
                    if v not in seen:
                        seen.add(v)
                        parent[v] = (u, key)
                        nxt.append(v)
                        order.append(v)
            frontier = nxt
        if len(seen) != len(self.names):
            raise ValueError("join graph is disconnected (cross join unsupported)")
        # elimination order: reverse BFS (leaves first)
        edges = []
        for v in reversed(order[1:]):
            p, key = parent[v]
            edge = frozenset((v, p))
            if edge not in self._key_ids:
                iv, ip, n = _key_dict(self.table(v), self.table(p), key)
                self._key_ids[edge] = ({v: iv, p: ip}, n)
            ids, n = self._key_ids[edge]
            cid, pid = ids[v], ids[p]
            edges.append(
                TreeEdge(
                    child=self.index[v], parent=self.index[p], key_cols=key,
                    child_ids=cid, parent_ids=pid, n_keys=n,
                )
            )
        jt = JoinTree(root=ri, edges=tuple(edges))
        self._tree_cache[ri] = jt
        return jt

    # ----------------------------------------------------------------- misc --
    @property
    def n_tables(self) -> int:
        return len(self.tables)

    def table(self, name: str) -> Table:
        return self.tables[self.index[name]]

    def feature_name(self, gid: int) -> Tuple[str, str]:
        ti, li = self.feat_global[gid]
        t = self.tables[ti]
        return t.name, self.feat_cols[t.name][li]
