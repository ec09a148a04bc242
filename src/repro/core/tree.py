"""Array-encoded regression trees (SoA pytrees) + relational masks.

A tree of depth D is complete-binary in heap layout: internal node k at
level ℓ has within-level index k ∈ [0, 2^ℓ); its children are 2k (left)
and 2k+1 (right).  Splits are the paper's ``J_feat ≥ thr → right``.
Dead nodes (no valid split / empty) carry thr = +inf so every point
routes left; the left descendant leaf holds the node's mean.

The relational core never materializes J; node/leaf membership lives as
*per-table row masks*: a row r of table T_t passes node v iff it
satisfies every constraint on the root→v path whose feature is owned by
T_t (constraints on other tables' features don't constrain T_t's rows —
the ⊗ of factors conjoins them across tables inside the SumProd query).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from .schema import Schema


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TreeArrays:
    """One regression tree.  Leaves: L = 2^depth.

    feat:  (L-1,) int32   global feature id per internal node (-1 = dead)
    thr:   (L-1,) float32 threshold (+inf on dead nodes → route left)
    leaf:  (L,)   float32 leaf predictions
    """

    feat: jnp.ndarray
    thr: jnp.ndarray
    leaf: jnp.ndarray

    @property
    def depth(self) -> int:
        return int(self.leaf.shape[0]).bit_length() - 1

    @staticmethod
    def empty(depth: int) -> "TreeArrays":
        L = 2 ** depth
        return TreeArrays(
            feat=jnp.full((L - 1,), -1, jnp.int32),
            thr=jnp.full((L - 1,), jnp.inf, jnp.float32),
            leaf=jnp.zeros((L,), jnp.float32),
        )

    def level_slice(self, level: int):
        """Within-level views of feat/thr for nodes at ``level``."""
        start = 2 ** level - 1
        size = 2 ** level
        return (
            jax.lax.dynamic_slice_in_dim(self.feat, start, size),
            jax.lax.dynamic_slice_in_dim(self.thr, start, size),
        )


def predict_rows(trees: List[TreeArrays], X: jnp.ndarray, lr: float = 1.0) -> jnp.ndarray:
    """Boosted prediction on a materialized feature matrix (tests/baseline).

    X: (n, d_global) in *global feature id* order.
    """
    out = jnp.zeros((X.shape[0],), jnp.float32)
    for t in trees:
        idx = jnp.zeros((X.shape[0],), jnp.int32)  # within-level index
        for level in range(t.depth):
            feat, thr = t.level_slice(level)
            f = jnp.take(feat, idx)
            th = jnp.take(thr, idx)
            v = jnp.take_along_axis(X, jnp.maximum(f, 0)[:, None], axis=1)[:, 0]
            go_right = (v >= th) & (f >= 0)
            idx = 2 * idx + go_right.astype(jnp.int32)
        out = out + lr * jnp.take(t.leaf, idx)
    return out


# ---------------------------------------------------------------------------
# Relational masks
# ---------------------------------------------------------------------------

def _local_feature_view(schema: Schema, table: str, featmat=None):
    """(g2l, featmat): map global feature id → local column, -1 if foreign.

    ``g2l`` is the schema's host-side int32 table, so a program that
    descends bakes it in as a constant.  ``featmat`` overrides the
    schema's device-resident (n_rows, d_t) matrix — used by incremental
    maintenance to evaluate masks for just a delta's rows (same columns,
    arbitrary row subset)."""
    fm = schema.featmat[table] if featmat is None else featmat
    return schema.local_feature_ids[table], _own_columns(fm)


def _own_columns(fm: jnp.ndarray) -> jnp.ndarray:
    """``fm``, or one column of zeros for a table with no feature: every
    split is foreign to such a table, so no node reads the column, and a
    gather needs one to read from."""
    return fm if fm.shape[1] else jnp.zeros((fm.shape[0], 1), fm.dtype)


def descend_masks_level(
    schema: Schema, table: str, feat: jnp.ndarray, thr: jnp.ndarray, masks: jnp.ndarray,
    featmat=None,
) -> jnp.ndarray:
    """One level of mask refinement for ``table``.

    feat/thr: (K,) this level's chosen splits; masks: (K, n_rows) →
    (2K, n_rows).  Constraints on foreign features pass both children
    through; dead nodes (feat = -1, thr = +inf) route everything left.
    """
    g2l, fm = _local_feature_view(schema, table, featmat)
    local = jnp.take(g2l, jnp.maximum(feat, 0)) * jnp.where(feat >= 0, 1, 0) + jnp.where(
        feat >= 0, 0, -1
    )
    mine = local >= 0
    vals = jnp.take(fm, jnp.maximum(local, 0), axis=1).T        # (K, n)
    cond = vals >= thr[:, None]                                  # (K, n)
    left = masks & (~mine[:, None] | ~cond)
    # a dead node owns no feature in any table, so the pass-through above
    # would put its rows in both children: its right child is empty
    right = masks & (feat >= 0)[:, None] & (~mine[:, None] | cond)
    return jnp.stack([left, right], axis=1).reshape(-1, masks.shape[-1])


def root_masks(schema: Schema, table: str, n_rows: int = None) -> jnp.ndarray:
    n = schema.table(table).n_rows if n_rows is None else n_rows
    return jnp.ones((1, n), jnp.bool_)


def leaf_masks(schema: Schema, table: str, tree: TreeArrays, featmat=None) -> jnp.ndarray:
    """(L, n_rows) bool: per-table projection of every leaf's J^{(ℓ)}.

    With ``featmat`` (k, d_t), masks are evaluated for those k feature
    rows instead of the whole stored table (the per-row ops are identical,
    so subset rows match the full-table pass bit-for-bit)."""
    m = root_masks(schema, table,
                   None if featmat is None else int(featmat.shape[0]))
    for level in range(tree.depth):
        feat, thr = tree.level_slice(level)
        m = descend_masks_level(schema, table, feat, thr, m, featmat)
    return m


def _pick_columns(fm: jnp.ndarray, col: jnp.ndarray) -> jnp.ndarray:
    """``fm[r, col[a]]`` for every row r and lane a: a select chain over
    ``fm``'s few columns, an exact copy that stays elementwise where a
    gather along the minor axis does not."""
    vals = fm[:, :1]
    for j in range(1, int(fm.shape[1])):
        vals = jnp.where(col == j, fm[:, j:j + 1], vals)
    return vals


def stacked_leaf_masks(g2l, fm: jnp.ndarray, trees: List[TreeArrays]) -> jnp.ndarray:
    """(n_rows, Σ leaves) bool: every tree's :func:`leaf_masks` over the
    feature rows ``fm`` (n_rows, d_t), concatenated tree-major and
    transposed, built in that layout.

    Leaf a of a depth-D tree holds row r iff the terms of its D
    ancestors all hold, ANDed root first — the chain
    :func:`descend_masks_level` builds level by level: at a node the
    left child's term is ``~mine | ~cond`` and the right child's
    ``alive & (~mine | cond)``, with ``cond = vals >= thr``.  Leaves lie
    along the minor axis, each lane reading its ancestors' splits, so a
    level is one elementwise pass over (n_rows, leaves): no gather of
    rows, no interleave of children and no transpose.  Trees of one
    depth share a pass."""
    fm, out = _own_columns(fm), []
    for depth, run in itertools.groupby(trees, key=lambda t: t.depth):
        run = list(run)
        feat = jnp.concatenate([t.feat for t in run])
        thr = jnp.concatenate([t.thr for t in run])
        local = jnp.take(g2l, jnp.maximum(feat, 0)) * jnp.where(feat >= 0, 1, 0) + jnp.where(
            feat >= 0, 0, -1
        )
        mine, alive, col = local >= 0, feat >= 0, jnp.maximum(local, 0)
        L = 1 << depth
        tree = np.repeat(np.arange(len(run)), L)          # each lane's tree,
        leaf = np.tile(np.arange(L), len(run))            # and leaf in it
        m = jnp.ones((fm.shape[0], len(run) * L), jnp.bool_)
        for level in range(depth):
            node = tree * (L - 1) + (1 << level) - 1 + (leaf >> (depth - level))
            right = ((leaf >> (depth - 1 - level)) & 1).astype(bool)
            vals = _pick_columns(fm, col[node])
            cond = vals >= thr[node]
            term = jnp.where(right, alive[node] & (~mine[node] | cond),
                             ~mine[node] | ~cond)
            m = m & term
        out.append(m)
    return jnp.concatenate(out, axis=1)


def all_tables_leaf_masks(schema: Schema, tree: TreeArrays) -> Dict[str, jnp.ndarray]:
    return {t.name: leaf_masks(schema, t.name, tree) for t in schema.tables}
