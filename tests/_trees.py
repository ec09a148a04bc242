"""Random ensembles over a schema's features, for tests of the factor
build that need no training: thresholds are drawn from the feature's own
values (so ties with ``>=`` occur), every tree has a dead node, and the
first tree splits on the first table's features alone (foreign to every
other table)."""
import numpy as np
import jax.numpy as jnp

from repro.core.schema import Schema
from repro.core.tree import TreeArrays


def random_trees(sch: Schema, depths, seed: int):
    rng = np.random.default_rng(seed)
    own0 = [g for g, (ti, _) in enumerate(sch.feat_global) if ti == 0]
    trees = []
    for i, depth in enumerate(depths):
        n_nodes = (1 << depth) - 1
        pool = own0 if i == 0 else range(sch.n_features)
        feat = rng.choice(pool, n_nodes).astype(np.int32)
        thr = np.empty(n_nodes, np.float32)
        for k, g in enumerate(feat):
            ti, li = sch.feat_global[g]
            col = np.asarray(sch.featmat[sch.tables[ti].name][:, li])
            thr[k] = col[rng.integers(len(col))]
        dead = rng.integers(n_nodes)
        feat[dead], thr[dead] = -1, np.inf
        trees.append(TreeArrays(
            feat=jnp.asarray(feat), thr=jnp.asarray(thr),
            leaf=jnp.asarray(rng.standard_normal(1 << depth), jnp.float32)))
    return trees
