"""Compile a trained ensemble into a one-pass relational scorer.

The seed scoring path (``Booster.predict_grouped``) walks tree × leaf
inside a ``fori_loop`` and issues one Arithmetic SumProd pass per leaf
per tree — O(n_trees · L) sequential inside-out passes per request.
Serving inverts that: compilation stacks **every leaf of every tree**
into one channel axis.

For each table T_t the per-leaf membership masks (L, n_rows) of all
trees concatenate into a single (total_leaves, n_rows) array; its
transpose, cast to ``factor_dtype``, is T_t's factor in a
``Channels(total_leaves)`` product semiring.  All tables' factors come
from ONE program keyed by shapes alone (``_factor_program``): the trees
and the feature matrices are its arguments, so a new ensemble or a new
dataset of the same shapes reuses it.  ONE inside-out pass grouped by
ρ's table then yields

    counts[ρ, a] = |{x ∈ ρ ⋈ J : x in leaf a}|        (all a at once)

and the served quantities are two dense contractions:

    Σŷ[ρ]  = counts[ρ, :] @ leaf_values                 (boosted sum)
    |ρ⋈J|  = Σ_{a ∈ leaves of tree 0} counts[ρ, a]      (any one tree
              partitions J, so its leaf counts sum to the group size)

SumProd evaluations per request drop from n_trees·L + 1 to **1**; the
wide segment-⊕ that remains is a dense (n_rows, total_leaves) segment
sum — optionally routed through the Pallas one-hot-matmul kernel
(`kernels/segment_sum`, same MXU reformulation as `count_sketch`).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.schema import Schema
from ..core.semiring import Channels
from ..core.sumprod import QueryCounter, SumProd
from ..core.tree import TreeArrays, stacked_leaf_masks
from ..distributed import spmd
from ..obs import get_registry, scope


@dataclasses.dataclass(frozen=True)
class KernelChannels(Channels):
    """Channels semiring whose segment-⊕ runs on the Pallas kernel.

    Under an active multi-device data mesh the Pallas route falls back to
    the dense ``segment_sum`` — `pallas_call` is a single-device program
    and would force an all-gather of the row-sharded factor; the XLA
    scatter path partitions cleanly instead.  The kernel runs in the
    Pallas interpreter only off the TPU (`kernels.resolve_interpret`)."""

    def segment_add(self, vals, segment_ids, num_segments):
        from ..kernels.segment_sum.ops import segment_sum_op

        if (vals.ndim == 2 and vals.dtype == jnp.float32
                and spmd.data_axis_size() <= 1):
            return segment_sum_op(vals, segment_ids, num_segments)
        return super().segment_add(vals, segment_ids, num_segments)


@partial(jax.jit, static_argnames="tree0_leaves")
def contract_leaves(counts: jnp.ndarray, leaf_values: jnp.ndarray,
                    tree0_leaves: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(Σŷ, |ρ⋈J|) per row from grouped leaf counts (n_g, A).

    The leaf-value contraction is an explicitly sequenced per-row FMA
    chain in f32, never a matmul: each output row reads only its own
    counts row, so row sharding cannot move the bits (a gemv's blocking
    varies with the local row count), and a TPU cannot round the leaf
    values to bf16 as its default-precision f32 matmul does.  Counts
    are integer-valued, so the cnt reduction is exact in any order."""
    tot = counts[:, 0] * leaf_values[0]
    for j in range(1, int(leaf_values.shape[0])):
        tot = tot + counts[:, j] * leaf_values[j]
    cnt = jnp.sum(counts[:, :tree0_leaves], axis=1)
    return tot.astype(jnp.float32), cnt.astype(jnp.float32)


@partial(jax.jit, static_argnames=("views", "dtype"))
def _factor_program(trees: List[TreeArrays], featmats: Tuple[jnp.ndarray, ...],
                    views, dtype):
    """((n_rows, total_leaves) factor per table of ``views``, leaf values).

    Keyed by structure alone: table names and feature maps (``views``),
    ``dtype``, and the shapes of the arguments (trees, depths, rows,
    feature widths).  Trees and feature matrices are arguments, so a new
    ensemble or dataset of the same shapes reuses the program.  Each
    trace bumps the counter ``serve.factor_programs``."""
    get_registry().counter("serve.factor_programs").inc()
    factors = tuple(
        stacked_leaf_masks(np.asarray(g2l, np.int32), fm, trees).astype(dtype)
        for (_, g2l), fm in zip(views, featmats))
    leaf_values = jnp.concatenate([t.leaf for t in trees]).astype(jnp.float32)
    return factors, leaf_values


def _stacked_factors(schema: Schema, trees: List[TreeArrays],
                     featmats: Dict[str, jnp.ndarray], dtype):
    """The factor program over ``featmats`` (table → feature rows):
    ({table: factor}, leaf values).  The tables' feature maps go in as
    plain ints, the structural half of the program's key."""
    views = tuple((t, tuple(int(g) for g in schema.local_feature_ids[t]))
                  for t in featmats)
    factors, leaf_values = _factor_program(
        list(trees), tuple(featmats.values()), views=views, dtype=jnp.dtype(dtype))
    return dict(zip(featmats, factors)), leaf_values


def stack_table_factor(
    schema: Schema,
    trees: List[TreeArrays],
    table: str,
    featmat: Optional[jnp.ndarray] = None,
    dtype=jnp.float32,
) -> jnp.ndarray:
    """Stacked leaf-mask factor for one table: (n_rows, total_leaves),
    built by the shape-keyed factor program with the trees and the
    feature matrix as its arguments.

    With ``featmat`` (k, d_t), only those k feature rows are evaluated —
    the per-row factor slice incremental maintenance scatters back into a
    live factor after a delta."""
    fm = schema.featmat[table] if featmat is None else featmat
    return _stacked_factors(schema, trees, {table: fm}, dtype)[0][table]


@dataclasses.dataclass
class CompiledEnsemble:
    """A trained ensemble lowered to single-pass relational scoring.

    factors: per-table (n_rows, total_leaves) — stacked leaf masks, ready
    to drop into a Channels(total_leaves) SumProd query.  ``factor_dtype``
    selects their storage dtype: f32 (exact counts) or bf16 (masks are
    0/1, so bf16 halves factor memory at a small count error bounded by
    the 8-bit mantissa — served totals stay within benchmark tolerance).

    ``data_version`` is bumped by whoever mutates served state in place
    (incremental/maintain.py) — caches keyed on it can never serve stale
    scores after a delta.

    ``mesh``: data mesh captured at compile time (ambient
    `spmd.current_data_mesh()` by default).  Factors are placed
    row-sharded over its data axis and flow as jit *arguments*, so the
    sharding sticks; leaf values replicate; the SumProd message
    emissions inside the pass are the collective point (`psum_message`),
    so grouped outputs come back replicated and bit-equal to
    single-device (0/1 leaf-mask counts are integer-exact under the
    cross-shard re-association).  ``mesh=None`` is the plain
    single-device program.
    """

    schema: Schema
    trees: List[TreeArrays]
    leaf_values: jnp.ndarray               # (total_leaves,)
    factors: Dict[str, jnp.ndarray]        # table → (n_rows, total_leaves)
    tree0_leaves: int                      # leaves of tree 0 (for counts)
    use_kernel: bool = False
    counter: Optional[QueryCounter] = None
    factor_dtype: "jnp.dtype" = jnp.float32
    data_version: int = 0
    mesh: Optional[object] = None          # jax.sharding.Mesh | None

    def __post_init__(self):
        self._sp = SumProd(self.schema)
        self._sem = (
            KernelChannels(self.total_leaves, self.factor_dtype)
            if self.use_kernel else Channels(self.total_leaves, self.factor_dtype)
        )
        self._score_fns: Dict[str, callable] = {}
        self._grouped: Dict[str, Tuple[jnp.ndarray, jnp.ndarray]] = {}
        if spmd.data_axis_size(self.mesh) > 1:
            self.factors = spmd.shard_factors(self.factors, self.mesh)
            self.leaf_values = spmd.replicate_put(self.leaf_values, self.mesh)

    def device_count(self) -> int:
        """Data-axis width this ensemble is sharded over (1 = unsharded)."""
        return spmd.data_axis_size(self.mesh)

    @property
    def total_leaves(self) -> int:
        return int(self.leaf_values.shape[0])

    @property
    def n_trees(self) -> int:
        return len(self.trees)

    def n_rows(self, table: str) -> int:
        """Row-id domain of ``table``'s factor (== schema n_rows here;
        capacity for maintained scorers — keeps id validation duck-typed)."""
        return int(self.factors[table].shape[0])

    # ----------------------------------------------------------- scoring --
    def _score_fn(self, group_by: str):
        """One-pass scorer program for one grouping table, built ahead of
        time on first use (``serve.scorer_build``: trace, lower, compile
        or load from the cache) and kept.  Each build bumps the counter
        ``serve.scorer_programs`` and adds its host milliseconds to the
        histogram ``serve.scorer_build_ms``."""
        if group_by not in self._score_fns:
            sp, sem, L0 = self._sp, self._sem, self.tree0_leaves

            mesh = self.mesh

            def run(factors, vals):
                counts = sp(sem, factors, group_by=group_by)   # (n_g, A)
                # the rows stay sharded through the whole pass; only the
                # two (n_g,) results are gathered back
                with scope("serve.contract"):
                    tot, cnt = contract_leaves(counts, vals, L0)
                return (spmd.replicate(tot, mesh),
                        spmd.replicate(cnt, mesh))

            t0 = time.perf_counter()
            with scope("serve.scorer_build", group_by=group_by):
                self._score_fns[group_by] = jax.jit(run).lower(
                    self.factors, self.leaf_values).compile()
            reg = get_registry()
            reg.counter("serve.scorer_programs").inc()
            reg.histogram("serve.scorer_build_ms").observe(
                (time.perf_counter() - t0) * 1e3)
        return self._score_fns[group_by]

    def score_grouped(self, group_by: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(Σŷ, |ρ⋈J|) per row of ``group_by`` — ONE SumProd evaluation."""
        if self.counter is not None:
            self.counter.bump(1)
        # the build must see this ensemble's mesh — psum_message inside
        # the pass reads the ambient context at trace time
        with spmd.use_data_mesh(self.mesh):
            run = self._score_fn(group_by)
            with scope("serve.score", group_by=group_by):
                return run(self.factors, self.leaf_values)

    def grouped_cached(self, group_by: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Memoized full-table scores: tables are static per model version,
        so interactive row lookups reduce to gathers into this pass."""
        if group_by not in self._grouped:
            self._grouped[group_by] = self.score_grouped(group_by)
        return self._grouped[group_by]


def compile_ensemble(
    schema: Schema,
    trees: List[TreeArrays],
    use_kernel: bool = False,
    counter: Optional[QueryCounter] = None,
    factor_dtype=jnp.float32,
    mesh=None,
) -> CompiledEnsemble:
    """Stack per-table leaf masks across all trees into channel factors,
    every table's in one call of the shape-keyed factor program.

    ``mesh``: explicit data mesh, or None to capture the ambient
    `spmd.use_data_mesh` context (still None outside any context —
    the plain single-device program)."""
    if not trees:
        raise ValueError("cannot compile an empty ensemble")
    with scope("serve.factor", tables=len(schema.featmat)):
        factors, leaf_values = _stacked_factors(schema, trees, schema.featmat,
                                                factor_dtype)
    return CompiledEnsemble(
        schema=schema,
        trees=list(trees),
        leaf_values=leaf_values,
        factors=factors,
        tree0_leaves=int(trees[0].leaf.shape[0]),
        use_kernel=use_kernel,
        counter=counter,
        factor_dtype=factor_dtype,
        mesh=mesh if mesh is not None else spmd.current_data_mesh(),
    )
