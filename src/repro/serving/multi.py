"""Batched multi-model scoring: A/B variants in one SumProd pass.

Compiled ensembles over the same schema differ only along the leaf
channel axis, so N variants stack into ONE factor set: per table the
(n_rows, A_m) factors concatenate to (n_rows, ΣA_m), one inside-out
pass yields every model's leaf counts at once, and the contraction
splits per model by slicing the channel axis — N models for the query
cost of one (the registry's A/B traffic no longer multiplies SumProd
evaluations by the number of live variants).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.semiring import Channels
from ..core.sumprod import QueryCounter, SumProd
from .compile import CompiledEnsemble, contract_leaves


@dataclasses.dataclass
class StackedEnsembles:
    """N compiled ensembles fused along the leaf channel axis."""

    ensembles: List[CompiledEnsemble]
    factors: Dict[str, jnp.ndarray]        # table → (n_rows, ΣA_m)
    leaf_values: jnp.ndarray               # (ΣA_m,)
    offsets: List[int]                     # model m spans [off[m], off[m+1])
    counter: Optional[QueryCounter] = None

    def __post_init__(self):
        self.schema = self.ensembles[0].schema
        # pin the constituents' data_versions at stack time: the stacked
        # factor set is immutable, and scores computed from it belong to
        # exactly these versions even if a constituent MaintainedScorer-
        # derived ensemble is later replaced under the same registry slot
        self.data_versions = tuple(
            getattr(e, "data_version", 0) for e in self.ensembles)
        self._sp = SumProd(self.schema)
        self._sem = Channels(int(self.leaf_values.shape[0]),
                             self.factors[self.schema.names[0]].dtype)
        self._score_fns: Dict[str, callable] = {}

    @property
    def n_models(self) -> int:
        return len(self.ensembles)

    def _score_fn(self, group_by: str):
        if group_by not in self._score_fns:
            sp, sem = self._sp, self._sem
            spans = [(self.offsets[m], self.offsets[m + 1],
                      self.ensembles[m].tree0_leaves)
                     for m in range(self.n_models)]

            @jax.jit
            def run(factors, vals):
                counts = sp(sem, factors, group_by=group_by)   # (n_g, ΣA)
                out = []
                for (lo, hi, l0) in spans:
                    out.append(contract_leaves(counts[:, lo:hi],
                                               vals[lo:hi], l0))
                return out

            self._score_fns[group_by] = run
        return self._score_fns[group_by]

    def score_grouped(self, group_by: str) -> List[Tuple[jnp.ndarray, jnp.ndarray]]:
        """Per-model [(Σŷ, |ρ⋈J|)] for every row of ``group_by`` — ONE
        SumProd evaluation for all N models."""
        if self.counter is not None:
            self.counter.bump(1)
        return self._score_fn(group_by)(self.factors, self.leaf_values)


def stack_ensembles(
    ensembles: List[CompiledEnsemble],
    counter: Optional[QueryCounter] = None,
) -> StackedEnsembles:
    """Concatenate N same-schema ensembles' leaf axes into one factor set."""
    if not ensembles:
        raise ValueError("need at least one ensemble to stack")
    sch = ensembles[0].schema
    for e in ensembles:
        # a MaintainedScorer's capacity-padded factors and dynamic key
        # dictionaries don't fit the static join tree this pass uses —
        # stack a static snapshot (compile_ensemble over its effective
        # tables) instead
        bad = [t.name for t in e.schema.tables
               if e.factors[t.name].shape[0] != t.n_rows]
        if bad:
            raise ValueError(
                f"cannot stack a maintained/padded scorer (factor rows ≠ "
                f"schema rows for {bad}); compile a static snapshot first"
            )
    shape0 = {t: f.shape[0] for t, f in ensembles[0].factors.items()}
    for e in ensembles[1:]:
        if {t: f.shape[0] for t, f in e.factors.items()} != shape0:
            raise ValueError(
                "stacked ensembles must share one schema (factor row "
                "domains differ)"
            )
    dtype = (jnp.bfloat16 if all(e.factor_dtype == jnp.bfloat16 for e in ensembles)
             else jnp.float32)
    factors = {
        t.name: jnp.concatenate(
            [e.factors[t.name].astype(dtype) for e in ensembles], axis=1
        )
        for t in sch.tables
    }
    leaf_values = jnp.concatenate([e.leaf_values for e in ensembles])
    offsets = [0]
    for e in ensembles:
        offsets.append(offsets[-1] + e.total_leaves)
    return StackedEnsembles(
        ensembles=list(ensembles), factors=factors,
        leaf_values=leaf_values, offsets=offsets, counter=counter,
    )
