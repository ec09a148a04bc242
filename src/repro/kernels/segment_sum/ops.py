"""jit'd wrapper: semiring-facing segment-⊕ entry point.

Handles the 1-D (Arithmetic) and 2-D (Channels) value layouts the
SumProd engine produces; higher-rank (complex/poly) values fall back to
the jnp oracle — the kernel targets the serving scorer's stacked-leaf
Channels evaluation.
"""
from __future__ import annotations

import jax.numpy as jnp

from .ref import segment_sum_ref  # noqa: F401
from .segment_sum import segment_sum_2d


def segment_sum_op(vals: jnp.ndarray, ids: jnp.ndarray, n_keys: int,
                   interpret=None) -> jnp.ndarray:
    if vals.ndim == 1:
        return segment_sum_2d(vals[:, None], ids, n_keys, interpret=interpret)[:, 0]
    if vals.ndim == 2 and vals.dtype in (jnp.float32, jnp.bfloat16):
        return segment_sum_2d(vals, ids, n_keys, interpret=interpret)
    return segment_sum_ref(vals, ids, n_keys)
