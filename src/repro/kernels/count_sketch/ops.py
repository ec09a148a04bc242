"""jit'd wrapper: count sketch from a Hash2 family (matches core.sketch)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.sketch import Hash2
from .count_sketch import count_sketch
from .ref import count_sketch_ref  # noqa: F401


def count_sketch_op(x: jnp.ndarray, h: Hash2, interpret=None) -> jnp.ndarray:
    idx = jnp.arange(x.shape[0])
    return count_sketch(x, h.bucket(idx), h.sign(idx), h.k, interpret=interpret)
