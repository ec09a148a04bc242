"""jit'd public wrapper: drop-in ⊗ for PolyCoeff factors of any batch rank."""
from __future__ import annotations

import jax.numpy as jnp

from .polymul import poly_mul
from .ref import poly_mul_ref  # noqa: F401  (re-exported oracle)


def poly_mul_op(a: jnp.ndarray, b: jnp.ndarray, interpret=None) -> jnp.ndarray:
    """Circular conv mod z^k over trailing axis; leading dims flattened
    into the kernel batch."""
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a, shape).reshape(-1, shape[-1])
    b = jnp.broadcast_to(b, shape).reshape(-1, shape[-1])
    return poly_mul(a, b, interpret=interpret).reshape(shape)
