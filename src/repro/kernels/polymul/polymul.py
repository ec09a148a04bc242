"""Pallas TPU kernel: batched circular polynomial multiplication mod z^k.

The ⊗ of the paper's sketch semiring (§3).  TPU adaptation: instead of
the paper's FFT (O(k log k), latency-bound on the VPU for the k ≤ 1024
regime the sketch uses), each product row is a **circulant matmul** on
the MXU: c = a ⊛ b = C(a)·b where C(a)[i, j] = a[(i − j) mod k].  The
systolic array runs k×k×batch MACs at peak; for k ≤ 1024 this beats an
FFT pipeline and needs no complex support.

Grid: one program per batch tile.  VMEM per program:
  a-tile (bt, k) + b-tile (bt, k) + circulant (k, k) + out (bt, k)
  = (2·bt·k + k² + bt·k) · 4 B ≤ ~0.5 MB at bt=64, k=256 — well inside
  the ~16 MB VMEM budget; k is padded to the 128-lane boundary upstream.

Building C(a) in-kernel: broadcasted-iota row/col indices and a
jnp.take along the (i−j) mod k index.  Mosaic does not lower that
gather (compiling for a TPU v5e fails with "Shape mismatch in input,
indices and output"), so the kernel runs in interpret mode only.  No
relational path calls it: PolyCoeff/PolyFreq multiply through the FFT
(core/semiring.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret


def _kernel(a_ref, b_ref, o_ref, *, k: int):
    a = a_ref[...]                                     # (bt, k)
    b = b_ref[...]                                     # (bt, k)
    ii = jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)
    idx = jnp.mod(ii - jj, k)                          # (k, k) circulant index

    def one(row_a, row_b):
        C = jnp.take(row_a, idx, axis=0)               # (k, k) circulant of a
        return jnp.dot(C, row_b, preferred_element_type=jnp.float32)

    o_ref[...] = jax.vmap(one)(a, b).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("batch_tile", "interpret"))
def poly_mul(a: jnp.ndarray, b: jnp.ndarray, batch_tile: int = 8,
             interpret=None) -> jnp.ndarray:
    """a, b: (B, k) → (B, k) circular products.  k should be a power of
    two (the sketch guarantees this); B is padded to the tile."""
    B, k = a.shape
    bt = min(batch_tile, B)
    pad = (-B) % bt
    if pad:
        a = jnp.pad(a, ((0, pad), (0, 0)))
        b = jnp.pad(b, ((0, pad), (0, 0)))
    grid = (a.shape[0] // bt,)
    out = pl.pallas_call(
        functools.partial(_kernel, k=k),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, k), lambda i: (i, 0)),
            pl.BlockSpec((bt, k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bt, k), lambda i: (i, 0)),
        interpret=resolve_interpret(interpret),
    )(a, b)
    return out[:B]
