"""Pallas TPU kernel: signed scatter-add into k buckets (count sketch).

Used by the sketch-semiring leaves and by gradient compression
(optim/grad_compress).  TPU adaptation: random scatter is slow on TPU
(serializes through scalar memory), so the kernel reformulates each
input tile's contribution as a **one-hot × value matmul** on the MXU:

    sketch[k] += Σ_t onehot(buckets[t])[k] · signs[t] · x[t]
               = (signs ⊙ x)[1, tile] · onehot[k, tile]ᵀ

The grid walks input tiles; the (1, k) output block is revisited across
grid steps and accumulated in place (Pallas runs the grid in order on
TPU, so the read-modify-write is safe).  Inputs travel as (1, n) rows so
their blocks are lane-aligned.  VMEM: two (1, tile) rows + the (k, tile)
f32 one-hot — 1 MB at tile = 1024, k = 256.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret

TILE = 1024


def _kernel(w_ref, b_ref, o_ref):
    t = pl.program_id(0)
    k, tile = o_ref.shape[1], b_ref.shape[1]
    oh = (jax.lax.broadcasted_iota(jnp.int32, (k, tile), 0)
          == b_ref[...]).astype(jnp.float32)                 # (k, tile)
    contrib = jax.lax.dot_general(
        w_ref[...], oh, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)                 # (1, k)

    @pl.when(t == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += contrib


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def count_sketch(x: jnp.ndarray, buckets: jnp.ndarray, signs: jnp.ndarray,
                 k: int, interpret=None) -> jnp.ndarray:
    """x/buckets/signs: (n,) → (k,).  n is padded to the tile; padded
    lanes carry weight 0 so they contribute nothing."""
    n = x.shape[0]
    tile = min(TILE, -(-max(n, 1) // 128) * 128)
    pad = (-n) % tile
    w = jnp.pad(x.astype(jnp.float32) * signs.astype(jnp.float32), (0, pad))
    b = jnp.pad(buckets.astype(jnp.int32), (0, pad))
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((1, k), jnp.float32),
        grid=(w.shape[0] // tile,),
        in_specs=[
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, k), lambda i: (0, 0)),
        interpret=resolve_interpret(interpret),
        name="count_sketch",
    )(w.reshape(1, -1), b.reshape(1, -1))
    return out[0]
