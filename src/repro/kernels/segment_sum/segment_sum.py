"""Pallas TPU kernel: multi-channel segment-⊕ (dense-key segment sum).

The serving scorer's one-pass SumProd evaluation is dominated by the
join-tree edge messages ``msg[key, c] = Σ_{r : ids[r]=key} vals[r, c]``
over stacked leaf channels c.  Like count_sketch, a random scatter-add
serializes through scalar memory on TPU, so the kernel reformulates each
row tile's contribution as a **one-hot × value matmul** on the MXU:

    msg[kblk, c] += onehot(kblk, tile) · vals[tile, c]

The grid is (key blocks, row tiles).  Each key block's (kb, c) output
block stays resident while the inner axis walks the row tiles and
accumulates in place (Pallas runs the grid in order on TPU, so the
read-modify-write is safe); the one-hot is built per (key block, tile),
so VMEM holds (kb × tile) + (tile × c) + (kb × c) floats whatever the
number of keys — about 6 MB at kb = tile = 1024, c = 128.  The row ids
travel as a (1, n) row so their block is lane-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import resolve_interpret

TILE = 1024          # rows per grid step
KEY_BLOCK = 1024     # keys per output block


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _kernel(i_ref, v_ref, o_ref):
    kblk, t = pl.program_id(0), pl.program_id(1)
    kb, tile = o_ref.shape[0], i_ref.shape[1]
    keys = kblk * kb + jax.lax.broadcasted_iota(jnp.int32, (kb, tile), 0)
    oh = (keys == i_ref[...]).astype(jnp.float32)            # (kb, tile)
    contrib = jnp.dot(oh, v_ref[...], preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)   # (kb, c)

    @pl.when(t == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += contrib


@functools.partial(jax.jit, static_argnames=("n_keys", "interpret"))
def segment_sum_2d(vals: jnp.ndarray, ids: jnp.ndarray, n_keys: int,
                   interpret=None) -> jnp.ndarray:
    """vals: (n, c) f32, ids: (n,) int32 in [0, n_keys) → (n_keys, c).

    Rows are padded to the tile and keys to the key block; padded rows
    carry value 0 so they add nothing to whatever key they name."""
    n, c = vals.shape
    tile = min(TILE, _round_up(max(n, 1), 128))
    kb = min(KEY_BLOCK, _round_up(n_keys, 8))
    n_pad, k_pad = _round_up(max(n, 1), tile), _round_up(n_keys, kb)
    vals = jnp.pad(vals.astype(jnp.float32), ((0, n_pad - n), (0, 0)))
    ids = jnp.pad(ids.astype(jnp.int32), (0, n_pad - n)).reshape(1, n_pad)
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((k_pad, c), jnp.float32),
        grid=(k_pad // kb, n_pad // tile),
        in_specs=[
            pl.BlockSpec((1, tile), lambda k, t: (0, t)),
            pl.BlockSpec((tile, c), lambda k, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((kb, c), lambda k, t: (k, 0)),
        interpret=resolve_interpret(interpret),
        name="segment_sum_2d",
    )(ids, vals)
    return out[:n_keys]
