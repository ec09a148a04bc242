"""Pallas TPU kernel: chunked RWKV-6 WKV recurrence.

Grid (B·H, n_chunks): the chunk axis is sequential on TPU, so the
(hs × hs) f32 state lives in VMEM scratch and flows across chunk steps
— HBM traffic is exactly r/k/v/w in + out out (the memory-optimal
schedule for a linear recurrence).  Within a chunk all math is dense
(c × c and c × hs matmuls on the MXU) with the stable all-non-positive
exponent formulation from models/rwkv6.

VMEM per program (c = 16, hs = 64, f32):
  4 tiles (c, hs) + E (c, c, hs) + A (c, c) + state (hs, hs)
  ≈ (4·1k + 16k + 0.25k + 4k) · 4 B ≈ 100 KB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state, *, c, hs):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    r = r_ref[0]                                  # (c, hs) f32
    k = k_ref[0]
    v = v_ref[0]
    w = w_ref[0]                                  # log-decay ≤ 0
    u = u_ref[0]                                  # (1, hs) bonus

    cum = jnp.cumsum(w, axis=0)                   # (c, hs) ≤ 0
    cum_excl = cum - w
    # intra-chunk pairwise decays: all exponents ≤ 0 → stable
    E = jnp.exp(
        jnp.clip(cum_excl[:, None, :] - cum[None, :, :], -60.0, 0.0)
    )                                             # (c, c, hs)
    A = jnp.einsum("id,jd,ijd->ij", r, k, E)
    mask = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    A = jnp.where(mask, A, 0.0)
    diag = jnp.sum(r * u * k, axis=-1)            # (c,)
    out = jnp.dot(A, v, preferred_element_type=jnp.float32) + diag[:, None] * v
    rW = r * jnp.exp(cum_excl)
    out = out + jnp.dot(rW, state[...], preferred_element_type=jnp.float32)
    o_ref[0] = out.astype(o_ref.dtype)

    kW = k * jnp.exp(cum[-1:, :] - cum)
    state[...] = jnp.exp(cum[-1, :])[:, None] * state[...] + jnp.dot(
        kW.T, v, preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_chunk(r, k, v, logw, u, chunk: int = 16, interpret=None):
    """r/k/v/logw: (B, S, H, hs) f32; u: (H, hs).  S % chunk == 0.
    Returns (B, S, H, hs)."""
    B, S, H, hs = r.shape
    nc = S // chunk
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, hs)
    rf, kf, vf, wf = fold(r), fold(k), fold(v), fold(logw)
    uf = jnp.tile(u, (B, 1)).reshape(B * H, 1, hs)
    out = pl.pallas_call(
        functools.partial(_kernel, c=chunk, hs=hs),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hs), jnp.float32),
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hs), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, hs), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, hs), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, hs), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, hs), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, hs), lambda b, i: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((hs, hs), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(rf, kf, vf, wf, uf)
    return out.reshape(B, H, S, hs).transpose(0, 2, 1, 3)
