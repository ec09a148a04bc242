"""Pallas TPU kernel: fused blockwise (flash) attention forward.

The perf-critical layer of every assigned transformer.  Grid is
(batch·heads, q_blocks, kv_blocks); TPU executes the grid sequentially
minor-to-major, so the kv axis revisits the same output block while the
running max `m`, denominator `l`, and accumulator live in VMEM scratch —
the textbook online-softmax recurrence, never materializing (S × S)
scores in HBM.

VMEM per program (qc = kc = 128, dh = 128, f32):
  q (qc,dh) + k,v (kc,dh) + acc (qc,dh) + m,l (qc) + s/p (qc,kc)
  ≈ 4 · 128·128 · 4 B + … ≈ 0.35 MB  → far under budget; the q/kv tile
  pair can be raised to 512/1024 on v5e for better MXU utilization
  (block shapes are parameters).

Causality skips nothing in the grid (masked instead) — a known ~2×
upper-bound on wasted work for causal shapes; the masked-block-skip
refinement is a TODO recorded in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret


def _kernel(q_ref, k_ref, v_ref, o_ref, acc, m, l, *, causal, qc, kc, nk, scale):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, -jnp.inf)
        l[...] = jnp.zeros_like(l)

    q = q_ref[0].astype(jnp.float32) * scale           # (qc, dh)
    k = k_ref[0].astype(jnp.float32)                   # (kc, dh)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (qc, kc)
    if causal:
        qpos = qi * qc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 0)
        kpos = ki * kc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 1)
        s = jnp.where(qpos >= kpos, s, -1e30)

    m_new = jnp.maximum(m[...], s.max(-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m[...] - m_new)
    l[...] = l[...] * corr + p.sum(-1)
    acc[...] = acc[...] * corr[:, None] + jnp.dot(
        p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32
    )
    m[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc[...] / jnp.maximum(l[...][:, None], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "q_block", "kv_block", "interpret")
)
def flash_attention(q, k, v, causal: bool = True, q_block: int = 128,
                    kv_block: int = 128, interpret=None):
    """q/k/v: (BH, S, dh) → (BH, S, dh).  S padded to block multiples
    (padding keys are masked out by the causal/position test when causal;
    for non-causal the caller must pass S % kv_block == 0)."""
    BH, S, dh = q.shape
    qc = min(q_block, S)
    kc = min(kv_block, S)
    pad_q = (-S) % qc
    pad_k = (-S) % kc
    assert causal or (pad_q == 0 and pad_k == 0)
    if pad_q or pad_k:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    nq = q.shape[1] // qc
    nk = k.shape[1] // kc
    out = pl.pallas_call(
        functools.partial(
            _kernel, causal=causal, qc=qc, kc=kc, nk=nk,
            scale=1.0 / np.sqrt(dh),
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, qc, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kc, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, kc, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, qc, dh), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((qc, dh), jnp.float32),
            pltpu.VMEM((qc,), jnp.float32),
            pltpu.VMEM((qc,), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
    return out[:, :S]
