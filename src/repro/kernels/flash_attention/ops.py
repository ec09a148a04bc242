"""jit'd wrapper with GQA head grouping (matches models/layers shapes)."""
from __future__ import annotations

import jax.numpy as jnp

from .flash_attention import flash_attention
from .ref import flash_attention_ref  # noqa: F401


def flash_attention_gqa(q, k, v, causal=True, interpret=None,
                        q_block=128, kv_block=128):
    """q: (B, S, N, dh); k/v: (B, S, Kh, dh) → (B, S, N·dh)."""
    B, S, N, dh = q.shape
    Kh = k.shape[2]
    G = N // Kh
    qf = q.transpose(0, 2, 1, 3).reshape(B * N, S, dh)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * N, S, dh)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * N, S, dh)
    out = flash_attention(qf, kf, vf, causal=causal, interpret=interpret,
                          q_block=q_block, kv_block=kv_block)
    return out.reshape(B, N, S, dh).transpose(0, 2, 1, 3).reshape(B, S, N * dh)
