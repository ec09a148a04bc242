"""jit'd wrapper matching models/rwkv6.time_mix's call signature."""
from __future__ import annotations

from .rwkv6_chunk import rwkv6_chunk as _kernel
from .ref import rwkv6_chunk_ref  # noqa: F401


def rwkv6_chunk(r, k, v, logw, u, chunk: int = 16, interpret=None):
    return _kernel(r, k, v, logw, u, chunk=chunk, interpret=interpret)
