"""Pallas TPU kernels of the relational hot path (one-hot-matmul
segment-⊕ and count sketch) plus the LM substrate's kernels.

Every entry point takes ``interpret=None``: the kernel compiles for the
chip when JAX's default backend is a TPU and runs in the Pallas
interpreter everywhere else, so CPU tests exercise the same BlockSpec
tiling without asking for it."""
from __future__ import annotations

import jax


def resolve_interpret(interpret=None) -> bool:
    """``interpret`` as given, else True exactly when not on a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
