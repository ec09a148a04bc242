"""Pre-JAX-import environment for the launch CLIs and `chip_smoke.py`.

Two settings only take effect if they are in the environment **before**
the first `import jax` anywhere in the process:

- the persistent compilation cache (:func:`configure_compile_cache`):
  `$JAX_COMPILATION_CACHE_DIR` when set, else a fixed `.jax_cache/` at
  the checkout root, so a second run on the same machine skips the
  compiles the first one paid for;
- `--devices N`, which multiplies one host CPU into N XLA devices via
  `--xla_force_host_platform_device_count` — the standard way to prove
  mesh-sharded programs without hardware.

So each CLI module calls :func:`apply_early_device_flags` as its very
first import, ahead of every `repro.*` import that pulls jax in.
(`python -m repro.launch.X` executes no package-level code first:
`repro`/`repro.launch` are namespace packages.)

This module itself must therefore import nothing but the stdlib.
"""
from __future__ import annotations

import os
import sys
import warnings

CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    A `JAX_COMPILATION_CACHE_DIR` set from outside is used as is;
    otherwise the cache is `.jax_cache/` at the checkout root.  The path
    is fixed (no temporary name, pid or time in it) because it is part
    of the cache's key.  Must run before jax is imported."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            CHECKOUT_ROOT, ".jax_cache")
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


def apply_early_device_flags(argv=None) -> int:
    """Place the compile cache, then scan argv for ``--devices N`` /
    ``--devices=N`` and, when found, append the forced-host-device flag
    to ``XLA_FLAGS``.  Returns the requested count (0 = flag absent,
    leave the platform alone).

    Must run before jax is imported; if it already is, the request
    cannot take effect and a warning says so instead of silently running
    single-device.
    """
    configure_compile_cache()
    argv = list(sys.argv[1:] if argv is None else argv)
    n = 0
    for i, a in enumerate(argv):
        if a == "--devices" and i + 1 < len(argv):
            n = int(argv[i + 1])
            break
        if a.startswith("--devices="):
            n = int(a.split("=", 1)[1])
            break
    if n <= 0:
        return 0
    if "jax" in sys.modules:
        warnings.warn(
            "--devices ignored: jax was already imported before the "
            "device flag could be applied (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} in the "
            "environment instead)")
        return 0
    flag = f"--xla_force_host_platform_device_count={n}"
    prev = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in prev:
        os.environ["XLA_FLAGS"] = (prev + " " + flag).strip()
    return n


def add_device_args(ap) -> None:
    """Register the shared --devices/--mesh arguments on a CLI parser.

    --devices is consumed by :func:`apply_early_device_flags` before
    argparse runs; it is declared here so it shows in --help and
    round-trips cleanly.  --mesh N runs the workload data-parallel over
    the first N visible devices (0 = single-device, the default;
    -1 = all visible devices).
    """
    ap.add_argument("--devices", type=int, default=0, metavar="N",
                    help="force N host XLA devices (CPU proof recipe; "
                         "applied before jax import)")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="shard factors/featmats data-parallel over N "
                         "devices (0 = off, -1 = all visible)")


def resolve_mesh(args):
    """Build the data mesh an argparse namespace asks for (or None).

    Imports jax lazily — safe to call only after
    :func:`apply_early_device_flags` has run.
    """
    n = getattr(args, "mesh", 0)
    if not n:
        return None
    from .mesh import make_data_mesh

    return make_data_mesh(None if n < 0 else n)
