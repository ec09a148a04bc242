"""Where the launch CLIs and `chip_smoke.py` keep JAX's compile cache.

`configure_compile_cache` must run before jax is imported, so each case
runs in a fresh interpreter."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = (
    "from repro.launch._devices import apply_early_device_flags\n"
    "apply_early_device_flags([])\n"
    "import jax\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def _cache_dir_seen_by_jax(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", PROBE], env=env, cwd=ROOT,
                         capture_output=True, text=True, check=True)
    return out.stdout.strip().splitlines()[-1]


@pytest.mark.parametrize("from_outside", [False, True])
def test_compile_cache_placement(tmp_path, from_outside):
    """A directory set from outside is used as is; otherwise the cache is
    the fixed `.jax_cache/` at the checkout root."""
    given = str(tmp_path / "cache") if from_outside else None
    want = given or os.path.join(ROOT, ".jax_cache")
    assert _cache_dir_seen_by_jax(given) == want
