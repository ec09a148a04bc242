"""Compile rehearsals of the relational path's Pallas kernels, and of its
serving factor program, for a TPU v5e.

The TPU compiler is installed alongside JAX, so each kernel is lowered
and compiled here for a *described* v5e:2x2 topology with no chip
attached, at the main path's real widths (a 2^20-row fact table, 128
stacked leaf channels, sketch k = 256).  Interpret-mode tests cannot
catch a block shape Mosaic refuses or a block set that overflows VMEM;
these do.  Each compiled kernel program must hold the kernel as a
``tpu_custom_call``, not a fallback.

The topology is described inside a module fixture (never at import):
only one process may load the TPU library, and under several test
workers the one given this file is the one that loads it.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N_ROWS = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # noqa: BLE001 — no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n_keys", [64, 2048, 65536])
def test_segment_sum_2d_compiles_for_tpu(one_chip, n_keys):
    from repro.kernels.segment_sum.segment_sum import segment_sum_2d

    hlo = _compile(lambda v, i: segment_sum_2d(v, i, n_keys, interpret=False),
                   one_chip, ((N_ROWS, 128), jnp.float32),
                   ((N_ROWS,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_count_sketch_compiles_for_tpu(one_chip):
    from repro.kernels.count_sketch.count_sketch import count_sketch

    hlo = _compile(lambda x, b, s: count_sketch(x, b, s, 256, interpret=False),
                   one_chip, ((N_ROWS,), jnp.float32), ((N_ROWS,), jnp.int32),
                   ((N_ROWS,), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_factor_program_compiles_for_tpu_in_one_pass(one_chip):
    """The serving factor program at the rescore's LINEITEM shape (2^20
    rows, 11 of 24 features, 8 depth-4 trees) compiles for a v5e and
    needs no temporary buffer: each leaf's mask is built in the factor's
    own (rows, leaves) layout, with no level-by-level intermediates."""
    from repro.core.tree import TreeArrays
    from repro.serving.compile import _factor_program

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=one_chip)

    trees = [TreeArrays(shape((15,), jnp.int32), shape((15,), jnp.float32),
                        shape((16,), jnp.float32)) for _ in range(8)]
    views = (("lineitem", tuple(range(11)) + (-1,) * 13),)
    compiled = _factor_program.lower(
        trees, (shape((N_ROWS, 11), jnp.float32),), views=views,
        dtype=jnp.dtype(jnp.float32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes == 0
