"""Compile the train cell's level programs for a described TPU v5e.

    JAX_PLATFORMS=cpu python benchmarks/chip/tools/compile_v5e.py \
        [--prev-leaves 48] [--shrink 1]

Builds the cell's ``Booster`` on the CPU at the training size, traces
its level step at every level for ``--prev-leaves`` previous leaves
(round 4 of the paper configuration: M = 48), and compiles each for one
chip of a described ``v5e:2x2`` without running it.  Prints each
program's ``memory_analysis`` against the chip's 15.75 GiB: what the
chip's compiler would refuse is found here at no chip time.
"""
from __future__ import annotations

import argparse
import os
import sys
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import core, models, program  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--prev-leaves", type=int, default=48)
    ap.add_argument("--shrink", type=int, default=1)
    ap.add_argument("--workload", default="tpch_star.train_round")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(core.ROOT / "src"))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.core import BoostConfig, Booster

    jax.config.update("jax_enable_compilation_cache", False)
    cell, cfg, traffic = core.cell_of(args.workload)
    data = models.tables(cfg, 0, cfg["train_rows"], args.shrink)
    schema = program.schema_of(cfg, data)
    booster = Booster(schema, BoostConfig(**traffic["boost"]))
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    M = args.prev_leaves
    rows = {t.name: t.n_rows for t in schema.tables}
    prev_masks = {t: jax.ShapeDtypeStruct((M, n), jnp.bool_) for t, n in rows.items()}
    prev_vals = jax.ShapeDtypeStruct((M,), jnp.float32)
    for level in range(traffic["boost"]["depth"]):
        K = 2 ** level
        masks = {t: jax.ShapeDtypeStruct((K, n), jnp.bool_) for t, n in rows.items()}
        node_mean = jax.ShapeDtypeStruct((K,), jnp.float32)
        closed = jax.make_jaxpr(booster._level_step_impl)(
            masks, prev_masks, prev_vals, node_mean)
        flat = jax.tree.leaves((masks, prev_masks, prev_vals, node_mean))
        run = jax.jit(partial(jax.core.eval_jaxpr, closed.jaxpr))
        compiled = run.lower([sds(c) for c in closed.consts],
                             *[sds(a) for a in flat]).compile()
        mem = compiled.memory_analysis()
        total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                 + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        print(f"level={level} K={K} M={M} temp_GiB={mem.temp_size_in_bytes / 2**30:.2f} "
              f"args_GiB={mem.argument_size_in_bytes / 2**30:.2f} "
              f"total_GiB={total / 2**30:.2f} (chip 15.75)", flush=True)


if __name__ == "__main__":
    main()
