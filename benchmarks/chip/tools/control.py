"""Program and control readings of a cell's compared numbers, per seed.

    python benchmarks/chip/tools/control.py --workload <cell> \
        --seeds 11,12,13 [--seconds 2] [--rehearse N]

For each seed, in one process: the cell's own set-up, a short window at
the cell's load, and then two readings of every compared number: the
program's (what ``run.py`` compares with its limit) and the control's,
where the reference computes the same answers in the program's place
one precision lower (bfloat16 for the configurations' float32).  The
limits in the traffic files sit between the two.  One JSON line per
seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402
from harness import core  # noqa: E402


def readings_for(workload: str, seed: int, seconds: float, clock,
                 rehearse: int = 0, controls: bool = True) -> dict:
    args = run.parse(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--rehearse", str(rehearse)])
    cell, config, traffic = core.cell_of(workload)
    ctx = run.Context(args, cell, config, traffic, clock)
    driver = run.load_module(core.BENCH / "drivers" / f"{traffic['driver']}.py")
    st = driver.setup(ctx)
    driver.window(ctx, st)
    out = driver.collect(ctx, st)
    del st
    gc.collect()
    row = {"seed": seed, "program": driver.readings(ctx, out)}
    if controls:
        from reference import bf16

        row["control"] = driver.readings(ctx, out, q=bf16)
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args(argv)
    core.configure_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not args.rehearse:
        core.require_tpu(1)
    clock = core.CompileClock()
    for s in args.seeds.split(","):
        print(json.dumps(readings_for(args.workload, int(s), args.seconds, clock,
                                      args.rehearse)), flush=True)


if __name__ == "__main__":
    main()
