"""Where a cell's window goes, by the program's own scopes and spans.

    python3 benchmarks/chip/tools/breakdown.py --workload <cell> \
        --seed <n> [--seconds 50] [--untraced-window 1] [--rehearse N]

Sets the cell up as ``run.py`` does and runs its traced window (as in a
``--trace 1`` run) under the profiler, then reduces the trace with
``harness.scopes``: device operations named by program and scope, idle
gaps named by the program's spans, device seconds under each scope,
and per host span its count, seconds and the program launches inside
it.  ``--untraced-window S`` then profiles the driver's untraced window
of ``S`` seconds as well (the program's tracing off, so nothing
fences), to show what tracing itself adds.  One JSON line per window,
also written to ``.bench_out/breakdown.<cell>.<seed>.json``.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402
from harness import core, scopes  # noqa: E402


def profiled_window(ctx, driver, st, tag: str) -> dict:
    import jax

    trace_dir = core.OUT / f"breakdown_{tag}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    try:
        with ctx.spans("bench.window"):
            measured = driver.window(ctx, st)
    finally:
        jax.profiler.stop_trace()
    found = sorted(trace_dir.glob("**/*.xplane.pb"))
    out = scopes.reduce(scopes.extract(str(found[0]))) if found else {}
    shutil.rmtree(trace_dir, ignore_errors=True)
    out["window"] = tag
    out["measured"] = {k: v for k, v in measured.items() if not k.startswith("_")}
    out["extra"] = {k: v for k, v in ctx.extra.items() if isinstance(v, (int, float))}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--untraced-window", type=float, default=0.0)
    ap.add_argument("--rehearse", type=int, default=0)
    a = ap.parse_args(argv)
    args = run.parse(["--workload", a.workload, "--seed", str(a.seed),
                      "--seconds", str(a.seconds), "--trace", "1",
                      "--rehearse", str(a.rehearse)])
    cell, config, traffic = core.cell_of(args.workload)
    core.configure_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not args.rehearse:
        core.require_tpu(cell["chips"])
    ctx = run.Context(args, cell, config, traffic, core.CompileClock())
    driver = run.load_module(core.BENCH / "drivers" / f"{traffic['driver']}.py")
    st = driver.setup(ctx)
    outs = [profiled_window(ctx, driver, st, "traced")]
    if a.untraced_window:
        ctx.traced, ctx.seconds = False, a.untraced_window
        ctx.spans.annotate = True
        outs.append(profiled_window(ctx, driver, st, "untraced"))
    dest = core.OUT / f"breakdown.{a.workload}.{a.seed}.json"
    dest.parent.mkdir(exist_ok=True)
    dest.write_text(json.dumps(outs))
    for out in outs:
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    try:
        sys.exit(main())
    except core.NoChip as e:
        print(f"breakdown.py: {e}", file=sys.stderr)
        sys.exit(3)
