"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration file and its traffic file are found by
name through ``BENCHMARK.json``; the traffic file names the driver
(``drivers/<driver>.py``) and holds its parameters, and each per-layer
metric is read by ``metrics/<metric>.py``.  A run:

1. fails, naming the platform, unless JAX finds a TPU (and as many
   chips as the cell asks for);
2. sets up: makes the tables and the model from ``--seed``, builds the
   program's objects and warms up every shape the window uses, with
   the persistent compilation cache in ``<checkout>/.jax_cache``;
3. measures for ``--seconds`` (with ``--trace 1`` under the profiler,
   reporting the per-layer metrics instead of the end-to-end ones),
   and counts compilations inside the window;
4. compares what the window produced with the plain reference
   (the driver's ``readings``) and prints each compared number beside
   its limit from the traffic file.

``--rehearse N`` is for rehearsals on the CPU: every table is cut by
N and the run goes on without a TPU.  Its numbers are the CPU's.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import core  # noqa: E402


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Context:
    """What a driver and a metric reader see of the run."""

    def __init__(self, args, cell, config, traffic, clock):
        self.args, self.cell, self.config, self.traffic = args, cell, config, traffic
        self.seed, self.seconds, self.traced = args.seed, args.seconds, bool(args.trace)
        self.shrink = max(1, args.rehearse)
        self.clock = clock
        self.spans = core.Spans(annotate=self.traced)
        self.extra: dict = {}          # what the driver leaves for the readers
        self.trace: dict = {}          # reduced profiler trace (traced runs)
        self.device: dict = {}
        self.peaks = core.load_json(core.BENCH / "peaks.json")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", type=int, default=0,
                    help="cut every table by this factor and allow a CPU")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    t_start = time.perf_counter()
    args = parse(argv)
    cell, config, traffic = core.cell_of(args.workload)
    core.configure_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not args.rehearse:
        core.require_tpu(cell["chips"])
    clock = core.CompileClock()
    ctx = Context(args, cell, config, traffic, clock)
    driver = load_module(core.BENCH / "drivers" / f"{traffic['driver']}.py")

    st = driver.setup(ctx)
    setup_s = time.perf_counter() - t_start
    print(f"setup_s={setup_s:.3f} compiles={clock.compiles} "
          f"cache_loads={clock.cache_hits} compile_s={clock.compile_s:.3f}",
          file=sys.stderr, flush=True)

    trace_dir = core.OUT / "trace"
    if ctx.traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    c0, h0 = clock.mark()
    try:
        with ctx.spans("bench.window"):
            measured = driver.window(ctx, st)
    finally:
        if ctx.traced:
            jax.profiler.stop_trace()
    c1, h1 = clock.mark()
    print(f"window_compiles={c1 - c0} window_cache_loads={h1 - h0}",
          file=sys.stderr, flush=True)
    ctx.device = core.device_info()

    if ctx.traced:
        from harness import trace as tr

        found = sorted(trace_dir.glob("**/*.xplane.pb"))
        ctx.trace = tr.reduce(tr.extract(str(found[0]))) if found else {}
        shutil.rmtree(trace_dir, ignore_errors=True)

    # the reference runs on the host once the program's state is freed
    attempted, failed = measured.pop("_attempted"), measured.pop("_failed")
    outputs = driver.collect(ctx, st)
    del st
    gc.collect()
    checks = core.Checks()
    try:
        for name, value in driver.readings(ctx, outputs).items():
            checks.add(name, value, ctx.traffic["limits"][name])
    except Exception as e:       # a comparison that cannot run is a failure
        checks.fail("reference", f"{type(e).__name__}: {e}")

    metrics = {}
    if ctx.traced:
        for spec in core.metric_specs(args.workload, "per_layer"):
            reader = load_module(core.BENCH / "metrics" / f"{spec['name']}.py")
            value = reader.read(ctx)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    else:
        measured["setup_s"] = setup_s
        for spec in core.metric_specs(args.workload, "end_to_end"):
            metrics[spec["name"]] = {"value": measured[spec["name"]],
                                     "unit": spec["unit"]}
    result = {"correct": checks.ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": ctx.device}
    if args.rehearse:
        result["rehearsal"] = args.rehearse
    if ctx.traced and ctx.trace:
        result["device"]["busy_s"] = ctx.trace["busy_s"]
        result["device"]["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}
    core.emit(result, checks)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except core.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        sys.exit(3)
