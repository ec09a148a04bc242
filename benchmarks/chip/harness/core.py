"""What every cell's run shares: the checkout's paths, the compile
cache, the device check, compile counting, host spans, the window
clock and the result line.

Nothing here imports jax at module level: ``configure_cache`` has to
run before the first jax import, so that the persistent compilation
cache sits at the checkout's fixed ``.jax_cache/``.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]          # benchmarks/chip
ROOT = BENCH.parents[1]                              # the checkout
OUT = ROOT / ".bench_out"                            # traces of traced runs


class NoChip(SystemExit):
    """Raised (and exits non-zero) when JAX finds no TPU or too few."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_of(name: str) -> tuple[dict, dict, dict]:
    """(cell, configuration file, traffic file) of a workload named in
    the checkout's ``BENCHMARK.json``."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return cell, load_json(ROOT / conf["file"]), traffic_of(cell["traffic"])


def config_of(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic_of(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def metric_specs(cell_name: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell_name``
    reports: those without a ``workloads`` list, and those whose list
    names it."""
    bench = load_json(ROOT / "BENCHMARK.json")
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def configure_cache() -> str:
    """Point JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    (a fixed path: the path is part of the cache key) and cache every
    program, however short its compile.  The TPU runtime's logs go under
    the checkout too, unless the machine names a place for them.  Call
    before importing jax."""
    path = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", str(OUT / "tpu_logs"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    sys.path.insert(0, str(ROOT / "src"))
    return path


def require_tpu(chips: int):
    """The first device, or a non-zero exit that names what JAX found."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"benchmark needs a TPU; JAX found platform "
                     f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} TPU chips; JAX found {len(devs)}")
    return devs[0]


class CompileClock:
    """Counts XLA compilations and persistent-cache loads, from JAX's
    monitoring events, so a run can say how many fell in its window.
    JAX reports a backend-compile duration for every program it hands
    the compiler's cache, hits included, so compiles are those less
    the hits."""

    def __init__(self):
        import jax

        self.requests = 0
        self.cache_hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.compile_s += duration

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def compiles(self) -> int:
        return self.requests - self.cache_hits

    def mark(self) -> tuple[int, int]:
        return self.compiles, self.cache_hits


class Spans:
    """Host spans around the benchmark's own calls into the program.

    Each span is kept as (name, seconds).  While a profiler trace runs
    (``annotate``), each also becomes a ``jax.profiler.TraceAnnotation``
    of the same name, so the trace reduction can label the device's
    idle gaps with what the host was doing."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.done: list[tuple[str, float]] = []

    def __call__(self, name: str):
        return _Span(self, name)

    def durations(self, name: str) -> list[float]:
        return [d for n, d in self.done if n == name]


class _Span:
    __slots__ = ("owner", "name", "t0", "ann")

    def __init__(self, owner: Spans, name: str):
        self.owner, self.name, self.ann = owner, name, None

    def __enter__(self):
        if self.owner.annotate:
            import jax

            self.ann = jax.profiler.TraceAnnotation(self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.owner.done.append((self.name, time.perf_counter() - self.t0))
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


class Checks:
    """The numbers compared against the reference, each with its limit.
    A number passes when it is at most its limit; a missing number
    (the comparison could not run) fails."""

    def __init__(self):
        self.items: dict[str, dict] = {}

    def add(self, name: str, value: float, limit: float) -> None:
        self.items[name] = {"value": float(value), "limit": float(limit)}

    def fail(self, name: str, why: str) -> None:
        self.items[name] = {"value": None, "limit": None, "error": why}

    @property
    def ok(self) -> bool:
        return bool(self.items) and all(
            c["value"] is not None and math.isfinite(c["value"])
            and c["value"] <= c["limit"] for c in self.items.values())


def device_info() -> dict:
    """The devices as JAX reports them; the memory peak is the fullest
    device's ``peak_bytes_in_use``."""
    import jax

    devs = jax.devices()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def emit(result: dict, checks: Checks) -> None:
    """Standard error ends with each compared number beside its limit;
    the last line of standard output is the result, ``checks`` last."""
    for name, c in checks.items.items():
        extra = f" error={c['error']}" if "error" in c else ""
        print(f"check {name} value={c['value']} limit={c['limit']}{extra}",
              file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = checks.items
    print(json.dumps(result), flush=True)
