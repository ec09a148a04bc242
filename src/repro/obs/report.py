"""Schema-versioned ``BENCH_<name>.json`` perf-trajectory reports.

Every ``benchmarks/bench_*.py`` smoke funnels its result rows and
headline metrics through a :class:`BenchReport`, which stamps a machine
/config fingerprint, the process metrics snapshot, and (when tracing
was on) a per-span rollup, then writes ``BENCH_<name>.json`` at the
repo root.  Committing those files makes the perf trajectory reviewable
PR-over-PR, and ``benchmarks/report.py --check`` gates the nightly job
on them: missing file, schema violation, or a pinned metric regressing
>2× versus the committed baseline all fail.

Schema v1 (validated by :func:`validate_bench`):

    {"schema_version": 1, "bench": str, "fingerprint": {...},
     "config": {...}, "metrics": {str: number}, "rows": [dict, ...],
     "metrics_snapshot": {...}?, "span_rollup": {...}?}

``metrics`` holds the headline scalars baselines pin (count-derived
ratios preferred over wall-clock — they are scheduler-noise free).
"""
from __future__ import annotations

import json
import os
import platform
from typing import Dict, List, Optional

from .metrics import get_registry
from .trace import get_tracer, tracing_enabled

__all__ = ["BenchReport", "fingerprint", "validate_bench", "bench_path"]

SCHEMA_VERSION = 1


def fingerprint() -> dict:
    """Machine/config identity a report was measured on — enough to
    judge whether two trajectory points are comparable."""
    import jax

    from ..distributed import spmd

    dev = jax.devices()[0]
    fp = {
        "host": platform.platform(),
        "python": platform.python_version(),
        "cpus": os.cpu_count(),
        "jax": jax.__version__,
        # the device a result was measured on, as JAX reports it: a CPU
        # run can never pass for a chip run
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
    }
    mesh = spmd.mesh_fingerprint()
    if mesh is not None:                # active data mesh at report time
        fp["mesh"] = mesh
    return fp


def bench_path(name: str, out_dir: Optional[str] = None) -> str:
    """Canonical location of ``BENCH_<name>.json`` — the repo root by
    default (override with ``REPRO_BENCH_DIR`` for scratch runs)."""
    if out_dir is None:
        out_dir = os.environ.get("REPRO_BENCH_DIR") or os.getcwd()
    return os.path.join(out_dir, f"BENCH_{name}.json")


class BenchReport:
    """Accumulates one benchmark's rows + headline metrics, then writes
    the schema-versioned JSON artifact."""

    def __init__(self, name: str, config: Optional[dict] = None):
        self.name = name
        self.config = dict(config or {})
        self.rows: List[dict] = []
        self.metrics: Dict[str, float] = {}

    def add_rows(self, rows: List[dict]) -> "BenchReport":
        self.rows.extend(rows)
        return self

    def set_metric(self, key: str, value) -> "BenchReport":
        self.metrics[key] = float(value)
        return self

    def to_dict(self) -> dict:
        doc = {
            "schema_version": SCHEMA_VERSION,
            "bench": self.name,
            "fingerprint": fingerprint(),
            "config": self.config,
            "metrics": self.metrics,
            "rows": self.rows,
            "metrics_snapshot": get_registry().snapshot(),
        }
        if tracing_enabled():
            doc["span_rollup"] = get_tracer().rollup()
        return doc

    def write(self, out_dir: Optional[str] = None) -> str:
        """Write ``BENCH_<name>.json`` (and, when tracing is enabled,
        the raw span sink ``TRACE_<name>.jsonl`` beside it)."""
        path = bench_path(self.name, out_dir)
        doc = self.to_dict()
        errors = validate_bench(doc)
        if errors:                    # a writer bug must fail loudly, not
            raise ValueError(errors)  # poison the committed trajectory
        with open(path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True, default=_jsonable)
            f.write("\n")
        if tracing_enabled():
            get_tracer().dump_jsonl(
                os.path.join(os.path.dirname(path),
                             f"TRACE_{self.name}.jsonl"))
        return path


def _jsonable(o):
    try:
        import numpy as np
        if isinstance(o, np.generic):
            return o.item()
    except Exception:
        pass
    raise TypeError(f"not JSON-serializable: {type(o).__name__}")


def validate_bench(doc: dict) -> List[str]:
    """Schema-v1 structural check; returns human-readable violations
    (empty list == valid)."""
    errs = []
    if not isinstance(doc, dict):
        return ["report is not an object"]
    if doc.get("schema_version") != SCHEMA_VERSION:
        errs.append(f"schema_version must be {SCHEMA_VERSION}, "
                    f"got {doc.get('schema_version')!r}")
    if not isinstance(doc.get("bench"), str) or not doc.get("bench"):
        errs.append("bench must be a non-empty string")
    if not isinstance(doc.get("fingerprint"), dict):
        errs.append("fingerprint must be an object")
    metrics = doc.get("metrics")
    if not isinstance(metrics, dict):
        errs.append("metrics must be an object")
    else:
        for k, v in metrics.items():
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                errs.append(f"metrics[{k!r}] must be a number, got {v!r}")
    rows = doc.get("rows")
    if not isinstance(rows, list) or any(not isinstance(r, dict) for r in rows):
        errs.append("rows must be a list of objects")
    return errs
