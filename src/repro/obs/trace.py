"""Nestable spans with thread-local stacks and Chrome-trace export.

Default-off, near-zero-overhead: ``span(...)`` returns a shared no-op
context manager unless tracing was enabled, so instrumented hot paths
(message emission, level steps, the serving batcher) pay one truthiness
check when disabled.  Enabled, each span records wall time
(``perf_counter``) and host CPU time (``process_time``), its thread and
nesting depth, and arbitrary JSON-able attributes.

Two export formats:

- ``dump_jsonl(path)`` — one event per line, the raw sink CI uploads;
- ``dump_chrome_trace(path)`` — Chrome's Trace Event JSON ("X" complete
  events), loadable in ``chrome://tracing`` / https://ui.perfetto.dev.

jax interplay: spans optionally pass through
``jax.profiler.TraceAnnotation`` (so a concurrent ``jax.profiler``
capture shows the same names on the device timeline), and
:func:`fence` gives call sites explicit ``block_until_ready`` fencing —
async-dispatched device work would otherwise be misattributed to
whichever span happens to force the value later.  Fencing only happens
while tracing is enabled, so the disabled path never serializes
dispatch.  Span bodies that run under a jit trace are recorded as such
(``traced=True``) — their duration is compile/trace time, not runtime.

:func:`scope` is the instrument for code that may run either way.
Staged by jit it is a ``jax.named_scope``: the name lands in the
compiled ops' ``op_name`` metadata, so a device trace attributes each
op to it, and no host event is recorded.  Run eagerly it is always a
``TraceAnnotation`` (any profiler capture carries it on the host
timeline, on the device trace's clock) and, while tracing is enabled,
also a recorded span.  Neither depends on the tracing state, so the
compiled program is the same with tracing on or off.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

__all__ = [
    "Tracer", "span", "scope", "fence", "enable_tracing", "disable_tracing",
    "tracing_enabled", "get_tracer",
]


def _under_jit_trace() -> bool:
    """True when called from inside a jax trace (jit/vmap staging)."""
    import jax.core

    return not jax.core.trace_ctx.is_top_level()


class Tracer:
    """Process-wide span recorder.  One instance lives in this module;
    ``enable_tracing()`` switches it on and returns it."""

    def __init__(self, jax_annotations: bool = True):
        self.enabled = False
        self.jax_annotations = jax_annotations
        self.events: List[dict] = []
        self._lock = threading.Lock()
        self._tls = threading.local()
        # every thread's span stack, so clear() can reset them all — a
        # span leaked across an enable/disable cycle must not skew the
        # recorded depth of every later span on that thread
        self._stacks: List[list] = []
        self._t0 = time.perf_counter()

    # ------------------------------------------------------------ recording --
    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
            with self._lock:
                self._stacks.append(st)
        return st

    def record(self, ev: dict) -> None:
        with self._lock:
            self.events.append(ev)

    def clear(self) -> None:
        with self._lock:
            self.events.clear()
            for st in self._stacks:
                del st[:]
        self._t0 = time.perf_counter()

    # --------------------------------------------------------- ring buffer --
    @property
    def ring_capacity(self) -> Optional[int]:
        """Flight-recorder capacity, or None when unbounded."""
        return self.events.maxlen if isinstance(self.events, deque) else None

    def set_ring(self, capacity: int) -> None:
        """Flight-recorder mode: keep only the newest ``capacity`` events
        (overwrite-oldest, O(1) per span) — always-on tracing with bounded
        memory instead of the enable-dump-disable workflow."""
        if capacity <= 0:
            raise ValueError(f"ring capacity must be positive, got {capacity}")
        with self._lock:
            self.events = deque(self.events, maxlen=capacity)

    def set_unbounded(self) -> None:
        """Back to the unbounded list sink (full-trace capture mode)."""
        with self._lock:
            self.events = list(self.events)

    # ------------------------------------------------------------- rollups --
    def rollup(self) -> Dict[str, dict]:
        """Per-span-name {count, total_ms, max_ms} aggregate — the cheap
        summary BENCH reports embed."""
        with self._lock:
            events = list(self.events)
        out: Dict[str, dict] = {}
        for e in events:
            r = out.setdefault(e["name"], {"count": 0, "total_ms": 0.0,
                                           "max_ms": 0.0})
            r["count"] += 1
            r["total_ms"] += e["dur_ms"]
            r["max_ms"] = max(r["max_ms"], e["dur_ms"])
        for r in out.values():
            r["total_ms"] = round(r["total_ms"], 3)
            r["max_ms"] = round(r["max_ms"], 3)
        return out

    # ------------------------------------------------------------- exports --
    def dump_jsonl(self, path: str) -> int:
        with self._lock:
            events = list(self.events)
        with open(path, "w") as f:
            for e in events:
                f.write(json.dumps(e) + "\n")
        return len(events)

    def to_chrome_trace(self) -> dict:
        """Trace Event Format dict (open in Perfetto / chrome://tracing)."""
        with self._lock:
            events = list(self.events)
        trace = []
        for e in events:
            args = {k: v for k, v in e.items()
                    if k not in ("name", "ts_ms", "dur_ms", "tid")}
            trace.append({
                "name": e["name"], "ph": "X", "cat": "obs",
                "ts": round(e["ts_ms"] * 1e3, 3),     # µs
                "dur": round(e["dur_ms"] * 1e3, 3),
                "pid": 1, "tid": e["tid"],
                "args": args,
            })
        return {"traceEvents": trace, "displayTimeUnit": "ms"}

    def dump_chrome_trace(self, path: str) -> int:
        doc = self.to_chrome_trace()
        with open(path, "w") as f:
            json.dump(doc, f)
        return len(doc["traceEvents"])


class _Span:
    """Recording context manager (only built while tracing is enabled)."""

    __slots__ = ("tracer", "name", "attrs", "annotate", "t0", "cpu0", "traced",
                 "_jax_cm")

    def __init__(self, tracer: Tracer, name: str, attrs: dict, annotate: bool):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        self.annotate = annotate
        self._jax_cm = None

    def __enter__(self):
        tr = self.tracer
        tr._stack().append(self)
        if self.annotate:
            import jax.profiler

            self._jax_cm = jax.profiler.TraceAnnotation(self.name, **self.attrs)
            self._jax_cm.__enter__()
        self.traced = _under_jit_trace()
        self.cpu0 = time.process_time()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        cpu1 = time.process_time()
        tr = self.tracer
        stack = tr._stack()
        # exception-safe: pop our own frame even if inner spans leaked
        while stack and stack[-1] is not self:
            stack.pop()
        if stack:
            stack.pop()
        ev = {
            "name": self.name,
            "ts_ms": round((self.t0 - tr._t0) * 1e3, 6),
            "dur_ms": round((t1 - self.t0) * 1e3, 6),
            "cpu_ms": round((cpu1 - self.cpu0) * 1e3, 6),
            "tid": threading.get_ident() & 0xFFFF,
            "depth": len(stack),
        }
        if self.traced:
            ev["traced"] = True
        if exc_type is not None:
            ev["error"] = exc_type.__name__
        ev.update(self.attrs)
        tr.record(ev)
        if self._jax_cm is not None:
            self._jax_cm.__exit__(exc_type, exc, tb)
        return False


class _NullSpan:
    """Shared do-nothing context manager — the disabled-mode fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


_NULL = _NullSpan()
_tracer = Tracer()


def get_tracer() -> Tracer:
    return _tracer


def tracing_enabled() -> bool:
    return _tracer.enabled


def enable_tracing(clear: bool = True, jax_annotations: bool = True) -> Tracer:
    """Switch the process tracer on.  ``jax_annotations`` decides whether
    :func:`span` also writes a ``TraceAnnotation``; :func:`scope` always
    does, whatever it says."""
    if clear:
        _tracer.clear()
    _tracer.jax_annotations = jax_annotations
    _tracer.enabled = True
    return _tracer


def disable_tracing() -> Tracer:
    _tracer.enabled = False
    return _tracer


def span(name: str, **attrs):
    """``with span("boost.level", level=2):`` — records a span while
    tracing is enabled, otherwise returns the shared no-op manager."""
    if not _tracer.enabled:
        return _NULL
    return _Span(_tracer, name, attrs, _tracer.jax_annotations)


def scope(name: str, **attrs):
    """``with scope("boost.sketch", table=t):`` — names device-path work.

    Under a jit trace: ``jax.named_scope(name)`` (the attributes are
    dropped; ops are named, nothing is recorded).  Eagerly: a
    ``TraceAnnotation`` carrying ``attrs`` as its stats, recorded as a
    span as well while tracing is enabled."""
    if _under_jit_trace():
        import jax

        return jax.named_scope(name)
    if not _tracer.enabled:
        import jax.profiler

        return jax.profiler.TraceAnnotation(name, **attrs)
    return _Span(_tracer, name, attrs, True)


def fence(value: Any) -> Any:
    """``block_until_ready`` on ``value`` — but ONLY while tracing, so
    spans measure finished device work without the disabled path ever
    paying a synchronization."""
    if _tracer.enabled:
        try:
            import jax
            jax.block_until_ready(value)
        except Exception:
            pass
    return value
