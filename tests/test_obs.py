"""Observability layer: metrics algebra, span semantics, exports, and
the guarantees the instrumented hot paths rely on — disabled-mode spans
are free and tracing never changes what training computes.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import logging
import math
import os
import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import BoostConfig, Booster, Channels, QueryCounter, SumProd
from repro.obs import (
    BenchReport, Histogram, MetricsRegistry, diff_snapshots,
    disable_tracing, enable_tracing, format_summary_table, get_registry,
    get_tracer, merge_snapshots, scope, span, validate_bench,
)
from repro.serving.service import ServiceStats
from repro.relational.generators import star_schema

# bucket grid: RES sub-buckets per octave → any quantile is within one
# bucket (~2^(1/8)−1 ≈ 9% relative) of the empirical value
BUCKET_REL = 2 ** (1 / Histogram.RES) - 1


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with tracing disabled (process-global)."""
    disable_tracing()
    yield
    disable_tracing()


# ------------------------------------------------------------------ metrics --

def test_histogram_quantiles_track_numpy():
    rng = np.random.default_rng(0)
    draws = rng.lognormal(mean=1.0, sigma=1.2, size=5000)
    h = Histogram("t")
    for v in draws:
        h.observe(v)
    for q in (0.50, 0.90, 0.99):
        want = float(np.quantile(draws, q))
        got = h.quantile(q)
        assert abs(got - want) / want <= 2 * BUCKET_REL, (q, got, want)
    s = h.summary()
    assert s["count"] == len(draws)
    assert s["min"] == pytest.approx(draws.min())
    assert s["max"] == pytest.approx(draws.max())
    assert s["mean"] == pytest.approx(draws.mean())


def test_histogram_nonpositive_underflow():
    h = Histogram()
    for v in (-1.0, 0.0, 2.0, 4.0):
        h.observe(v)
    assert h.count == 4 and h.min == -1.0
    assert h.quantile(0.0) == -1.0          # underflow bucket reports min
    assert h.quantile(1.0) == 4.0


def test_histogram_merge_is_exact():
    rng = np.random.default_rng(1)
    a, b, both = Histogram(), Histogram(), Histogram()
    for i, v in enumerate(rng.lognormal(size=2000)):
        (a if i % 2 else b).observe(v)
        both.observe(v)
    a.merge(b)
    assert a.buckets == both.buckets
    assert a.count == both.count and a.sum == pytest.approx(both.sum)
    assert a.quantile(0.9) == both.quantile(0.9)


def test_snapshot_diff_and_merge():
    reg = MetricsRegistry()
    reg.counter("c").inc(5)
    reg.gauge("g").set(2.5)
    h = reg.histogram("h")
    h.observe(1.0)
    before = reg.snapshot()
    reg.counter("c").inc(3)
    h.observe(8.0)
    h.observe(8.0)
    after = reg.snapshot()
    d = diff_snapshots(before, after)
    assert d["c"]["value"] == 3
    assert d["h"]["count"] == 2 and d["h"]["mean"] == pytest.approx(8.0)
    # the window's quantiles come from the differenced buckets: ~8, not 1
    assert d["h"]["p50"] == pytest.approx(8.0, rel=2 * BUCKET_REL)
    m = merge_snapshots(before, d)
    assert m["c"]["value"] == after["c"]["value"]
    assert m["h"]["count"] == after["h"]["count"]
    table = format_summary_table(after, title="t")
    assert "c" in table and "p99" in table


def test_registry_type_conflict():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.histogram("x")


# -------------------------------------------------------------------- spans --

def test_span_nesting_depth_and_rollup():
    tr = enable_tracing()
    with span("outer", k=1):
        with span("inner"):
            pass
        with span("inner"):
            pass
    disable_tracing()
    evs = {((e["name"]), e["depth"]) for e in tr.events}
    assert ("outer", 0) in evs and ("inner", 1) in evs
    outer = next(e for e in tr.events if e["name"] == "outer")
    assert outer["k"] == 1 and outer["dur_ms"] >= 0
    roll = tr.rollup()
    assert roll["inner"]["count"] == 2 and roll["outer"]["count"] == 1


def test_span_exception_safety_with_duplicate_names():
    tr = enable_tracing()
    with pytest.raises(RuntimeError):
        with span("same"):
            with span("same"):
                raise RuntimeError("boom")
    # both frames popped despite the exception; a fresh span sits at depth 0
    with span("after"):
        pass
    disable_tracing()
    errs = [e for e in tr.events if e.get("error")]
    assert len(errs) == 2 and all(e["error"] == "RuntimeError" for e in errs)
    assert next(e for e in tr.events if e["name"] == "after")["depth"] == 0


def test_span_under_jit_trace_is_tagged_traced():
    """A span opened while jit stages a function records trace time,
    not runtime, and says so; the same span outside any trace does not."""
    import jax

    tr = enable_tracing()

    @jax.jit
    def f(x):
        with span("staged"):
            return x * 2.0

    f(jnp.ones(3)).block_until_ready()
    with span("eager"):
        pass
    disable_tracing()
    ev = {e["name"]: e for e in tr.events}
    assert ev["staged"].get("traced") is True
    assert "traced" not in ev["eager"]


def test_scope_under_jit_names_ops_and_records_nothing():
    """Staged by jit, a scope reaches the compiled ops' ``op_name``
    metadata (what a device trace attributes ops by) and records no
    host event, tracing on or not."""
    tr = enable_tracing()

    def f(x):
        with scope("probe.scope", table="t"):
            return jnp.sin(x) * 2.0

    hlo = jax.jit(f).lower(jnp.ones(8)).compile().as_text()
    disable_tracing()
    assert "probe.scope/sin" in hlo
    assert tr.events == []


def test_eager_scope_records_only_while_enabled():
    tr = enable_tracing()
    disable_tracing()
    with scope("probe.off", table="t"):
        pass
    assert tr.events == []
    enable_tracing()
    with scope("probe.on", table="t"):
        jnp.ones(3).block_until_ready()
    disable_tracing()
    (ev,) = tr.events
    assert ev["name"] == "probe.on" and ev["table"] == "t"
    assert "traced" not in ev


def test_level_program_is_named_level_step(caplog):
    sch = star_schema(seed=3, n_fact=40, n_dim=6)
    with caplog.at_level(logging.WARNING), jax.log_compiles():
        Booster(sch, BoostConfig(n_trees=1, depth=1)).fit()
    built = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("Compiling jit(")]
    assert any(m.startswith("Compiling jit(level_step)") for m in built), built
    assert not any("unknown" in m for m in built), built


def test_level_program_bytes_gauge_after_sketch_fit():
    gauge = get_registry().gauge("train.level_program_bytes")
    gauge.set(0)
    sch = star_schema(seed=5, n_fact=40, n_dim=6)
    Booster(sch, BoostConfig(n_trees=1, depth=1, mode="sketch", sketch_k=8)).fit()
    assert gauge.value > 0


def test_scorer_programs_counted_once_per_compiled_ensemble():
    from repro.serving import compile_ensemble, score_grouped

    sch = star_schema(seed=6, n_fact=40, n_dim=6)
    trees, _ = Booster(sch, BoostConfig(n_trees=1, depth=1)).fit()
    programs = get_registry().counter("serve.scorer_programs")
    fact = sch.tables[0].name
    for _ in range(2):
        n0 = programs.value
        ens = compile_ensemble(sch, trees)
        first = score_grouped(ens, fact)
        assert programs.value == n0 + 1
        again = score_grouped(ens, fact)
        assert programs.value == n0 + 1
        assert np.array_equal(np.asarray(first[0]), np.asarray(again[0]))


def test_factor_programs_counted_once_per_shape():
    """One factor program per shape: a new ensemble, or a new dataset of
    the same shapes, reuses it; another tree count or depth builds one
    more.  Scores match an ensemble built on the eager per-tree masks."""
    from _trees import random_trees
    from repro.core.tree import leaf_masks
    from repro.serving import CompiledEnsemble, compile_ensemble, score_grouped

    programs = get_registry().counter("serve.factor_programs")
    sch = star_schema(seed=31, n_fact=46, n_dim=9)    # shapes of this test only
    n0 = programs.value
    ensembles = [compile_ensemble(sch, random_trees(sch, (2, 2), seed=s))
                 for s in (1, 2)]
    assert programs.value == n0 + 1
    other = star_schema(seed=32, n_fact=46, n_dim=9)
    compile_ensemble(other, random_trees(other, (2, 2), seed=3))
    assert programs.value == n0 + 1
    compile_ensemble(sch, random_trees(sch, (2, 2, 2), seed=4))
    assert programs.value == n0 + 2
    compile_ensemble(sch, random_trees(sch, (3, 3), seed=5))
    assert programs.value == n0 + 3
    for ens in ensembles:
        eager = CompiledEnsemble(
            schema=sch, trees=ens.trees, leaf_values=ens.leaf_values,
            factors={t.name: jnp.concatenate(
                [leaf_masks(sch, t.name, tr) for tr in ens.trees]).T.astype(jnp.float32)
                for t in sch.tables},
            tree0_leaves=ens.tree0_leaves)
        for a, b in zip(score_grouped(ens, "fact"), score_grouped(eager, "fact")):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    assert programs.value == n0 + 3


def test_disabled_span_is_shared_noop():
    assert span("a", x=1) is span("b")          # no allocation when off
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        with span("hot", i=0):
            pass
    dt = time.perf_counter() - t0
    # generous CI bound — the real figure is tens of ns per span
    assert dt / n < 20e-6, f"{dt / n * 1e9:.0f}ns per disabled span"


def test_chrome_trace_roundtrip(tmp_path):
    tr = enable_tracing()
    with span("phase", rows=3):
        with span("step"):
            pass
    disable_tracing()
    p = tmp_path / "trace.json"
    n = tr.dump_chrome_trace(str(p))
    doc = json.loads(p.read_text())
    assert len(doc["traceEvents"]) == n == 2
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X" and ev["pid"] == 1
        assert isinstance(ev["ts"], (int, float)) and ev["dur"] >= 0
    names = {ev["name"] for ev in doc["traceEvents"]}
    assert names == {"phase", "step"}
    phase = next(e for e in doc["traceEvents"] if e["name"] == "phase")
    assert phase["args"]["rows"] == 3

    jl = tmp_path / "trace.jsonl"
    assert tr.dump_jsonl(str(jl)) == 2
    lines = [json.loads(x) for x in jl.read_text().splitlines()]
    assert {e["name"] for e in lines} == {"phase", "step"}


def test_span_threads_do_not_share_stacks():
    tr = enable_tracing()

    def work(i):
        with span("t", i=i):
            time.sleep(0.001)
            with span("u", i=i):
                pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    disable_tracing()
    us = [e for e in tr.events if e["name"] == "u"]
    assert len(us) == 4 and all(e["depth"] == 1 for e in us)


# ----------------------------------------------------- QueryCounter shim --

def test_query_counter_thread_safe_and_mirrored():
    g = get_registry().counter("sumprod.edges")
    g0 = g.value
    c = QueryCounter()

    def work():
        for _ in range(1000):
            c.bump()
            c.bump_edges(2)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.count == 8000 and c.edges == 16000
    assert g.value - g0 == 16000            # global mirror sees the same work


def test_query_counter_per_instance_isolation():
    a, b = QueryCounter(), QueryCounter()
    a.bump_edges(5)
    assert (a.edges, b.edges) == (5, 0)     # the IVM benchmark ratios


def test_edge_accounting_unchanged(star):
    """Regression pin: one inside-out pass still bumps exactly one
    segment-⊕ emission per join-tree edge, per counter instance."""
    sch = star[0]
    c = QueryCounter()
    sp = SumProd(sch, counter=c)
    sem = Channels(2)
    fac = sp.ones_factors(sem)
    lbl = sch.labels
    fac[sch.label_table] = jnp.stack([jnp.ones_like(lbl), lbl], -1)
    e0, q0 = c.edges, c.count
    sp(sem, fac, group_by=sch.label_table)
    n_edges = len(sch.tables) - 1           # rooted join tree: τ − 1 edges
    assert c.edges - e0 == n_edges
    assert c.count - q0 == 1


# -------------------------------------------- tracing is observation-only --

def test_tracing_does_not_change_trained_trees(monkeypatch):
    sch = star_schema(seed=11, n_fact=120, n_dim=12)
    cfg = BoostConfig(n_trees=2, depth=2, mode="sketch", ssr_mode="off")
    plain, _ = Booster(sch, cfg).fit()
    enable_tracing()
    traced, _ = Booster(sch, cfg).fit()
    tr = disable_tracing()
    assert len(tr.events) > 0               # instrumentation actually fired
    # and with every scope taken out of the program
    import repro.core.sumprod as sumprod_mod
    import repro.core.trainer as trainer_mod

    unscoped_fn = lambda *a, **k: contextlib.nullcontext()  # noqa: E731
    monkeypatch.setattr(trainer_mod, "scope", unscoped_fn)
    monkeypatch.setattr(sumprod_mod, "_scope", unscoped_fn)
    unscoped, _ = Booster(sch, cfg).fit()
    for a, b, c in zip(plain, traced, unscoped):
        for f in ("feat", "thr", "leaf"):
            want = np.asarray(getattr(a, f))
            assert np.array_equal(want, np.asarray(getattr(b, f)))
            assert np.array_equal(want, np.asarray(getattr(c, f)))
    names = {e["name"] for e in tr.events}
    # eager scopes are recorded; the jitted level step's scopes name
    # device ops instead of recording host spans
    assert {"boost.round", "boost.level", "boost.prev_masks",
            "boost.level_build"} <= names
    assert not names & {"boost.stats", "boost.sweep", "boost.sketch",
                        "boost.descend", "sumprod.emit"}


# ------------------------------------------------------- service metrics --

def test_service_stats_snapshot_quantiles():
    st = ServiceStats()
    lats = [float(v) for v in range(1, 101)]    # 1..99ms plus one 100ms tail
    for ms in lats:
        st.latency_ms.observe(ms)
        st.queue_wait_ms.observe(ms / 10)
        st._requests.inc()
    snap = st.snapshot()
    assert snap["requests"] == 100
    assert snap["latency_ms"]["count"] == 100
    assert snap["latency_ms"]["p99"] == pytest.approx(
        float(np.quantile(lats, 0.99)), rel=2 * BUCKET_REL)
    assert snap["queue_wait_ms"]["p50"] < snap["latency_ms"]["p50"]


# ------------------------------------------------------------ BENCH files --

def _load_report_module():
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "benchmarks", "report.py")
    spec = importlib.util.spec_from_file_location("bench_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_report_write_and_validate(tmp_path):
    rep = BenchReport("demo", config={"smoke": True})
    rep.add_rows([{"bench": "D1", "x": 1}])
    rep.set_metric("ratio", 3.5)
    path = rep.write(str(tmp_path))
    doc = json.loads(open(path).read())
    assert validate_bench(doc) == []
    assert doc["schema_version"] == 1 and doc["metrics"]["ratio"] == 3.5
    assert validate_bench({"schema_version": 2}) != []


def test_report_check_gate(tmp_path):
    mod = _load_report_module()
    rep = BenchReport("demo")
    rep.add_rows([{"bench": "D1"}])
    rep.set_metric("ratio", 4.0)
    rep.set_metric("err", 0.1)
    rep.write(str(tmp_path))
    baselines = tmp_path / "baselines.json"

    def gate(pins):
        baselines.write_text(json.dumps({"demo": pins}))
        return mod.check(mod.load_benches(str(tmp_path)), str(baselines))

    assert gate({"ratio": {"pin": 4.0, "kind": "min"}}) == []
    assert gate({"ratio": {"pin": 4.0, "kind": "min"},
                 "err": {"pin": 0.1, "kind": "max"}}) == []
    # >2× regressions trip; within-2× drift does not
    assert gate({"ratio": {"pin": 9.0, "kind": "min"}})      # 4 < 9/2
    assert gate({"ratio": {"pin": 7.0, "kind": "min"}}) == []
    assert gate({"err": {"pin": 0.04, "kind": "max"}})       # 0.1 > 0.08
    assert gate({"missing": {"pin": 1.0, "kind": "min"}})
    baselines.write_text(json.dumps({"absent": {"m": {"pin": 1, "kind": "min"}}}))
    assert mod.check(mod.load_benches(str(tmp_path)), str(baselines))
