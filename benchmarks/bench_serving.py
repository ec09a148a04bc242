"""Serving benchmark: seed per-leaf scoring loop vs compiled one-pass
scorer, plus micro-batching service throughput.

  S1  SumProd-evaluation counts + bulk wall time, old (per-leaf loop,
      n_trees·L + 1 passes) vs new (stacked-leaf Channels pass, 1),
      with scores cross-checked bit-for-bit against the materialized
      join oracle.
  S2  micro-batching service QPS under zipf-skewed interactive traffic
      (batch coalescing + LRU cache), measured end to end.
  S3  open-loop mixed delta+query workload under SLO burn-rate
      monitoring: interleaved table deltas and scoring chunks with a
      healthy-phase compliance measurement, then an injected dispatch
      latency spike that must flip the burn-rate state off healthy AND
      trigger a flight-recorder dump (validated as a loadable Chrome
      trace).  The SLO summary fields land in BENCH_serving.json so
      report.py --check gates on them.
  S4  data-parallel scaling: the same ensemble compiled unsharded and
      row-sharded over every visible device (CI forces 8 host devices
      via XLA_FLAGS), with grouped scores required bit-equal and the
      segment-⊕ edge count identical — sharding may move work, never
      change it.  Single-device runs emit the 1.0 identity point.
  S5  snapshot isolation under concurrent ingest: a real ingest thread
      applies deltas while the service scores, every batch dispatching
      against an MVCC snapshot pinned at cutoff; post-run, every LRU
      cache entry must bit-match the recompute oracle at the
      data_version in its own key, with the SLO monitor healthy
      end-to-end.
  S6  durability: the same delta stream applied with and without a
      group-committed WAL attached (append overhead %), a follower
      process tailing the log into a live replica (replication lag
      p99), and a timed cold recovery from checkpoint + WAL tail —
      replica and recovered scorer must both serve bit-identically to
      the writer.

    PYTHONPATH=src python benchmarks/bench_serving.py
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python benchmarks/bench_serving.py --smoke
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    BoostConfig, Booster, QueryCounter, materialize_join, predict_rows,
)
from _common import REPO_ROOT, emit
from repro.incremental import MaintainedScorer
from repro.obs import FlightRecorder, SLOMonitor, get_tracer, parse_slo_spec
from repro.relational.generators import delta_stream, star_schema
from repro.serving import (
    ModelRegistry, RelationalScoringService, compile_ensemble,
    score_grouped, score_grouped_reference,
)


def _timeit(fn, n=3):
    jax.block_until_ready(fn())   # compile
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / n * 1e3  # ms


def s1_one_pass_vs_leaf_loop(n_fact=2000, n_dim=64, n_trees=5, depth=3):
    sch = star_schema(seed=3, n_fact=n_fact, n_dim=n_dim)
    cfg = BoostConfig(n_trees=n_trees, depth=depth, mode="sketch", ssr_mode="off")
    booster = Booster(sch, cfg)
    trees, _ = booster.fit()

    c_old = QueryCounter()
    tot_old, cnt_old = score_grouped_reference(sch, trees, "fact", counter=c_old)
    ms_old = _timeit(lambda: score_grouped_reference(sch, trees, "fact"))

    c_new = QueryCounter()
    ens = compile_ensemble(sch, trees, counter=c_new)
    tot_new, cnt_new = score_grouped(ens, "fact")
    ms_new = _timeit(lambda: ens._score_fn("fact")(*ens._score_args("fact")))

    # oracle: brute force over the materialized join
    J = materialize_join(sch)
    X = jnp.stack([J[c] for (_, c) in sch.features], axis=1)
    rows = np.asarray(J["__rows__fact"])
    preds = np.asarray(predict_rows(trees, X))
    want_tot = np.bincount(rows, weights=preds, minlength=n_fact)
    want_cnt = np.bincount(rows, minlength=n_fact)
    np.testing.assert_allclose(np.asarray(tot_new), want_tot, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(cnt_new), want_cnt, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(tot_new), np.asarray(tot_old),
                               rtol=1e-3, atol=1e-3)

    ratio = c_old.count / max(c_new.count, 1)
    assert ratio >= 5.0, f"expected ≥5× fewer SumProd evaluations, got {ratio:.1f}×"
    return [{
        "bench": "S1", "n_fact": n_fact, "trees": n_trees, "leaves": 2 ** depth,
        "sumprod_evals_old": c_old.count, "sumprod_evals_new": c_new.count,
        "eval_ratio": round(ratio, 1),
        "bulk_ms_old": round(ms_old, 1), "bulk_ms_new": round(ms_new, 1),
        "oracle_match": True,
    }], sch, trees


def s2_service_qps(sch, trees, n_requests=2000, max_batch=64, max_wait_ms=1.0,
                   cache_size=4096, zipf_a=1.3):
    registry = ModelRegistry()
    registry.publish(compile_ensemble(sch, trees))
    service = RelationalScoringService(
        registry, "fact", max_batch=max_batch, max_wait_ms=max_wait_ms,
        cache_size=cache_size,
    )
    n_rows = sch.table("fact").n_rows
    rng = np.random.default_rng(1)
    ids = np.minimum(rng.zipf(zipf_a, n_requests) - 1, n_rows - 1)

    async def run():
        await service.start()
        await service.score_many(ids[:64].tolist())   # warm the jit + cache
        t0 = time.perf_counter()
        for chunk in np.array_split(ids, max(1, n_requests // 256)):
            await service.score_many(chunk.tolist())
        dt = time.perf_counter() - t0
        await service.stop()
        return dt

    dt = asyncio.run(run())
    snap = service.stats_snapshot()
    return [{
        "bench": "S2", "requests": n_requests, "wall_s": round(dt, 3),
        "qps": int(n_requests / dt),
        "batches": snap["batches"], "mean_batch": round(snap["mean_batch"], 1),
        "cache_hit_pct": round(100 * snap["cache_hit_rate"], 1),
        "latency_ms_p50": round(snap["latency_ms"]["p50"], 3),
        "latency_ms_p99": round(snap["latency_ms"]["p99"], 3),
        "queue_wait_ms_p50": round(snap["queue_wait_ms"]["p50"], 3),
        "queue_wait_ms_p99": round(snap["queue_wait_ms"]["p99"], 3),
    }]


def s3_slo_mixed_workload(sch, trees, n_clean=8, n_spike=4, chunk=64,
                          spike_sleep_s=0.6):
    """Open-loop mixed delta+query run with SLO monitoring.

    Clean phase: interleave delta batches (MaintainedScorer.apply) with
    score_many chunks and measure latency compliance.  Spike phase: wrap
    the service's dispatch in a sleep so every request blows the latency
    objective — the burn-rate state must leave ``healthy`` and the
    flight recorder must dump a valid Chrome trace.
    """
    out_dir = os.environ.get("REPRO_BENCH_DIR") or REPO_ROOT
    tracer = get_tracer()
    was_enabled = tracer.enabled          # REPRO_TRACE=1 in CI
    slo = SLOMonitor(parse_slo_spec("latency=300ms@0.9,errors=0.05,staleness=10s"),
                     fast_window_s=2.0, slow_window_s=8.0)
    flight = FlightRecorder(capacity=2048, out_dir=out_dir, name="serving",
                            latency_trigger_ms=450.0, cooldown_s=0.3).start()
    registry = ModelRegistry()
    ms = MaintainedScorer(compile_ensemble(sch, trees))
    registry.publish(ms)
    # shed_when_unhealthy off: the bench drives PAST the SLO on purpose
    # and wants latencies, not ServiceOverloadedError, from the far side
    service = RelationalScoringService(
        registry, "fact", max_batch=chunk, max_wait_ms=0.5, cache_size=256,
        flight=flight, shed_when_unhealthy=False,
    )
    n_rows = sch.table("fact").n_rows
    rng = np.random.default_rng(5)
    deltas = list(delta_stream(sch, ms.live_rows, seed=11,
                               n_batches=n_clean, ops_per_batch=4))

    async def run():
        await service.start()
        # warm the jit + message cache before the SLO clock starts
        await service.score_many(rng.integers(0, n_rows, chunk).tolist())
        service.slo = slo
        max_stale = 0.0
        for batch in deltas:              # clean phase: deltas + queries
            ms.apply(batch)
            max_stale = max(max_stale, ms.staleness_s())
            ids = np.minimum(rng.zipf(1.3, chunk) - 1, n_rows - 1)
            await service.score_many(ids.tolist())
        clean_state = slo.state()
        clean_compliance = slo.compliance("latency")
        # spike phase: every dispatch stalls past the latency objective
        orig = service._dispatch
        service._dispatch = lambda b: (time.sleep(spike_sleep_s), orig(b))[1]
        for _ in range(n_spike):
            ids = rng.integers(0, n_rows, chunk)
            await service.score_many(ids.tolist())
        service._dispatch = orig
        spike_state = slo.state()
        await service.stop()
        return clean_state, clean_compliance, spike_state, max_stale

    clean_state, clean_compliance, spike_state, max_stale = asyncio.run(run())
    flight.stop()
    if was_enabled:
        tracer.enabled = True             # keep the CI TRACE dump alive

    dumps = [d for d in flight.status()["dumps"] if d["path"]]
    assert spike_state != "healthy", (
        f"latency spike did not move the burn-rate state: {spike_state}")
    assert dumps, "latency spike did not trigger a flight dump"
    with open(dumps[0]["path"]) as f:     # must load as a Chrome trace
        doc = json.load(f)
    events = doc["traceEvents"]
    triggers = [e for e in events if e.get("name") == "flight.trigger"]
    assert triggers and triggers[0]["ph"] == "i", "dump lacks trigger marker"
    snap = service.stats_snapshot()
    return [{
        "bench": "S3", "deltas": len(deltas), "requests": snap["requests"],
        "clean_state": clean_state,
        "clean_latency_compliance": round(clean_compliance, 4),
        "max_staleness_s": round(max_stale, 4),
        "spike_state": spike_state,
        "flight_dumps": len(dumps), "flight_events": len(events),
        "errors": snap["errors"], "shed": snap["shed"],
    }]


def s5_snapshot_isolation(sch, trees, n_batches=6, chunk=48, ops_per_batch=4):
    """Concurrent ingest + serve under MVCC snapshot isolation.

    A REAL ingest thread applies delta batches against the published
    MaintainedScorer while the asyncio service scores zipf traffic with
    the full backpressure stack on (SLO-fed admission control, queue
    depth cap, deadline-aware batch cutoff).  Every applied version pins
    a ``pin_oracle=True`` snapshot; after the run EVERY entry in the
    service's LRU cache must match the full-recompute oracle at the
    data_version in its own key, bit for bit — a single mixed-version
    score fails the bench.  The SLO monitor must end the run healthy:
    isolation is only interesting if it holds while latency/staleness
    stay within objective.  The latency objective is sized for this
    workload's worst case — every new data_version re-jits the
    path-restricted refresh for its new message/factor shapes, so the
    first batch per version carries a compile — which keeps admission
    control armed without the bench shedding itself on compile spikes.
    """
    slo = SLOMonitor(parse_slo_spec("latency=2000ms@0.9,errors=0.05,staleness=10s"),
                     fast_window_s=2.0, slow_window_s=8.0)
    registry = ModelRegistry()
    ms = MaintainedScorer(compile_ensemble(sch, trees))
    group = "fact"
    v = registry.publish(ms)
    # the SLO attaches after warm-up (below), so the deadline budget is
    # passed explicitly — the cutoff must be live from the first batch
    service = RelationalScoringService(
        registry, group, max_batch=chunk, max_wait_ms=0.5, cache_size=8192,
        max_queue=256, latency_budget_ms=2000.0,
    )
    rng = np.random.default_rng(7)
    oracles = {}
    n0 = sch.table(group).n_rows

    import threading

    async def run():
        await service.start()
        # warm jit + message cache, pin the version-0 oracle, THEN attach
        # the SLO monitor so compile time doesn't burn the latency budget
        await service.score_many(rng.integers(0, n0, chunk).tolist())
        oracles[0] = ms.snapshot(roots=(group,), pin_oracle=True)
        service.slo = slo
        done = threading.Event()

        def ingest():
            # the stream is LAZY on live_rows — batches must be generated
            # against the rows they will apply to, version by version
            for batch in delta_stream(sch, ms.live_rows, seed=13,
                                      n_batches=n_batches,
                                      ops_per_batch=ops_per_batch):
                ms.apply(batch)
                oracles[ms.data_version] = ms.snapshot(roots=(group,),
                                                       pin_oracle=True)
                time.sleep(0.004)
            done.set()

        t = threading.Thread(target=ingest)
        t.start()
        max_stale = 0.0
        while not done.is_set():
            ids = np.minimum(rng.zipf(1.3, chunk) - 1, n0 - 1)
            await service.score_many(ids.tolist())
            max_stale = max(max_stale, service.stats.staleness_s.value)
        t.join()
        # one post-ingest round guarantees final-version cache entries
        await service.score_many(rng.integers(0, n0, chunk).tolist())
        await service.stop()
        return max_stale

    max_stale = asyncio.run(run())
    end_state = slo.state()
    compliance = slo.compliance("latency")

    # the isolation audit: every cached score vs the oracle pinned at
    # the data_version baked into its own cache key
    means = {}
    audited = 0
    for (kv, ep, dv, row), val in service.cache._d.items():
        assert kv == v and ep == registry.epoch(v)
        if dv not in means:
            tot, cnt = oracles[dv].recompute_oracle(group)
            tot, cnt = np.asarray(tot), np.asarray(cnt)
            means[dv] = (tot / np.maximum(cnt, np.float32(1.0))).astype(np.float32)
        assert val == float(means[dv][row]), (
            f"cached score at data_version {dv} row {row} does not match "
            f"its pinned recompute oracle — snapshot isolation violated")
        audited += 1
    assert len(means) > 1, "audit never spanned a version boundary"
    assert end_state == "healthy", (
        f"SLO left healthy under concurrent ingest: {end_state}")
    assert max_stale <= 10.0, f"staleness blew the objective: {max_stale:.3f}s"

    snap = service.stats_snapshot()
    return [{
        "bench": "S5", "deltas": n_batches, "requests": snap["requests"],
        "versions_audited": len(means), "cache_entries_audited": audited,
        "isolation_exact": True,
        "mixed_latency_compliance": round(compliance, 4),
        "latency_ms_p50": round(snap["latency_ms"]["p50"], 3),
        "latency_ms_p99": round(snap["latency_ms"]["p99"], 3),
        "max_staleness_s": round(max_stale, 4),
        "end_state": end_state,
        "errors": snap["errors"], "shed": snap["shed"],
    }]


def s4_sharded_scaling(n_fact=131072, n_dim=64, n_trees=4, depth=3):
    """Row-sharded vs unsharded scoring of one ensemble.

    The compiled factors carry integer-valued leaf-membership counts, so
    the cross-shard segment-⊕ re-association is exact: grouped scores
    must match the single-device run bit for bit, and the host-side edge
    accounting must be untouched by where the rows live.  The headline
    ``qps_scaling`` is bulk-pass throughput sharded ÷ unsharded.

    Trees are fit on a small fact table and compiled against a large one
    (the feature list of a star schema is fact-size independent): the
    bench times the serving regime where sharding pays — the per-row
    segment-⊕ over a big fact factor — without paying a big training
    run.  Small-problem sharding IS slower (collective setup dominates
    sub-ms passes); that regime is covered by the bit-equality tests,
    not timed here.
    """
    from repro.distributed import spmd
    from repro.launch.mesh import make_data_mesh

    n_dev = jax.device_count()
    train_sch = star_schema(seed=9, n_fact=1024, n_dim=n_dim)
    cfg = BoostConfig(n_trees=n_trees, depth=depth, mode="sketch",
                      ssr_mode="off")
    trees, _ = Booster(train_sch, cfg).fit()
    sch = star_schema(seed=9, n_fact=n_fact, n_dim=n_dim)

    c1 = QueryCounter()
    ens1 = compile_ensemble(sch, trees, counter=c1)
    tot1, cnt1 = score_grouped(ens1, "fact")
    e1 = c1.edges
    ms1 = _timeit(lambda: score_grouped(ens1, "fact"), n=5)

    row = {"bench": "S4", "devices": n_dev, "n_fact": n_fact,
           "bulk_ms_1dev": round(ms1, 1)}
    if n_dev == 1:
        row.update(bulk_ms_ndev=round(ms1, 1), qps_scaling=1.0,
                   bit_equal=True, edges_equal=True)
        return [row]

    mesh = make_data_mesh()
    cN = QueryCounter()
    with spmd.use_data_mesh(mesh):
        ensN = compile_ensemble(sch, trees, counter=cN)
    assert spmd.is_row_sharded(ensN.factors["fact"], mesh), \
        "fact factor did not shard"
    totN, cntN = score_grouped(ensN, "fact")
    eN = cN.edges
    msN = _timeit(lambda: score_grouped(ensN, "fact"), n=5)

    bit_equal = (np.array_equal(np.asarray(tot1), np.asarray(totN))
                 and np.array_equal(np.asarray(cnt1), np.asarray(cntN)))
    assert bit_equal, "sharded grouped scores diverged from single-device"
    assert e1 == eN, f"sharding changed the counted work: {e1} vs {eN}"
    row.update(bulk_ms_ndev=round(msN, 1),
               qps_scaling=round(ms1 / msN, 3),
               bit_equal=True, edges_equal=True)
    return [row]


def s6_durability(sch, trees, n_batches=16, ops_per_batch=4):
    """Durable delta log: append overhead, recovery time, replication lag.

    One deterministic delta stream (same seed ⇒ bit-identical batches)
    drives three apply loops: a warm-up (jit/compile caches), a measured
    loop with a group-committed :class:`WalWriter` attached, and an
    untimed replication loop where a :class:`WalFollower` tails a
    streaming writer into a live replica.  The overhead metric is read
    from the ``wal.append_ms`` histogram — the time actually spent
    inside ``append()`` (encode + CRC + write + group-commit fsyncs) as
    a fraction of the rest of the ingest loop — because differencing
    two whole apply loops buries the sub-ms append cost under jit
    dispatch noise.  A checkpoint lands mid-stream; after the writer
    closes, the full recovery path (newest checkpoint + WAL-tail
    replay) is timed cold.

    Invariants asserted inline: the follower replica, the recovered
    scorer, and the writer all serve bit-identical grouped scores at the
    final data_version.  Headline metrics — ``wal_append_overhead_pct``,
    ``recovery_replay_s``, ``replication_lag_p99_s`` — are pinned in
    baselines.json and gated by report.py --check.
    """
    import shutil
    import tempfile

    from repro.incremental.recover import recover_scorer, save_checkpoint
    from repro.incremental.wal import WalFollower, WalWriter
    from repro.obs import get_registry

    group = "fact"

    def apply_loop(ms, on_batch=None):
        """Apply the canonical stream; returns summed apply() seconds."""
        total = 0.0
        for bi, batch in enumerate(delta_stream(
                sch, ms.live_rows, seed=29, n_batches=n_batches,
                ops_per_batch=ops_per_batch)):
            t0 = time.perf_counter()
            ms.apply(batch)
            total += time.perf_counter() - t0
            if on_batch is not None:
                on_batch(bi, ms)
        return total

    # warm-up: same stream, same shapes — populates every jit cache the
    # measured loops will hit
    apply_loop(MaintainedScorer(compile_ensemble(sch, trees)))

    wal_dir = tempfile.mkdtemp(prefix="bench_wal_")
    ckpt_dir = os.path.join(wal_dir, "ckpt")
    rep_dir = tempfile.mkdtemp(prefix="bench_wal_follow_")
    try:
        # measured WAL pass — writer only, so the timing isolates the
        # append path (replication runs as its own phase below: a live
        # follower competes for the interpreter and would bill its
        # apply work to the writer loop)
        # count-based group commit only: the default 50ms interval flush
        # is an idle-writer latency bound, but at this loop's batch
        # cadence (slower than 50ms/batch) it degenerates to an fsync
        # per append and the metric stops measuring the append path
        ms_wal = MaintainedScorer(compile_ensemble(sch, trees))
        wal = WalWriter(wal_dir, sync_every=8,
                        sync_interval_s=60.0).attach(ms_wal.state)

        def on_batch(bi, ms):
            if bi + 1 == n_batches // 2:
                save_checkpoint(ms.state, ckpt_dir)

        h_append = get_registry().histogram("wal.append_ms")
        append_ms0 = h_append.sum
        t_wal = apply_loop(ms_wal, on_batch=on_batch)
        append_s = (h_append.sum - append_ms0) / 1e3
        wal.heartbeat()
        wal.sync()
        wal.close()
        want_t, want_c = ms_wal.grouped_cached(group)
        assert ms_wal.data_version == n_batches

        # cold recovery: newest checkpoint + WAL-tail replay
        t0 = time.perf_counter()
        recovered, rep = recover_scorer(
            compile_ensemble(sch, trees), wal_dir, ckpt_dir)
        recovery_s = time.perf_counter() - t0
        assert rep.recovered_lsn == ms_wal.data_version
        rec_t, rec_c = recovered.grouped_cached(group)
        assert (np.array_equal(np.asarray(want_t), np.asarray(rec_t))
                and np.array_equal(np.asarray(want_c), np.asarray(rec_c))), \
            "recovered scorer diverged from the writer"

        overhead_pct = 100.0 * append_s / max(t_wal - append_s, 1e-9)

        # replication phase (untimed): a live follower tails a streaming
        # writer into a second scorer; apply-lag is measured per record
        # from its WAL wall-clock stamp
        ms_src = MaintainedScorer(compile_ensemble(sch, trees))
        replica = MaintainedScorer(compile_ensemble(sch, trees))
        wal2 = WalWriter(rep_dir, sync_every=8).attach(ms_src.state)
        follower = WalFollower(rep_dir, replica.apply,
                               poll_interval_s=0.005).start()
        apply_loop(ms_src)
        wal2.heartbeat()
        wal2.sync()
        wal2.close()
        follower.stop(drain=True)
        src_t, src_c = ms_src.grouped_cached(group)
        got_t, got_c = replica.grouped_cached(group)
        assert (np.array_equal(np.asarray(src_t), np.asarray(got_t))
                and np.array_equal(np.asarray(src_c), np.asarray(got_c))), \
            "follower replica diverged from the writer"
        assert replica.data_version == ms_src.data_version == n_batches
        lag_p99 = get_registry().histogram(
            "wal.follower.apply_lag_s").quantile(0.99)

        return [{
            "bench": "S6", "deltas": n_batches,
            "apply_s_wal": round(t_wal, 4),
            "wal_append_s": round(append_s, 4),
            "wal_append_overhead_pct": round(overhead_pct, 2),
            "wal_bytes": os.path.getsize(wal.path),
            "checkpoint_lsn": rep.checkpoint_lsn,
            "replayed": rep.replayed,
            "recovery_replay_s": round(recovery_s, 4),
            "replication_lag_p99_s": round(lag_p99, 4),
            "replica_bit_equal": True, "recovered_bit_equal": True,
        }]
    finally:
        shutil.rmtree(wal_dir, ignore_errors=True)
        shutil.rmtree(rep_dir, ignore_errors=True)


def run_all(fast: bool = True):
    rows, sch, trees = s1_one_pass_vs_leaf_loop(
        n_fact=1000 if fast else 4000, n_trees=4 if fast else 6,
        depth=3,
    )
    rows += s2_service_qps(sch, trees, n_requests=1000 if fast else 5000)
    rows += s3_slo_mixed_workload(sch, trees, n_clean=6 if fast else 10,
                                  n_spike=4 if fast else 6)
    rows += s4_sharded_scaling(n_fact=131072 if fast else 262144)
    rows += s5_snapshot_isolation(sch, trees, n_batches=6 if fast else 12)
    rows += s6_durability(sch, trees, n_batches=16 if fast else 40)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="fast sizes (the default; named for CI legs)")
    args = ap.parse_args(argv)
    rows = run_all(fast=not args.full)
    for r in rows:
        print(r)
    s1 = next(r for r in rows if r["bench"] == "S1")
    s2 = next(r for r in rows if r["bench"] == "S2")
    s3 = next(r for r in rows if r["bench"] == "S3")
    s4 = next(r for r in rows if r["bench"] == "S4")
    s5 = next(r for r in rows if r["bench"] == "S5")
    s6 = next(r for r in rows if r["bench"] == "S6")
    emit("serving", rows, {
        "eval_ratio": s1["eval_ratio"],
        "qps": s2["qps"],
        "cache_hit_pct": s2["cache_hit_pct"],
        "latency_ms_p99": s2["latency_ms_p99"],
        "slo_latency_compliance": s3["clean_latency_compliance"],
        "slo_spike_detected": 1.0 if (s3["spike_state"] != "healthy"
                                      and s3["flight_dumps"] > 0) else 0.0,
        "qps_scaling_8dev": s4["qps_scaling"],
        "mixed_latency_compliance": s5["mixed_latency_compliance"],
        "snapshot_isolation_exact": 1.0 if (s5["isolation_exact"]
                                            and s5["end_state"] == "healthy")
                                    else 0.0,
        "wal_append_overhead_pct": s6["wal_append_overhead_pct"],
        "recovery_replay_s": s6["recovery_replay_s"],
        "replication_lag_p99_s": s6["replication_lag_p99_s"],
        "durability_exact": 1.0 if (s6["replica_bit_equal"]
                                    and s6["recovered_bit_equal"]) else 0.0,
    }, config={"full": args.full, "devices": jax.device_count()})
    return rows


if __name__ == "__main__":
    main()
