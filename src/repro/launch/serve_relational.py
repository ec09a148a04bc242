"""Relational serving driver: train → compile → micro-batch serve.

Trains a booster on a synthetic relational workload, compiles the
ensemble into the one-pass scorer, publishes it to a versioned registry,
and drives the async micro-batching service with synthetic interactive
traffic (zipf-skewed row ids — the regime where the LRU cache earns its
keep).  Ends with a hot-swap: a refreshed model is published mid-traffic
and new requests pick it up with zero downtime.

    PYTHONPATH=src python -m repro.launch.serve_relational --requests 2000

Sharded serving: `--devices 8 --mesh 8` forces 8 host XLA devices (set
before any jax import — that's why the _devices import leads) and
compiles the ensemble with row-sharded factors over a ("data",) mesh.
"""
from __future__ import annotations

from repro.launch._devices import (          # noqa: I001  (must precede
    add_device_args, apply_early_device_flags, resolve_mesh)   # jax imports)

apply_early_device_flags()

import argparse
import asyncio
import dataclasses
import os
import time

import numpy as np

from repro.core import BoostConfig, Booster, QueryCounter
from repro.distributed import spmd
from repro.obs import (
    FlightRecorder, PeriodicSampler, SLOMonitor, TelemetryServer,
    enable_tracing, format_summary_table, get_registry, get_tracer,
    merge_snapshots, parse_slo_spec,
)
from repro.relational import generators
from repro.serving import (
    ModelRegistry, RelationalScoringService, ServiceOverloadedError,
    compile_ensemble,
)


def build_schema(args):
    if args.schema == "star":
        return generators.star_schema(seed=args.seed, n_fact=args.n_fact, n_dim=args.n_dim)
    if args.schema == "chain":
        return generators.chain_schema(seed=args.seed, n_rows=args.n_fact)
    if args.schema == "snowflake":
        return generators.snowflake_schema(seed=args.seed, n_fact=args.n_fact, n_dim=args.n_dim)
    raise ValueError(args.schema)


def train(schema, args, seed=0):
    cfg = BoostConfig(n_trees=args.trees, depth=args.depth, mode="sketch",
                      ssr_mode="off", seed=seed)
    booster = Booster(schema, cfg)
    trees, _ = booster.fit()
    return trees


async def drive(service, n_rows, n_requests, concurrency, zipf_a, registry,
                schema, args, counter, telemetry=None, hot_swap=True):
    rng = np.random.default_rng(1)
    ids = np.minimum(rng.zipf(zipf_a, n_requests) - 1, n_rows - 1)
    await service.start()
    if telemetry is not None:
        await telemetry.start()
        print(f"telemetry: {telemetry.url('/metricsz')}  "
              f"{telemetry.url('/healthz')}  {telemetry.url('/statusz')}  "
              f"{telemetry.url('/tracez')}")
    # jit warmup outside the SLO clock: the first batch pays compile
    # time, which would read as an instant budget burn and trip the
    # shedder before any real traffic
    saved_slo, service.slo = service.slo, None
    await service.score_many(ids[:64].tolist())
    service.slo = saved_slo
    shed_chunks = 0
    t0 = time.perf_counter()
    for chunk in np.array_split(ids, max(1, n_requests // concurrency)):
        try:
            await service.score_many(chunk.tolist())
        except ServiceOverloadedError:   # open loop: shed work is dropped
            shed_chunks += 1
    dt = time.perf_counter() - t0
    qps = n_requests / dt
    if shed_chunks:
        print(f"admission control shed {shed_chunks} chunk(s) "
              f"({service.stats.shed} requests) while unhealthy")
    snap = service.stats_snapshot()
    lat, qw = snap["latency_ms"], snap["queue_wait_ms"]
    print(f"served {snap['requests']} requests in {dt:.2f}s → {qps:,.0f} QPS")
    print(f"latency: p50 {lat['p50']:.2f} ms, p99 {lat['p99']:.2f} ms "
          f"(queue wait p50 {qw['p50']:.2f} / p99 {qw['p99']:.2f} ms)")
    print(f"batches: {snap['batches']} (mean size {snap['mean_batch']:.1f}), "
          f"cache hit rate {100 * snap['cache_hit_rate']:.1f}%")

    if hot_swap:
        # hot swap: publish a refreshed model mid-traffic (same kernel
        # route, query accounting and mesh placement as v1)
        with spmd.use_data_mesh(getattr(args, "_mesh", None)):
            v2 = registry.publish(compile_ensemble(
                schema, train(schema, args, seed=7),
                use_kernel=args.kernel, counter=counter,
            ))
        more = rng.integers(0, n_rows, 64)
        try:
            out = await service.score_many(more.tolist())
            print(f"hot-swapped to version {v2}; {len(out)} post-swap "
                  f"requests OK (sample score {out[0]:+.3f})")
        except ServiceOverloadedError:
            print(f"hot-swapped to version {v2}; post-swap requests shed "
                  f"(SLO state unhealthy)")
    if service.slo is not None:
        rep = service.slo.evaluate()
        objs = "  ".join(
            f"{n}: burn {o['burn_fast']:.2f}/{o['burn_slow']:.2f} [{o['state']}]"
            for n, o in rep["objectives"].items())
        print(f"SLO state: {rep['state']}  ({objs})")
    if telemetry is not None:
        await telemetry.stop()
    await service.stop()
    return qps


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--schema", default="star",
                    choices=["star", "chain", "snowflake"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-fact", type=int, default=2000)
    ap.add_argument("--n-dim", type=int, default=64)
    ap.add_argument("--trees", type=int, default=5)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--requests", type=int, default=2000)
    ap.add_argument("--concurrency", type=int, default=256)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--max-wait-ms", type=float, default=1.0)
    ap.add_argument("--cache-size", type=int, default=4096)
    ap.add_argument("--zipf", type=float, default=1.3)
    ap.add_argument("--kernel", action="store_true",
                    help="route the segment-⊕ through the Pallas kernel")
    ap.add_argument("--follow", metavar="WAL_DIR", default=None,
                    help="follower mode: recover a read-only replica from "
                         "this WAL dir (+ its ckpt/ checkpoints) and tail "
                         "the writer's log live; replication lag feeds the "
                         "SLO staleness objective (degrade-only — a dead "
                         "writer degrades the replica, never kills it)")
    ap.add_argument("--follow-poll-ms", type=float, default=10.0,
                    help="follower tail-poll interval")
    ap.add_argument("--heartbeat-grace-s", type=float, default=5.0,
                    help="writer idle time beyond which the follower "
                         "reports the idle age as staleness (writer "
                         "presumed dead past its heartbeat cadence)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="record spans and write a Chrome trace "
                         "(open in Perfetto) plus PATH.jsonl")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve /metricsz /healthz /statusz /tracez on this "
                         "port (0 = ephemeral, printed on start)")
    ap.add_argument("--slo", metavar="SPEC", default=None,
                    help="SLO objectives, e.g. "
                         "'latency=50ms@0.99,errors=0.01,staleness=5s' — "
                         "burn-rate state feeds /healthz and admission control")
    ap.add_argument("--flight", type=int, default=None, metavar="N",
                    help="always-on flight recorder keeping the last N spans "
                         "(O(1) memory ring; dumps FLIGHT_serve_*.json)")
    ap.add_argument("--flight-latency-ms", type=float, default=None,
                    help="dump the flight ring when a request exceeds this "
                         "latency (requires --flight)")
    ap.add_argument("--sample", metavar="PATH", default=None,
                    help="append periodic metric-snapshot deltas to this "
                         "JSONL time series")
    ap.add_argument("--sample-interval", type=float, default=1.0)
    add_device_args(ap)
    args = ap.parse_args(argv)

    if args.trace:
        enable_tracing()

    mesh = resolve_mesh(args)
    args._mesh = mesh                       # drive()'s hot-swap recompile
    schema = build_schema(args)
    with spmd.use_data_mesh(mesh):
        trees = train(schema, args)
        counter = QueryCounter()
        ens = compile_ensemble(schema, trees, use_kernel=args.kernel,
                               counter=counter)
    group = schema.label_table
    print(f"compiled ensemble: {ens.n_trees} trees, {ens.total_leaves} stacked "
          f"leaves over {schema.n_tables} tables (group_by={group})"
          + (f" [data-parallel over {spmd.data_axis_size(mesh)} devices]"
             if mesh is not None else ""))

    # follower mode: the served model is a recovered replica driven by a
    # WAL tail from another process's writer, not the fresh compile
    follower = None
    serve_model = ens
    if args.follow:
        from repro.incremental.recover import recover_scorer
        from repro.incremental.wal import WalFollower

        ckpt_dir = os.path.join(args.follow, "ckpt")
        with spmd.use_data_mesh(mesh):
            serve_model, rep = recover_scorer(
                ens, args.follow,
                ckpt_dir if os.path.isdir(ckpt_dir) else None,
                counter=counter)
        print(f"follower: recovered to data_v{rep.recovered_lsn} "
              f"(checkpoint lsn {rep.checkpoint_lsn} + {rep.replayed} "
              f"replayed, {rep.tail_bytes_discarded}B torn tail discarded)")
        follower = WalFollower(
            args.follow, serve_model.apply, start_lsn=rep.recovered_lsn,
            poll_interval_s=args.follow_poll_ms / 1e3).start()

    slo = None
    if args.slo:
        objectives = parse_slo_spec(args.slo)
        if follower is not None:
            # a dead/lagging writer must degrade the replica (serve
            # stale), never shed its traffic — cap staleness at degraded
            objectives = [dataclasses.replace(o, degrade_only=True)
                          if o.kind == "staleness" else o
                          for o in objectives]
        slo = SLOMonitor(objectives,
                         fast_window_s=5.0, slow_window_s=30.0)
    flight = None
    if args.flight:
        flight = FlightRecorder(
            capacity=args.flight, name="serve",
            latency_trigger_ms=args.flight_latency_ms, cooldown_s=5.0,
        ).start()

    registry = ModelRegistry()
    v1 = registry.publish(serve_model)
    extra_staleness = None
    if follower is not None:
        grace = args.heartbeat_grace_s

        def extra_staleness():
            # served data lags by the undrained log tail; once drained,
            # a writer silent past its heartbeat cadence is presumed
            # dead and its idle age becomes the staleness signal
            return max(follower.replication_lag_s(),
                       max(0.0, follower.writer_idle_s() - grace))

    service = RelationalScoringService(
        registry, group, max_batch=args.max_batch,
        max_wait_ms=args.max_wait_ms, cache_size=args.cache_size,
        slo=slo, flight=flight, extra_staleness=extra_staleness,
    )
    telemetry = None
    if args.metrics_port is not None:
        telemetry = TelemetryServer(
            registries=[get_registry(), service.stats.registry],
            slo=slo, flight=flight, port=args.metrics_port,
            status_fn=lambda: {
                "model_version": registry.latest_version(),
                "stats": service.stats_snapshot(),
            },
        )
    sampler = None
    if args.sample:
        sampler = PeriodicSampler(
            args.sample, interval_s=args.sample_interval,
            registries=[get_registry(), service.stats.registry],
            extra_fn=lambda: {"slo_state": slo.state() if slo else None},
        ).start()
    n_rows = schema.table(group).n_rows
    qps = asyncio.run(drive(service, n_rows, args.requests, args.concurrency,
                            args.zipf, registry, schema, args, counter,
                            telemetry=telemetry, hot_swap=follower is None))
    if follower is not None:
        # a replica that failed to drain served a log it could not apply:
        # the error propagates and the process exits non-zero
        follower.stop(drain=True)
        print(f"follower: applied through lsn {follower.applied_lsn}, "
              f"replication lag {follower.replication_lag_s():.3f}s, "
              f"writer idle {follower.writer_idle_s():.1f}s")
    if sampler is not None:
        sampler.stop()
        print(f"wrote {sampler.samples} telemetry samples to {args.sample}")
    if flight is not None:
        flight.stop()
        st = flight.status()
        print(f"flight recorder: {st['buffered']} spans buffered, "
              f"{len(st['dumps'])} dump(s), {st['suppressed']} suppressed")
    print(f"SumProd evaluations for all traffic: {counter.count} "
          f"(seed loop would need {args.trees * 2 ** args.depth + 1} per bulk pass)")
    # one-screen exit summary: process-wide series ⊎ this service's
    print(format_summary_table(
        merge_snapshots(get_registry().snapshot(),
                        service.stats.registry.snapshot()),
        title="serve_relational metrics"))
    if args.trace:
        n = get_tracer().dump_chrome_trace(args.trace)
        get_tracer().dump_jsonl(args.trace + ".jsonl")
        print(f"wrote {n} spans to {args.trace} (chrome://tracing / Perfetto)")
    return qps


if __name__ == "__main__":
    main()
