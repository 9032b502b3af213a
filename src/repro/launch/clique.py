"""Distributed k-clique counting driver (the paper's operator as a service).

``python -m repro.launch.clique --graph rmat:14 --k 5 --devices all``

Pipeline: host preprocessing (truss order cached in a PipelinePlan) ->
vectorized extraction + capacity-batched packing (repro.core.pipeline) ->
LPT cost-balanced batch scheduling (Section 6.2(7) EdgeParallel; scheduler
bins map one-to-one onto real local devices, repro.runtime.dispatch) ->
per-device jit kernels with double-buffered host->device staging -> exact
host combine.  Oversize tiles spill to the host recursion instead of
aborting.  On this CPU container it runs on however many host devices
exist (``XLA_FLAGS=--xla_force_host_platform_device_count=N`` forges N);
the 512-way layout is exercised by dryrun.py.
"""
from __future__ import annotations

import argparse
import time

from ..core import ebbkc, engine_jax, listing, pipeline
from ..core import tiles as tiles_mod
from ..core.engine_np import Stats
from ..core.graph import Graph
from ..data import graphs as gdata
from ..launch.mesh import make_local_mesh
from ..obs import metrics as obs_metrics
from ..obs import trace
from ..obs.export import MetricsServer
from ..obs.logging import LEVELS, get_logger, setup_logging
from ..runtime.dispatch import dispatch_scheduled, resolve_devices
from .. import tune
from ..tune import search as tune_search


def load_graph(desc: str) -> Graph:
    kind, _, arg = desc.partition(":")
    if kind == "rmat":
        return gdata.rmat_graph(int(arg or 12), edge_factor=8, seed=7)
    if kind == "er":
        n, p = arg.split(",")
        return gdata.erdos_renyi(int(n), float(p), seed=7)
    if kind == "powerlaw":
        return gdata.powerlaw_graph(int(arg or 2000), 16, seed=7)
    if kind == "planted":
        return gdata.planted_cliques(int(arg or 2000), 30, 12, seed=7)
    raise ValueError(f"unknown graph spec {desc}")


def parse_devices(spec: str):
    """CLI device spec: "all" or an int count (graceful clamp)."""
    return "all" if spec == "all" else int(spec)


def _finish_obs(args, stats, metrics_server):
    """Flush run observability: publish stats, export trace, stop server."""
    if stats is not None:
        obs_metrics.observe_stats(stats)
    if args.trace_out:
        trace.export(args.trace_out)
        print(f"trace: wrote {args.trace_out} "
              f"({len(trace.events())} events, "
              f"{trace.dropped()} dropped)")
    if metrics_server is not None:
        metrics_server.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="rmat:12")
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--order", default="hybrid")
    ap.add_argument("--batch-size", type=int, default=None,
                    help="tiles per packed batch (default: tuned geometry "
                         "record if present, else 256)")
    ap.add_argument("--devices", default="all",
                    help='"all" or device count (clamped to available)')
    ap.add_argument("--backend", default=None,
                    choices=["auto", "pallas", "lax", "ref", "autotune"],
                    help="kernel backend (repro.kernels.ops registry); "
                         "default auto = compiled lax on every platform "
                         "(Mosaic refuses the Pallas kernels on TPU); "
                         "also settable via REPRO_BACKEND")
    ap.add_argument("--shard-map", action="store_true",
                    help="shard each batch over a device mesh instead of "
                         "LPT-placing whole batches on devices")
    ap.add_argument("--offline-lpt", action="store_true",
                    help="materialize all batches, then map schedule_batches"
                         " LPT bins one-to-one onto devices (prints balance;"
                         " default is streaming online-LPT dispatch, which"
                         " overlaps packing with device execution and keeps"
                         " host memory bounded)")
    ap.add_argument("--sync-staging", action="store_true",
                    help="disable double-buffered host->device staging")
    ap.add_argument("--list", action="store_true", dest="list_mode",
                    help="materialize the cliques through the emission "
                         "subsystem instead of counting them")
    ap.add_argument("--sink", default=None, metavar="PATH",
                    help="with --list: write the cliques to PATH as an NPZ "
                         "(key 'cliques'); default is an in-memory buffer")
    ap.add_argument("--max-out", type=int, default=None,
                    help="with --list: stop after this many cliques")
    ap.add_argument("--pack-workers", type=int, default=None,
                    help="pack worker processes (default auto; "
                         "0 = serial inline packing)")
    ap.add_argument("--plan-cache", default=None, metavar="DIR",
                    help="persist the PipelinePlan (truss order + tile "
                         "tables) under DIR, keyed by graph content: a "
                         "repeated invocation on the same graph skips the "
                         "O(delta m) decomposition entirely")
    ap.add_argument("--tune-cache", default=None, metavar="DIR",
                    help="persistent autotuner directory (repro.tune): "
                         "backend/geometry tuning records, so a repeated "
                         "invocation skips microbenchmarks; also settable "
                         "via REPRO_TUNE_CACHE")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="chaos mode: seeded fault-injection plan for "
                         "repro.resilience (e.g. 'seed=7;*=0.1;"
                         "kernel.launch=0.3'); results stay exact via "
                         "retry/demotion; also settable via "
                         "REPRO_FAULT_PLAN")
    ap.add_argument("--verify", action="store_true",
                    help="cross-check against the host engine")
    ap.add_argument("--log-level", default="warning", choices=list(LEVELS),
                    help="repro.* logger verbosity (obs/logging)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record a structured span trace of the whole run "
                         "and write it as Chrome/Perfetto trace_event JSON "
                         "(open at https://ui.perfetto.dev)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    metavar="PORT",
                    help="serve Prometheus /metrics on 127.0.0.1:PORT for "
                         "the run's duration (0 = ephemeral port)")
    args = ap.parse_args()

    setup_logging(args.log_level)
    log = get_logger("launch.clique")
    if args.trace_out:
        trace.configure(enabled=True)
    metrics_server = None
    if args.metrics_port is not None:
        metrics_server = MetricsServer(port=args.metrics_port)
        print(f"metrics: {metrics_server.address}/metrics")
    if args.tune_cache:
        tune.configure(args.tune_cache)
    if args.fault_plan:
        from ..resilience import inject

        inject.configure(args.fault_plan)
        print(f"fault injection: {args.fault_plan}")
    g = load_graph(args.graph)
    log.info("loaded %s: n=%d m=%d", args.graph, g.n, g.m)
    print(f"graph: n={g.n} m={g.m}")
    l = args.k - 2
    devices = resolve_devices(parse_devices(args.devices))
    n_dev = len(devices)
    mesh = None
    if args.shard_map:
        mesh = make_local_mesh((n_dev, 1), axes=("data", "model"))

    t0 = time.time()
    plan_stats = Stats()
    plan = pipeline.cached_plan(g, order=args.order,
                                cache_dir=args.plan_cache, stats=plan_stats)
    t_plan = time.time() - t0
    if args.plan_cache:
        src = "warm (decomposition skipped)" if plan_stats.plan_cache_hit \
            else f"cold (built in {plan_stats.plan_build_s:.2f}s, saved)"
        print(f"plan cache [{args.plan_cache}]: {src}")

    if args.list_mode:
        sink = (listing.NpzSink(args.sink, args.k, max_out=args.max_out)
                if args.sink
                else listing.ArraySink(args.k, max_out=args.max_out))
        t0 = time.time()
        res = listing.stream_cliques(
            plan, args.k, sink, order=args.order,
            batch_size=args.batch_size, devices=devices,
            backend=args.backend,
            pack_workers=args.pack_workers,
            async_staging=not args.sync_staging)
        t_list = time.time() - t0
        sink.close()
        st = res.stats
        rate = st.emitted_cliques / max(t_list, 1e-9)
        print(f"k={args.k}: listed {st.emitted_cliques} cliques in "
              f"{t_list:.2f}s ({rate:.0f} cliques/s, "
              f"{st.sink_bytes} sink bytes"
              f"{', -> ' + args.sink if args.sink else ''})")
        print(f"tiles={res.tiles} spilled={st.spilled_tiles} "
              f"overflowed={st.overflowed_tiles} devices={n_dev} "
              f"backend={st.backend} compile={st.kernel_compile_s:.2f}s "
              f"tune={st.tune_s:.2f}s tune_hit={st.tune_cache_hit} "
              f"pack_workers={st.pack_workers} "
              f"frontend={st.frontend_s:.2f}s "
              f"queue_occ={st.pack_queue_occupancy:.2f}")
        match = True
        if args.verify:
            ref = ebbkc.count(g, args.k, order=args.order, plan=plan).count
            want = ref if args.max_out is None else min(args.max_out, ref)
            match = want == st.emitted_cliques
            print(f"host count: {ref}  match={match}")
        _finish_obs(args, st, metrics_server)
        if not match:
            raise SystemExit("verify failed: listed count != host count")
        return

    t0 = time.time()
    stage = {}
    info = {}
    batch_txt = ""
    if args.offline_lpt:
        # materialize, then scheduler bins become real devices
        stats = Stats()
        geom = tune_search.resolve_geometry("count", l,
                                            batch_size=args.batch_size,
                                            pack_workers=args.pack_workers)
        stream = pipeline.stream_batches(plan, args.k, order=args.order,
                                         batch_size=geom.batch_size,
                                         bins=geom.bins,
                                         timings=stage,
                                         pack_workers=geom.pack_workers,
                                         prefetch=geom.prefetch,
                                         stats=stats)
        batches = []
        total = 0
        n_tiles = 0
        for item in stream:
            if isinstance(item, tiles_mod.Tile):
                n_tiles += 1
                total += engine_jax.count_spilled(item, args.order, l, stats,
                                                  et_t=3, use_rule2=True)
            else:
                batches.append(item)
                n_tiles += item.B
        got, info = dispatch_scheduled(
            batches, l, devices, mesh=mesh, backend=args.backend,
            async_staging=not args.sync_staging, stats=stats)
        total += got
        batch_txt = f"batches={len(batches)} "
    else:
        # streaming: pack(i+1) on the host overlaps kernel(i) on devices
        res = engine_jax.count(g, args.k, order=args.order, plan=plan,
                               batch_size=args.batch_size,
                               stage_times=stage, devices=devices, mesh=mesh,
                               async_staging=not args.sync_staging,
                               backend=args.backend,
                               pack_workers=args.pack_workers)
        total, n_tiles, stats = res.count, res.tiles, res.stats
    t_count = time.time() - t0
    # packing is interleaved with counting; stream_batches bills it apart
    t_pack = stage.get("extract", 0.0) + stage.get("pack", 0.0)

    balance = info.get("max_over_mean")
    bal_txt = f" balance max/mean={balance:.3f}" if balance else ""
    print(f"{batch_txt}tiles={n_tiles} "
          f"spilled={stats.spilled_tiles} devices={n_dev}"
          f"{' (shard_map)' if mesh is not None else ''}{bal_txt}")
    per_dev = " ".join(
        f"d{d}:{stats.device_tiles[d]}t/{stats.device_flops[d] / 1e6:.0f}MF"
        for d in sorted(stats.device_tiles))
    print(f"device tiles/flops: {per_dev or '-'} "
          f"staging_overlap={stats.staging_overlap_s:.2f}s "
          f"backend={stats.backend} compile={stats.kernel_compile_s:.2f}s "
          f"tune={stats.tune_s:.2f}s tune_hit={stats.tune_cache_hit}")
    print(f"k={args.k}: {total} cliques "
          f"(plan {t_plan:.2f}s, front-to-finish {t_count:.2f}s, "
          f"of which extract+pack {t_pack:.2f}s; "
          f"pack_workers={stats.pack_workers} "
          f"queue_occ={stats.pack_queue_occupancy:.2f})")
    match = True
    if args.verify:
        ref = ebbkc.count(g, args.k, order=args.order, plan=plan).count
        match = ref == total
        print(f"host engine: {ref}  match={match}")
    _finish_obs(args, stats, metrics_server)
    if not match:
        raise SystemExit("verify failed: device count != host count")


if __name__ == "__main__":
    tune.enable_compilation_cache()
    main()
