"""End-to-end driver (the paper's kind is a graph-mining operator, so the
end-to-end application is a multi-tenant clique-analytics service):

  1. ingest a stream of graph snapshots (synthetic RMAT / power-law) and
     register each with a long-lived ``repro.serve.CliqueService``;
  2. submit every tenant's queries CONCURRENTLY -- exact counts at
     several k, plus a materializing "top-N k-cliques containing vertex
     v" listing query per snapshot, each with its own latency deadline;
  3. the service coalesces ready tiles from different requests into
     shared fixed-shape device batches (continuous batching: fused
     batches reuse the same warm executables as single-query traffic),
     schedules pulls EDF-first under the deadlines, and routes exact
     counts / byte-identical clique rows back to per-request sinks;
  4. preprocessing is shared: each snapshot is truss-decomposed ONCE into
     a cached PipelinePlan (in-process keyed cache, plus an on-disk store
     with --plan-cache so a restarted service skips it entirely);
  5. the run ends with per-request latencies and the service's own
     accounting (fused batches, cross-request batches, deadline misses).

    PYTHONPATH=src python examples/clique_service.py --snapshots 3 --k 5
    # multi-device serving on a CPU host:
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        PYTHONPATH=src python examples/clique_service.py --snapshots 3
"""
import argparse
import time

import numpy as np

from repro.data import powerlaw_graph, rmat_graph
from repro.obs import trace
from repro.obs.logging import LEVELS, setup_logging
from repro.serve import CliqueService


def snapshot(i: int):
    """Synthetic tenant graph stream: alternating RMAT / power-law."""
    if i % 2 == 0:
        return f"rmat-{i}", rmat_graph(11, 6, seed=100 + i)
    return f"powerlaw-{i}", powerlaw_graph(2500, 10, seed=100 + i)


def main():
    """Ingest snapshots, serve all tenants' queries concurrently."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--snapshots", type=int, default=3)
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--topn", type=int, default=5,
                    help="N for the top-N cliques-containing-v query")
    ap.add_argument("--deadline", type=float, default=30.0, metavar="S",
                    help="per-query latency deadline in seconds (EDF "
                         "scheduling + miss accounting, never cancellation)")
    ap.add_argument("--backend", default=None,
                    choices=["auto", "pallas", "lax", "ref", "autotune"],
                    help="kernel backend for all queries (default auto = "
                         "compiled lax on CPU hosts)")
    ap.add_argument("--plan-cache", default=None, metavar="DIR",
                    help="on-disk plan store: a restarted service reloads "
                         "each snapshot's truss order + tile tables "
                         "instead of re-decomposing (keyed by graph "
                         "content, see pipeline.cached_plan)")
    ap.add_argument("--tune-cache", default=None, metavar="DIR",
                    help="persistent autotuner directory (repro.tune): "
                         "a restarted service reuses tuned backend/geometry "
                         "records and XLA's persistent compilation cache "
                         "instead of re-measuring and re-compiling")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="chaos mode: seeded repro.resilience fault plan "
                         "(e.g. 'seed=7;*=0.1'); the service keeps every "
                         "answer exact via retry/demotion")
    ap.add_argument("--log-level", default="warning", choices=list(LEVELS),
                    help="repro.* logger verbosity (obs/logging)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto span trace of the whole "
                         "serving run (per-request async tracks included)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve Prometheus /metrics from the service on "
                         "127.0.0.1:PORT (0 = ephemeral port)")
    args = ap.parse_args()
    setup_logging(args.log_level)
    if args.trace_out:
        trace.configure(enabled=True)
    if args.tune_cache:
        from repro import tune

        tune.configure(args.tune_cache)
    if args.fault_plan:
        from repro.resilience import inject

        inject.configure(args.fault_plan)
        print(f"fault injection: {args.fault_plan}")

    svc = CliqueService(backend=None if args.backend == "auto"
                        else args.backend,
                        plan_cache_dir=args.plan_cache,
                        metrics_port=args.metrics_port)
    if svc.metrics_address:
        print(f"metrics: {svc.metrics_address}/metrics")
    graphs = {}
    for i in range(args.snapshots):
        name, g = snapshot(i)
        graphs[name] = g
        svc.register_graph(name, g)

    # every tenant submits at once: two counting queries plus the top-N
    # materializing query per snapshot, all inside one serving pipeline
    t0 = time.time()
    tickets = []
    for name, g in graphs.items():
        for k in (args.k, args.k + 1):
            tickets.append((name, f"count k={k}",
                            svc.submit(name, k, "count",
                                       deadline_s=args.deadline)))
        v = int(np.argmax(g.degrees()))
        tickets.append((name, f"top-{args.topn} {args.k}-cliques @ v={v}",
                        svc.submit(name, args.k, "list", vertex_filter=v,
                                   max_out=args.topn,
                                   deadline_s=args.deadline)))
    print(f"submitted {len(tickets)} concurrent queries over "
          f"{len(graphs)} snapshots "
          f"({svc.engine_stats.backend or 'auto'} backend)")

    for name, what, ticket in tickets:
        res = ticket.result()
        late = " LATE" if res.deadline_missed else ""
        if res.kind == "count":
            g = graphs[name]
            print(f"[{name}] {what}: {res.count} "
                  f"({res.count / max(g.n, 1):.2f}/vertex, "
                  f"{res.latency_s * 1e3:.0f}ms{late})")
        else:
            first = (f" first={res.rows[0].tolist()}"
                     if res.rows.shape[0] else "")
            print(f"[{name}] {what}: {res.rows.shape[0]} found "
                  f"({res.latency_s * 1e3:.0f}ms{late}){first}")
    wall = time.time() - t0

    s = svc.stats
    print(f"served {s.completed} requests in {wall:.2f}s: "
          f"{s.fused_batches} device batches "
          f"({s.cross_request_batches} cross-request, "
          f"{s.fused_chunks} chunks fused, {s.spill_tiles} host spills), "
          f"{s.deadline_missed} deadline misses")
    svc.close()
    if args.trace_out:
        trace.export(args.trace_out)
        print(f"trace: wrote {args.trace_out} "
              f"({len(trace.events())} events)")


if __name__ == "__main__":
    from repro import tune

    tune.enable_compilation_cache()
    main()
