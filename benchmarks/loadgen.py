"""Open/closed-loop load generator for the serving tier.

Drives a mixed count+listing workload at ``repro.serve.CliqueService``
(and, for comparison, at a serial one-query-at-a-time executor built on
the plain engines), measuring per-request latency (p50/p90/p99), goodput
(deadline-meeting completions per second), deadline-miss rate, and
backpressure rejections -- and verifying every response against a
precomputed oracle (exact counts; byte-identical clique rows).  Results
land in the BENCH json format with the full loadgen config recorded in
each row, so serving capacity is a tracked number like every other
benchmark.

Closed loop (the default): ``--clients N`` threads each submit
``--requests-per-client M`` requests back to back (a new request only
after the previous response).  Open loop: ``--rates R1,R2,...`` sweeps
Poisson arrivals at each rate for ``--duration`` seconds; arrivals that
find the admission queue full are shed and counted as rejected.

    # the BENCH_pr7.json acceptance run: 8-client closed loop, serve vs
    # serial, >= 1.5x goodput at no worse p99
    PYTHONPATH=src python -m benchmarks.loadgen --mode both --clients 8 \\
        --requests-per-client 4 --graphs rmat:8,er:300,0.08 --ks 4,5 \\
        --list-frac 0.4 --json BENCH_pr7.json --assert-goodput-x 1.5

    # CI serve-smoke: short mixed workload at 1 and 4 virtual devices
    PYTHONPATH=src python -m benchmarks.loadgen --virtual-devices 4 \\
        --clients 4 --requests-per-client 2 --graphs rmat:8 --ks 4,5 \\
        --list-frac 0.5 --json serve_smoke.json

The workload is fully seeded: the same ``--seed`` produces the same
request multiset in every mode, which is what makes the serve-vs-serial
goodput ratio and the oracle comparison meaningful.
"""
from __future__ import annotations

import argparse
import json
import os
import queue
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def build_workload(graphs, ks, n_requests, list_frac, filter_frac, max_out,
                   deadline_ms, seed):
    """The seeded request multiset: one spec dict per request.

    Specs cycle deterministically through the graph/k grid with a
    seeded RNG choosing mode/filter, so every mode of every run on the
    same seed serves exactly the same work.
    """
    rng = np.random.default_rng(seed)
    specs = []
    for i in range(n_requests):
        gname = graphs[i % len(graphs)]
        k = int(ks[(i // len(graphs)) % len(ks)])
        is_list = bool(rng.random() < list_frac)
        spec = {
            "graph": gname,
            "k": k,
            "mode": "list" if is_list else "count",
            "vertex_filter": None,
            "max_out": None,
            "deadline_s": deadline_ms / 1e3 if deadline_ms else None,
        }
        if is_list and rng.random() < filter_frac:
            spec["vertex_filter"] = int(rng.integers(0, 64))
        if is_list and max_out:
            spec["max_out"] = int(max_out)
        specs.append(spec)
    return specs


def build_oracle(graph_objs, specs, backend):
    """Exact expected answers plus per-spec solo latencies.

    Counts come from ``engine_jax.count``; listing rows from one full
    ``stream_cliques`` per (graph, k), filtered/truncated with the same
    ``apply_vertex_filter``-then-``max_out`` semantics the service uses.
    Each distinct (graph, k, mode) is run twice -- warm executables,
    then a timed run -- so ``solo_s`` is the request's isolated warm
    latency, the basis of proportional SLOs (``--deadline-x``).

    Returns ``(oracle, solo_s)``: expected result per spec key, and
    isolated seconds per ``(graph, k, mode)``.
    """
    from repro.core import engine_jax, listing
    from repro.serve import apply_vertex_filter

    counts = {}
    rows = {}
    solo = {}
    oracle = {}
    for spec in specs:
        key = _spec_key(spec)
        g = graph_objs[spec["graph"]]
        gkm = (spec["graph"], spec["k"], spec["mode"])
        if spec["mode"] == "count":
            if gkm not in solo:
                engine_jax.count(g, spec["k"], backend=backend)  # warm
                t0 = time.perf_counter()
                counts[gkm] = engine_jax.count(g, spec["k"],
                                               backend=backend).count
                solo[gkm] = time.perf_counter() - t0
            if key not in oracle:
                oracle[key] = counts[gkm]
        else:
            if gkm not in solo:
                sink = listing.ArraySink(spec["k"])
                listing.stream_cliques(g, spec["k"], sink, backend=backend)
                rows[gkm] = sink.result()  # warm run doubles as reference
                sink = listing.ArraySink(spec["k"])
                t0 = time.perf_counter()
                listing.stream_cliques(g, spec["k"], sink, backend=backend)
                solo[gkm] = time.perf_counter() - t0
            if key not in oracle:
                expect = rows[gkm]
                if spec["vertex_filter"] is not None:
                    expect = apply_vertex_filter(expect, spec["vertex_filter"])
                if spec["max_out"] is not None:
                    expect = expect[: spec["max_out"]]
                oracle[key] = expect
    return oracle, solo


def _spec_key(spec):
    return (spec["graph"], spec["k"], spec["mode"], spec["vertex_filter"],
            spec["max_out"])


def _check(spec, result, oracle):
    """True when one response matches its oracle entry exactly."""
    want = oracle[_spec_key(spec)]
    if spec["mode"] == "count":
        return result.count == want
    return np.array_equal(result.rows, want)


class SerialExecutor:
    """The serve-tier baseline: one worker, one full query at a time.

    Mirrors the service's client API (``submit`` -> ticket with
    ``result(timeout)``) and its admission bound, but executes each
    request with a plain ``engine_jax.count`` / ``stream_cliques`` call
    -- the pre-serving ``examples/clique_service.py`` behavior.  Latency
    includes queue wait, so an 8-client burst pays the serialization.
    """

    class _Ticket:
        """Future-like handle of one queued serial request."""

        def __init__(self):
            self.event = threading.Event()
            self.result = None
            self.error = None

        def done(self):
            """True once the worker resolved this request."""
            return self.event.is_set()

        def get(self, timeout=None):
            """Block for the RequestResult (or re-raise the failure)."""
            if not self.event.wait(timeout):
                raise TimeoutError("serial request not resolved")
            if self.error is not None:
                raise self.error
            return self.result

    def __init__(self, graph_objs, devices, backend, max_pending=256):
        from repro.serve import ServiceOverloaded

        self._graphs = graph_objs
        self._devices = devices
        self._backend = backend
        self._q = queue.Queue()
        self._max_pending = max_pending
        self._overloaded = ServiceOverloaded
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def submit(self, spec, block=True):
        """Enqueue one spec; returns a ticket (queue-bounded like serve)."""
        if self._q.qsize() >= self._max_pending and not block:
            raise self._overloaded("serial queue full")
        t = (time.monotonic(), spec, self._Ticket())
        self._q.put(t)
        return t[2]

    def close(self):
        """Stop the worker after the queue drains."""
        self._q.put(None)
        self._thread.join()

    def _run(self):
        from repro.core import engine_jax, listing
        from repro.serve import RequestResult, apply_vertex_filter

        while True:
            item = self._q.get()
            if item is None:
                return
            t0, spec, ticket = item
            g = self._graphs[spec["graph"]]
            try:
                if spec["mode"] == "count":
                    r = engine_jax.count(g, spec["k"], devices=self._devices,
                                         backend=self._backend)
                    res = RequestResult(kind="count", count=r.count,
                                        stats=r.stats)
                else:
                    vf = spec["vertex_filter"]
                    if vf is None:
                        sink = listing.ArraySink(spec["k"],
                                                 max_out=spec["max_out"])
                        listing.stream_cliques(
                            g, spec["k"], sink, devices=self._devices,
                            backend=self._backend)
                        rows = sink.result()
                    else:
                        sink = listing.ArraySink(spec["k"])
                        listing.stream_cliques(
                            g, spec["k"], sink, devices=self._devices,
                            backend=self._backend)
                        rows = apply_vertex_filter(sink.result(), vf)
                        if spec["max_out"] is not None:
                            rows = rows[: spec["max_out"]]
                    res = RequestResult(kind="list", rows=rows,
                                        emitted=rows.shape[0])
                now = time.monotonic()
                res.latency_s = now - t0
                res.deadline_s = spec["deadline_s"]
                res.deadline_missed = (spec["deadline_s"] is not None
                                       and res.latency_s > spec["deadline_s"])
                ticket.result = res
            except BaseException as exc:
                ticket.error = exc
            ticket.event.set()


def _submit_serve(svc, spec, block=True):
    return svc.submit(spec["graph"], spec["k"], spec["mode"],
                      vertex_filter=spec["vertex_filter"],
                      max_out=spec["max_out"],
                      deadline_s=spec["deadline_s"], block=block)


HIST_EDGES_MS = [2.0 ** e for e in range(-1, 15)]  # 0.5ms .. 16s


def stage_breakdown(stage_dicts):
    """Aggregate per-request ``stage_s`` dicts into per-stage summaries.

    Returns ``{stage: {mean_ms, p99_ms, total_s, requests}}`` over the
    requests that recorded that stage (queue-wait vs fuse-wait vs device
    vs reorder; device seconds overlap across fused requests, so totals
    are attribution, not wall time).
    """
    per_stage = {}
    for d in stage_dicts:
        for stage, dt in (d or {}).items():
            per_stage.setdefault(stage, []).append(dt)
    out = {}
    for stage, vals in sorted(per_stage.items()):
        ms = np.asarray(vals) * 1e3
        out[stage] = {
            "mean_ms": float(ms.mean()),
            "p99_ms": float(np.percentile(ms, 99)),
            "total_s": float(ms.sum() / 1e3),
            "requests": int(ms.size),
        }
    return out


def summarize(name, latencies_s, missed, mismatches, rejected, wall_s):
    """Fold one run's raw measurements into a BENCH record body."""
    lat_ms = np.asarray(sorted(latencies_s)) * 1e3
    completed = lat_ms.size
    good = completed - missed
    hist, _ = (np.histogram(lat_ms, bins=[0.0] + HIST_EDGES_MS)
               if completed else (np.zeros(len(HIST_EDGES_MS), np.int64),
                                  None))
    rec = {
        "mode": name,
        "requests": completed + rejected,
        "completed": completed,
        "rejected": rejected,
        "mismatches": mismatches,
        "seconds": wall_s,
        "goodput_rps": good / wall_s if wall_s > 0 else 0.0,
        "throughput_rps": completed / wall_s if wall_s > 0 else 0.0,
        "deadline_missed": missed,
        "miss_rate": missed / completed if completed else 0.0,
        "p50_ms": float(np.percentile(lat_ms, 50)) if completed else 0.0,
        "p90_ms": float(np.percentile(lat_ms, 90)) if completed else 0.0,
        "p99_ms": float(np.percentile(lat_ms, 99)) if completed else 0.0,
        "mean_ms": float(lat_ms.mean()) if completed else 0.0,
        "latency_hist_edges_ms": HIST_EDGES_MS,
        "latency_hist": [int(x) for x in hist],
    }
    return rec


def run_closed(submit, specs, clients, oracle, timeout=600.0):
    """Closed-loop drive: ``clients`` threads, each spec waits its turn.

    ``submit(spec)`` must return a ticket with ``result``/``get``;
    returns (latencies, missed, mismatches, wall_s).  The executor is
    left open so warmup epochs and the measured epoch share one
    steady-state service (warm plans, warm executables).
    """
    per_client = [specs[c::clients] for c in range(clients)]
    lock = threading.Lock()
    latencies, missed, mismatches = [], [0], [0]
    stages = []
    errors = []

    def client(idx):
        try:
            for spec in per_client[idx]:
                ticket = submit(spec)
                res = (ticket.result(timeout) if hasattr(ticket, "result")
                       and not hasattr(ticket, "get") else ticket.get(timeout))
                with lock:
                    latencies.append(res.latency_s)
                    stages.append(dict(getattr(res, "stage_s", None) or {}))
                    if res.deadline_missed:
                        missed[0] += 1
                    if not _check(spec, res, oracle):
                        mismatches[0] += 1
        except BaseException as exc:  # surfaced after join
            errors.append(exc)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    if errors:
        raise errors[0]
    return latencies, missed[0], mismatches[0], wall, stages


def run_open(submit, specs, rate, oracle, seed, timeout=600.0):
    """Open-loop drive: Poisson arrivals at ``rate``/s, non-blocking admit.

    Overloaded submissions are shed (rejected); returns
    (latencies, missed, mismatches, rejected, wall_s, stage_dicts).
    """
    from repro.serve import ServiceOverloaded

    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, size=len(specs))
    inflight = []
    rejected = 0
    t0 = time.monotonic()
    due = t0
    for spec, gap in zip(specs, gaps):
        due += gap
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        try:
            inflight.append((spec, submit(spec, block=False)))
        except ServiceOverloaded:
            rejected += 1
    latencies, missed, mismatches = [], 0, 0
    stages = []
    for spec, ticket in inflight:
        res = (ticket.result(timeout) if hasattr(ticket, "result")
               and not hasattr(ticket, "get") else ticket.get(timeout))
        latencies.append(res.latency_s)
        stages.append(dict(getattr(res, "stage_s", None) or {}))
        if res.deadline_missed:
            missed += 1
        if not _check(spec, res, oracle):
            mismatches += 1
    wall = time.monotonic() - t0
    return latencies, missed, mismatches, rejected, wall, stages


def main(argv=None):
    """CLI entry point; returns the process exit code."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mode", default="serve",
                    choices=["serve", "serial", "both"])
    ap.add_argument("--loop", default="closed", choices=["closed", "open"])
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--requests-per-client", type=int, default=4)
    ap.add_argument("--rates", default="4",
                    help="open loop: comma-separated arrivals/s sweep")
    ap.add_argument("--duration", type=float, default=8.0,
                    help="open loop: seconds of arrivals per rate")
    ap.add_argument("--graphs", default="rmat:8",
                    help="comma-separated launch.clique load_graph specs")
    ap.add_argument("--ks", default="4,5")
    ap.add_argument("--list-frac", type=float, default=0.4)
    ap.add_argument("--filter-frac", type=float, default=0.25,
                    help="fraction of listing requests with a vertex filter")
    ap.add_argument("--max-out", type=int, default=0,
                    help="max_out on listing requests (0 = unbounded)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="fixed per-request latency deadline (0 = none)")
    ap.add_argument("--deadline-x", type=float, default=0.0,
                    help="proportional SLO: deadline = X * the spec's "
                         "measured solo latency (0 = off; overrides "
                         "--deadline-ms)")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="chaos mode: inject seeded faults at every "
                         "repro.resilience site with this probability "
                         "during the serve-mode run (the oracle and the "
                         "serial baseline stay fault-free); results must "
                         "still match the oracle exactly")
    ap.add_argument("--fault-seed", type=int, default=7,
                    help="seed of the deterministic fault schedule")
    ap.add_argument("--devices", default="all")
    ap.add_argument("--virtual-devices", type=int, default=0,
                    help="forge N virtual CPU devices (sets XLA_FLAGS; "
                         "must win the race with backend init)")
    ap.add_argument("--backend", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunk-tiles", type=int, default=64)
    ap.add_argument("--fuse-rows", type=int, default=256)
    ap.add_argument("--warmup", type=int, default=1,
                    help="unmeasured epochs of the full workload before the "
                         "measured one (compiles all steady-state shapes)")
    ap.add_argument("--max-pending", type=int, default=256)
    ap.add_argument("--plan-cache", default=None)
    ap.add_argument("--json", dest="out_json", default=None)
    ap.add_argument("--append", action="store_true")
    ap.add_argument("--log-level", default="warning",
                    help="repro.* logger verbosity (obs/logging)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto span trace of the whole "
                         "run (warmup included) with per-request async "
                         "tracks keyed by ticket id")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="expose the serve-mode service's Prometheus "
                         "/metrics on 127.0.0.1:PORT (0 = ephemeral)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="scrape the service's /metrics once after the "
                         "measured epoch and write the exposition text to "
                         "PATH (implies an ephemeral --metrics-port)")
    ap.add_argument("--assert-goodput-x", type=float, default=None,
                    help="require serve goodput >= X * serial goodput at "
                         "p99 <= --p99-tol * serial p99 (needs --mode both)")
    ap.add_argument("--p99-tol", type=float, default=1.1)
    args = ap.parse_args(argv)
    if args.virtual_devices:
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={args.virtual_devices}")

    from repro.launch.clique import load_graph, parse_devices
    from repro.obs import trace
    from repro.obs.export import scrape
    from repro.obs.logging import setup_logging
    from repro.resilience import inject
    from repro.serve import CliqueService

    setup_logging(args.log_level)
    if args.trace_out:
        trace.configure(enabled=True)
    if args.metrics_out is not None and args.metrics_port is None:
        args.metrics_port = 0  # snapshot needs a live endpoint to scrape

    # graph specs may contain commas (er:300,0.08): a comma only starts a
    # new spec when the next fragment has its own "name:" prefix
    graphs: list = []
    for part in args.graphs.split(","):
        if graphs and ":" not in part:
            graphs[-1] += "," + part
        else:
            graphs.append(part)
    ks = [int(x) for x in args.ks.split(",")]
    devices = parse_devices(args.devices)
    graph_objs = {name: load_graph(name) for name in graphs}

    if args.loop == "closed":
        n_requests = args.clients * args.requests_per_client
    else:
        n_requests = max(1, int(args.duration * max(
            float(r) for r in args.rates.split(","))))
    workload = build_workload(graphs, ks, n_requests, args.list_frac,
                              args.filter_frac, args.max_out,
                              args.deadline_ms, args.seed)
    config = {k: v for k, v in vars(args).items() if k != "out_json"}
    print(f"# workload: {len(workload)} requests over {graphs} ks={ks}",
          flush=True)
    print("# building oracle (plain engines)...", flush=True)
    oracle, solo = build_oracle(graph_objs, workload, args.backend)
    if args.deadline_x:
        # proportional SLOs: a heavy request gets a proportionally longer
        # deadline, so goodput measures scheduling (head-of-line blocking
        # vs EDF interleaving), not just raw speed
        for spec in workload:
            base = solo[(spec["graph"], spec["k"], spec["mode"])]
            spec["deadline_s"] = max(args.deadline_x * base, 2e-3)
        slos = sorted(set(round(s["deadline_s"] * 1e3, 1)
                          for s in workload))
        print(f"# proportional SLOs ({args.deadline_x}x solo): "
              f"{slos[0]}..{slos[-1]}ms", flush=True)

    def serve_factory():
        svc = CliqueService(
            devices=devices, backend=args.backend,
            chunk_tiles=args.chunk_tiles, fuse_rows=args.fuse_rows,
            max_pending=args.max_pending, plan_cache_dir=args.plan_cache,
            metrics_port=args.metrics_port)
        if svc.metrics_address:
            print(f"# metrics: {svc.metrics_address}/metrics", flush=True)
        for name, g in graph_objs.items():
            svc.register_graph(name, g)
        return (lambda spec, block=True: _submit_serve(svc, spec, block),
                svc.close, svc)

    def snapshot_metrics(svc):
        # one scrape while the service (and its collector) is still alive
        if svc is None or args.metrics_out is None:
            return
        if svc.metrics_address is None:
            return
        text = scrape(svc.metrics_address)
        with open(args.metrics_out, "w") as f:
            f.write(text)
        print(f"# wrote {args.metrics_out} "
              f"({len(text.splitlines())} exposition lines)", flush=True)

    def serial_factory():
        ex = SerialExecutor(graph_objs, devices, args.backend,
                            max_pending=args.max_pending)
        return ex.submit, ex.close, None

    def _wait(ticket, timeout=600.0):
        # serve Tickets expose result(); SerialExecutor tickets expose get()
        if hasattr(ticket, "get"):
            return ticket.get(timeout)
        return ticket.result(timeout)

    def finish_record(rec, mode, svc):
        rec.update(kind="serve_loadgen", graph="+".join(graphs), ks=ks,
                   devices=args.devices, backend=args.backend, config=config)
        if svc is not None:
            s = svc.stats
            es = svc.engine_stats
            rec["serve_stats"] = {
                "fused_batches": s.fused_batches,
                "cross_request_batches": s.cross_request_batches,
                "fused_rows": s.fused_rows,
                "fused_chunks": s.fused_chunks,
                "deadline_flushes": s.deadline_flushes,
                "rejected": s.rejected,
                # resilience counters (nonzero under --fault-rate chaos)
                "retries": es.retries,
                "demotions": es.demotions,
                "isolated_failures": s.isolated_failures,
                "deadline_cancels": s.deadline_cancels,
                "shed": s.shed,
                "faults_injected": inject.fired(),
            }
        print(f"# {mode}/{rec['loop']}: {rec['completed']} ok, "
              f"{rec['mismatches']} mismatches, "
              f"p50={rec['p50_ms']:.1f}ms p99={rec['p99_ms']:.1f}ms "
              f"goodput={rec['goodput_rps']:.2f}/s "
              f"miss_rate={rec['miss_rate']:.2f}", flush=True)
        records.append(rec)
        return rec["mismatches"]

    modes = [args.mode] if args.mode != "both" else ["serve", "serial"]
    records = []
    failures = 0
    for mode in modes:
        factory = serve_factory if mode == "serve" else serial_factory
        # chaos mode: seeded injection is scoped to the serve run only --
        # the oracle (already built) and the serial baseline stay clean,
        # so any mismatch is a real resilience bug, not a noisy reference
        chaos = args.fault_rate > 0 and mode == "serve"
        if chaos:
            inject.configure(
                f"seed={args.fault_seed};*={args.fault_rate};"
                f"kernel.launch={max(args.fault_rate, 0.1)}")
            print(f"# chaos: injecting faults at rate {args.fault_rate} "
                  f"(seed {args.fault_seed})", flush=True)
        if args.loop == "closed":
            submit, close, svc = factory()
            # unmeasured epochs of the identical concurrent workload: warm
            # plans and every steady-state executable shape (including the
            # partial-flush pow2 buckets only concurrency produces) so the
            # measured epoch is the steady serving state
            for _ in range(args.warmup):
                run_closed(submit, workload, args.clients, oracle)
            lat, missed, mism, wall, stages = run_closed(
                submit, workload, args.clients, oracle)
            snapshot_metrics(svc)
            close()
            rec = summarize(mode, lat, missed, mism, 0, wall)
            rec.update(loop="closed", clients=args.clients,
                       stage_breakdown=stage_breakdown(stages))
            failures += finish_record(rec, mode, svc)
        else:
            for rate in (float(r) for r in args.rates.split(",")):
                submit, close, svc = factory()
                for _ in range(args.warmup):
                    run_closed(submit, workload, max(4, args.clients), oracle)
                lat, missed, mism, rejected, wall, stages = run_open(
                    submit, workload, rate, oracle, args.seed)
                snapshot_metrics(svc)
                close()
                rec = summarize(mode, lat, missed, mism, rejected, wall)
                rec.update(loop="open", rate=rate,
                           stage_breakdown=stage_breakdown(stages))
                failures += finish_record(rec, mode, svc)
        if chaos:
            inject.configure(None)

    if args.trace_out:
        trace.export(args.trace_out)
        print(f"# wrote {args.trace_out} ({len(trace.events())} trace "
              f"events, {trace.dropped()} dropped)", flush=True)

    if args.out_json:
        payload = {"graph": "+".join(graphs), "ks": ks,
                   "devices": args.devices,
                   "backends": [args.backend or "auto"],
                   "records": records}
        if args.out_json == "-":
            json.dump(payload, sys.stdout, indent=1)
            print(flush=True)
        else:
            if args.append and os.path.exists(args.out_json):
                with open(args.out_json) as f:
                    prior = json.load(f)
                payload["records"] = prior.get("records", []) + records
            with open(args.out_json, "w") as f:
                json.dump(payload, f, indent=1)
            print(f"# wrote {args.out_json} "
                  f"({len(payload['records'])} records)", flush=True)

    if failures:
        print(f"# FAIL: {failures} oracle mismatches", flush=True)
        return 1
    if args.assert_goodput_x is not None:
        serve = [r for r in records if r["mode"] == "serve"]
        serial = [r for r in records if r["mode"] == "serial"]
        if not serve or not serial:
            print("# FAIL: --assert-goodput-x needs --mode both", flush=True)
            return 1
        gx = serve[0]["goodput_rps"] / max(serial[0]["goodput_rps"], 1e-9)
        p99_ok = serve[0]["p99_ms"] <= serial[0]["p99_ms"] * args.p99_tol
        print(f"# goodput serve/serial = {gx:.2f}x "
              f"(p99 {serve[0]['p99_ms']:.1f}ms vs "
              f"{serial[0]['p99_ms']:.1f}ms)", flush=True)
        if gx < args.assert_goodput_x or not p99_ok:
            print(f"# FAIL: goodput ratio {gx:.2f} < "
                  f"{args.assert_goodput_x} or p99 regressed", flush=True)
            return 1
    return 0


if __name__ == "__main__":
    from repro import tune

    tune.enable_compilation_cache()
    sys.exit(main())
