"""Observability layer: trace determinism and schema shape, disabled-path
overhead budget, the Stats.merge classification table, metrics registry +
Prometheus exposition, and the serve-tier per-request stage breakdown.

Run with ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` to check
that trace *structure* is device-count invariant (the CI matrix does 1
and 4).
"""

import dataclasses
import json
import os
import subprocess
import sys
import threading
import time
from collections import Counter as TallyCounter

import numpy as np
import pytest

from conftest import random_graph
from repro.core import engine_jax, listing, pipeline
from repro.core.engine_np import Stats
from repro.obs import metrics as obs_metrics
from repro.obs import trace
from repro.obs.export import MetricsServer, render_prometheus, scrape
from repro.obs.logging import get_logger, setup_logging
from repro.obs.profile import note_kernel
from repro.serve import CliqueService


@pytest.fixture
def tracer():
    """Enabled process tracer, reset and disabled again afterwards."""
    trace.configure(enabled=True)
    trace.reset()
    yield trace
    trace.configure(enabled=False)
    trace.reset()


@pytest.fixture
def registry():
    """A private metrics registry (the global one is left alone)."""
    return obs_metrics.Registry()


def small_graph(seed=11):
    rng = np.random.default_rng(seed)
    return random_graph(rng, n_lo=28, n_hi=29, p_lo=0.3, p_hi=0.3)


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_spans_nest_and_validate(tracer):
    with trace.span("outer", x=1):
        with trace.span("inner") as sp:
            sp.set(y=2)
        trace.instant("tick")
    recs = trace.span_records()
    assert ("inner", "outer") in recs
    assert ("outer", None) in recs
    doc = trace.chrome_trace()
    assert trace.validate_chrome_trace(doc) == []
    by_name = {e["name"]: e for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert by_name["inner"]["args"] == {"y": 2}
    # inner lies within outer
    o, i = by_name["outer"], by_name["inner"]
    assert o["ts"] <= i["ts"] and i["ts"] + i["dur"] <= o["ts"] + o["dur"]


def test_thread_local_nesting(tracer):
    def worker():
        with trace.span("w-outer"):
            with trace.span("w-inner"):
                pass

    with trace.span("main"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    recs = trace.span_records()
    # the worker's spans never parent onto the main thread's open span
    assert ("w-inner", "w-outer") in recs
    assert ("w-outer", None) in recs
    assert ("main", None) in recs


def test_async_request_track(tracer):
    trace.async_begin("request", id=7, k=5)
    trace.async_instant("request/admit", id=7)
    trace.async_end("request", id=7, latency_ms=1.5)
    doc = trace.chrome_trace()
    assert trace.validate_chrome_trace(doc) == []
    phs = [e["ph"] for e in doc["traceEvents"] if e.get("id") == "7"]
    assert phs == ["b", "n", "e"]


def test_unmatched_async_flagged(tracer):
    trace.async_begin("request", id=9)
    problems = trace.validate_chrome_trace(trace.chrome_trace())
    assert any("begin without end" in p for p in problems)


def test_retroactive_complete(tracer):
    t0 = time.perf_counter_ns()
    trace.complete("reorder/park", t0, 1500, rid=3)
    (ev,) = [e for e in trace.events() if e["name"] == "reorder/park"]
    assert ev["ph"] == "X" and ev["dur"] == 1500


def test_ring_buffer_drops_oldest(tracer):
    try:
        trace.configure(enabled=True, capacity=8)
        for i in range(20):
            trace.instant(f"e{i}")
        evs = trace.events()
        assert len(evs) == 8
        assert evs[0]["name"] == "e12" and trace.dropped() == 12
    finally:
        trace.configure(enabled=True, capacity=trace._DEFAULT_CAPACITY)


def test_validate_rejects_malformed():
    assert trace.validate_chrome_trace({}) != []
    bad = {"traceEvents": [{"ph": "X", "name": "x", "ts": 0.0}]}
    assert any("dur" in p or "pid" in p or "tid" in p
               for p in trace.validate_chrome_trace(bad))


# ---------------------------------------------------------------------------
# trace determinism + overhead budget (tentpole acceptance)
# ---------------------------------------------------------------------------


def _traced_pipeline_structure(g, k):
    """(name, parent) multiset of one serial-packed pipeline run."""
    trace.reset()
    plan = pipeline.build_plan(g, order="hybrid")
    for _ in pipeline.stream_batches(plan, k, batch_size=64,
                                     pack_workers=0):
        pass
    return TallyCounter(trace.span_records())


def test_trace_structure_deterministic(tracer):
    g = small_graph()
    first = _traced_pipeline_structure(g, 4)
    assert first, "pipeline produced no spans"
    assert {"extract", "pack"} <= {name for name, _ in first}
    for _ in range(2):
        assert _traced_pipeline_structure(g, 4) == first


def test_trace_well_nested_under_load(tracer):
    # serve a small concurrent workload; every sync span must close and
    # every request track must be begin/end matched
    g = small_graph(5)
    with CliqueService() as svc:
        svc.register_graph("g", g)
        tickets = [svc.submit("g", k, mode) for k in (3, 4)
                   for mode in ("count", "list")]
        for t in tickets:
            t.result(timeout=120)
    doc = trace.chrome_trace()
    assert trace.validate_chrome_trace(doc) == []
    begins = [e for e in doc["traceEvents"] if e.get("ph") == "b"]
    assert len(begins) == len(tickets)


def test_disabled_tracer_overhead_budget():
    # the contract: tracing disabled adds <= 1% to bench-smoke-like work.
    # Measured as (per-disabled-span cost) * (spans the workload emits),
    # which is robust where wall-clock diffing is noise-dominated.
    g = small_graph(23)
    trace.configure(enabled=False)

    def workload():
        t0 = time.perf_counter()
        engine_jax.count(g, 4, batch_size=64)
        return time.perf_counter() - t0

    workload()  # warm executables/plan caches
    work_s = min(workload() for _ in range(3))

    trace.configure(enabled=True)
    trace.reset()
    engine_jax.count(g, 4, batch_size=64)
    n_spans = len(trace.events())
    trace.configure(enabled=False)
    trace.reset()
    assert n_spans > 0

    n_iter = 50_000
    t0 = time.perf_counter()
    for _ in range(n_iter):
        with trace.span("x", a=1):
            pass
    per_call = (time.perf_counter() - t0) / n_iter
    overhead = per_call * n_spans
    assert overhead <= 0.01 * work_s, (
        f"disabled tracing would add {overhead * 1e3:.3f}ms over "
        f"{n_spans} spans to a {work_s * 1e3:.1f}ms workload (> 1%)"
    )


def test_engine_trace_covers_device_stages(tracer):
    g = small_graph(31)
    engine_jax.count(g, 4, batch_size=64, devices="all")
    names = {name for name, _ in trace.span_records()}
    assert {"extract", "pack", "device/stage", "device/harvest",
            "combine"} <= names
    doc = trace.chrome_trace()
    assert trace.validate_chrome_trace(doc) == []
    # one batch id follows a batch from its packer through the dispatcher
    ids = {}
    for ev in trace.events():
        if ev["name"] in ("pack", "device/stage", "device/harvest",
                          "combine"):
            ids.setdefault(ev["name"], []).append(ev["args"]["batch"])
    assert sorted(ids["pack"]) == list(range(len(ids["pack"])))
    for name in ("device/stage", "device/harvest", "combine"):
        assert sorted(ids[name]) == sorted(ids["pack"]), name


# ---------------------------------------------------------------------------
# Stats.merge (the single classification table)
# ---------------------------------------------------------------------------


def test_stats_merge_all_fields_classified():
    # tripwire: adding a Stats field without classifying it must fail
    # loudly in merge, not silently drift between merge and metrics
    fields = {f.name for f in dataclasses.fields(Stats)}
    assert fields == set(Stats._MERGE_KINDS)
    assert fields == set(Stats._METRIC_KINDS)


def test_stats_merge_combines():
    a = Stats(branches=2, peak_graph=10, device_tiles={0: 3},
              spill_sizes=[4], backend="lax", plan_cache_hit=False,
              pack_queue_occupancy=0.5)
    b = Stats(branches=3, peak_graph=7, device_tiles={0: 1, 1: 2},
              spill_sizes=[9], backend="lax", plan_cache_hit=True,
              pack_queue_occupancy=0.75)
    a.merge(b)
    assert a.branches == 5
    assert a.peak_graph == 10
    assert a.device_tiles == {0: 4, 1: 2}
    assert a.spill_sizes == [4, 9]
    assert a.plan_cache_hit is True
    assert a.pack_queue_occupancy == 0.75
    assert a.backend == "lax"


def test_stats_merge_rejects_unclassified():
    @dataclasses.dataclass
    class Odd(Stats):
        novel_field: int = 0

    with pytest.raises(TypeError, match="novel_field"):
        Odd().merge(Odd())


def test_stats_merge_keeps_info_identity():
    a, b = Stats(), Stats(backend="pallas")
    a.merge(b)
    assert a.backend == "pallas"  # empty self adopts other's identity
    a.merge(Stats(backend="lax"))
    assert a.backend == "pallas"  # non-empty self wins


# ---------------------------------------------------------------------------
# metrics registry + exposition
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram(registry):
    c = registry.counter("repro_t_total", help="h")
    c.inc(3)
    c.inc()
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    g = registry.gauge("repro_g")
    g.set(2.5)
    g.set_max(1.0)
    assert g.value == 2.5
    h = registry.histogram("repro_h", edges=[1.0, 2.0])
    for v in (0.5, 1.5, 99.0):
        h.observe(v)
    counts, total, n = h.snapshot()
    assert counts == [1, 1, 1] and n == 3 and total == pytest.approx(101.0)


def test_registry_get_or_create_and_label_identity(registry):
    a = registry.counter("repro_x_total", key="0")
    b = registry.counter("repro_x_total", key="0")
    c = registry.counter("repro_x_total", key="1")
    assert a is b and a is not c
    with pytest.raises(TypeError):
        registry.gauge("repro_x_total", key="0")


def test_observe_stats_and_publish_totals(registry):
    st = Stats(branches=4, device_tiles={0: 2, 1: 1}, spilled_tiles=1,
               peak_graph=9, plan_cache_hit=True, backend="lax")
    obs_metrics.observe_stats(st, "repro_engine", registry)
    obs_metrics.observe_stats(st, "repro_engine", registry)
    got = {(m.name, m.labels): m for m in registry.collect()}
    assert got[("repro_engine_branches_total", ())].value == 8
    assert got[("repro_engine_device_tiles_total",
                (("key", "0"),))].value == 4
    assert got[("repro_engine_peak_graph", ())].value == 9
    # publish_totals is absolute, not additive
    reg2 = obs_metrics.Registry()
    obs_metrics.publish_totals(st, "repro_engine", reg2)
    obs_metrics.publish_totals(st, "repro_engine", reg2)
    got2 = {m.name: m for m in reg2.collect()}
    assert got2["repro_engine_branches_total"].value == 4


def _parse_exposition(text):
    """Minimal 0.0.4 parser: {metric-with-labels: value}; validates shape."""
    out = {}
    types = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE"):
            _, _, name, kind = line.split()
            assert kind in ("counter", "gauge", "histogram"), line
            types[name] = kind
        elif line.startswith("#"):
            assert line.startswith("# HELP"), line
        else:
            key, val = line.rsplit(" ", 1)
            float(val)  # must parse
            out[key] = float(val)
    return out, types


def test_prometheus_render_parses(registry):
    registry.counter("repro_a_total", help="things").inc(2)
    registry.gauge("repro_b", key="x").set(1.5)
    registry.histogram("repro_c_seconds", edges=[0.1, 1.0]).observe(0.05)
    text = render_prometheus(registry)
    values, types = _parse_exposition(text)
    assert values['repro_a_total'] == 2
    assert values['repro_b{key="x"}'] == 1.5
    assert types["repro_c_seconds"] == "histogram"
    assert values['repro_c_seconds_bucket{le="+Inf"}'] == 1
    assert values["repro_c_seconds_count"] == 1
    # histogram buckets are cumulative and ordered
    assert values['repro_c_seconds_bucket{le="0.1"}'] <= \
        values['repro_c_seconds_bucket{le="1"}']


def test_metrics_server_scrape(registry):
    registry.counter("repro_up_total").inc()
    calls = []
    registry.add_collector(lambda: calls.append(1))
    srv = MetricsServer(port=0, registry=registry)
    try:
        text = scrape(srv.address)
    finally:
        srv.close()
    assert calls, "collector did not run at scrape time"
    values, _ = _parse_exposition(text)
    assert values["repro_up_total"] == 1


def test_note_kernel_attribution(registry):
    note_kernel("count[l=3,T=64,B=256,backend=lax]", compile_s=0.5,
                registry=registry)
    note_kernel("count[l=3,T=64,B=256,backend=lax]", compile_s=0.25,
                registry=registry)
    got = {(m.name, m.labels): m for m in registry.collect()}
    key = ("repro_kernel_compile_seconds_total",
           (("sig", "count[l=3,T=64,B=256,backend=lax]"),))
    assert got[key].value == pytest.approx(0.75)


# ---------------------------------------------------------------------------
# profiler bridge + device-to-host bytes
# ---------------------------------------------------------------------------


def test_obs_imports_without_jax():
    # the bridge imports jax inside configure only
    code = ("import sys; sys.modules['jax'] = None; "
            "import repro.obs; from repro.obs import trace; "
            "trace.configure(enabled=True); "
            "trace.configure(enabled=False)")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120,
                          env=dict(os.environ, PYTHONPATH=src))
    assert proc.returncode == 0, proc.stderr


def test_profiler_bridge_writes_program_spans(tmp_path):
    import jax
    from jax.profiler import ProfileData

    g = small_graph(31)
    engine_jax.count(g, 4, batch_size=16, devices=1)  # compile outside
    trace.configure(enabled=True)
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            want = engine_jax.count(g, 4, batch_size=16, devices=1).count
        finally:
            jax.profiler.stop_trace()
    finally:
        trace.configure(enabled=False)
        trace.reset()
    (path,) = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
               for f in fs if f.endswith(".xplane.pb")]
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(trace.PROFILER_PREFIX):
                    seen.setdefault(ev.name, []).append(dict(ev.stats))
    assert {"repro/extract", "repro/device/stage", "repro/device/harvest",
            "repro/combine"} <= set(seen)
    assert {"repro/pack/wait", "repro/pack"} & set(seen)
    # the span args ride along: one batch id per staged batch
    staged = sorted(st["batch"] for st in seen["repro/device/stage"])
    assert staged == list(range(len(staged)))
    assert want == engine_jax.count(g, 4, batch_size=16).count


def test_bridge_waits_for_a_profiler_session(tracer):
    # tracing on and no profiler session: the span records, and opens no
    # annotation
    with trace.span("pack", batch=0) as sp:
        assert sp._ann is None
    assert [ev["name"] for ev in trace.events()] == ["pack"]
    assert trace.configure(enabled=False).annotate is None


@pytest.mark.parametrize("k, outputs", [(4, 1), (5, 4)])
def test_d2h_bytes_counts_the_partials_a_combine_reads(k, outputs):
    g = random_graph(np.random.default_rng(7), n_lo=28, n_hi=29, p_lo=0.5,
                     p_hi=0.5)
    bs, bins = 16, (32,)
    res = engine_jax.count(g, k, batch_size=bs, bins=bins, devices=1)
    # each batch fetches its (B',) 4-byte partials: ``hard`` alone where
    # no closed form applies (l = 2), all four where it does (l = 3)
    want = sum(engine_jax.bucket_rows(item.A).shape[0] * 4 * outputs
               for item in pipeline.stream_batches(g, k, batch_size=bs,
                                                   bins=bins)
               if isinstance(item, pipeline.TileBatch))
    assert want > 0 and res.stats.d2h_bytes == want


@pytest.mark.parametrize("devices", [None, 1])
def test_d2h_bytes_counts_a_listings_fetched_buffers(devices):
    g = small_graph(7)
    cap, bs, bins = 16, 16, (32,)
    sink = listing.ArraySink(4)
    res = listing.stream_cliques(g, 4, sink, capacity=cap, batch_size=bs,
                                 bins=bins, devices=devices, pack_workers=0,
                                 plan_cache=False)
    # a pinned capacity skips the sizing pass: each batch fetches its
    # (B', cap, l) int32 emit buffer and (B',) uint32 counts and overflow
    # flags, B' the batch padded to a power of two
    want = 0
    for item in pipeline.stream_batches(g, 4, batch_size=bs, bins=bins):
        if isinstance(item, pipeline.TileBatch):
            rows = engine_jax.bucket_rows(item.A).shape[0]
            want += rows * (cap * 2 + 2) * 4
    assert want > 0 and res.stats.emitted_cliques == len(sink.result())
    assert res.stats.d2h_bytes == want


# ---------------------------------------------------------------------------
# logging + serve integration
# ---------------------------------------------------------------------------


def test_setup_logging_idempotent():
    root = setup_logging("info")
    n = len(root.handlers)
    assert setup_logging("debug") is root
    assert len(root.handlers) == n
    log = get_logger("test_obs")
    assert log.name == "repro.test_obs"
    with pytest.raises(ValueError):
        setup_logging("shout")


def test_serve_stage_breakdown_and_metrics_endpoint(tracer):
    g = small_graph(47)
    svc = CliqueService(metrics_port=0)
    try:
        svc.register_graph("g", g)
        res = svc.submit("g", 4, "count").result(timeout=120)
        assert "queue" in res.stage_s
        assert "device" in res.stage_s
        assert all(v >= 0 for v in res.stage_s.values())
        lst = svc.submit("g", 4, "list").result(timeout=120)
        assert "reorder" in lst.stage_s
        text = scrape(svc.metrics_address)
        values, types = _parse_exposition(text)
        assert values["repro_serve_completed_total"] == 2
        assert types["repro_request_latency_seconds"] == "histogram"
        assert any(k.startswith("repro_engine_") for k in values)
        assert any(k.startswith("repro_request_stage_seconds_total")
                   for k in values)
    finally:
        svc.close()
    assert svc.metrics_address is None
    # request rollup went through Stats.merge: listing emitted cliques
    assert svc.request_stats.emitted_cliques == lst.emitted
