"""Fault-injection, retry/fallback, and graceful degradation (ISSUE 9).

The chaos-determinism gate: with a seeded fault plan active at every
named site (including a double-digit share of kernel launches), counts
stay exact and listing output stays byte-identical to the fault-free
run, with nonzero retry/demotion accounting and no hangs.  Plus the
isolation gates (one bad request never takes down its cotenants; a
deadline-enforced request cancels cooperatively), artifact quarantine,
and the disabled-injection overhead budget.

Run with ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` to chaos
the multi-device dispatch paths too (the CI matrix does both 1 and 4).
"""

import logging
import time

import jax
import numpy as np
import pytest

from conftest import random_graph
from repro.checkpoint import store
from repro.core import engine_jax, listing, pipeline
from repro.core.engine_np import Stats
from repro.data import rmat_graph
from repro.resilience import inject, retry
from repro.runtime import dispatch as dsp
from repro.serve import CliqueService, DeadlineExceeded, ServiceClosed

#: every site armed; kernel.launch well above the >=10% gate requirement
CHAOS_PLAN = ("seed=11;plan.load=0.3;extract=0.3;pack=0.3;device.stage=0.3;"
              "kernel.launch=0.3;device.harvest=0.3;decode=0.3;"
              "sink.write=0.3;tune.read=0.3")


@pytest.fixture(autouse=True)
def _clean_injection():
    inject.configure(None)
    yield
    inject.configure(None)


def make_graph(seed=3, n=48, edges=700):
    rng = np.random.default_rng(seed)
    es = set()
    while len(es) < edges:
        a, b = (int(x) for x in rng.integers(0, n, 2))
        if a != b:
            es.add((min(a, b), max(a, b)))
    from repro.core import graph as G
    return G.from_edges(n, sorted(es))


# ---------------------------------------------------------------------------
# fault-plan parsing + deterministic schedule
# ---------------------------------------------------------------------------


def test_fault_plan_parse():
    plan = inject.FaultPlan.parse("seed=9;*=0.1;kernel.launch=0.5:delay:0.01")
    assert plan.seed == 9
    assert plan.rules["decode"].rate == 0.1
    assert plan.rules["decode"].kind == "raise"
    assert plan.rules["kernel.launch"].rate == 0.5
    assert plan.rules["kernel.launch"].kind == "delay"
    assert plan.rules["kernel.launch"].param == 0.01
    with pytest.raises(ValueError):
        inject.FaultPlan.parse("nonsense.site=0.5")
    with pytest.raises(ValueError):
        inject.FaultPlan.parse("decode=0.5:explode")


def test_fault_schedule_is_deterministic():
    inject.configure("seed=4;decode=0.5")
    first = []
    for _ in range(64):
        try:
            inject.fire("decode")
            first.append(False)
        except inject.FaultInjected:
            first.append(True)
    assert any(first) and not all(first)
    # same plan, reset counters -> identical schedule, call for call
    inject.reset_counts()
    for i in range(64):
        fired = False
        try:
            inject.fire("decode")
        except inject.FaultInjected:
            fired = True
        assert fired == first[i], i
    # a different seed produces a different schedule
    inject.configure("seed=5;decode=0.5")
    second = []
    for _ in range(64):
        try:
            inject.fire("decode")
            second.append(False)
        except inject.FaultInjected:
            second.append(True)
    assert second != first


def test_disabled_injection_is_noop_and_cheap():
    # off by default: fire() at any site is a no-op...
    inject.configure(None)
    for site in inject.SITES:
        inject.fire(site)
    # ...and cheap enough that baked-in sites cost <= 1% of engine work
    # (same budget methodology as the disabled-tracer test in test_obs)
    g = rmat_graph(6, 6, seed=3)

    def workload():
        t0 = time.perf_counter()
        engine_jax.count(g, 4, batch_size=64)
        return time.perf_counter() - t0

    workload()  # warm executables + plan caches
    work_s = min(workload() for _ in range(3))

    # count how many site calls that workload makes (epsilon-rate plan:
    # every call advances the schedule, none of them fire at 1e-12)
    inject.configure("seed=1;*=0.000000000001")
    engine_jax.count(g, 4, batch_size=64)
    n_calls = sum(inject.calls().values())
    assert sum(inject.fired().values()) == 0
    inject.configure(None)
    assert n_calls > 0

    n_iter = 50_000
    t0 = time.perf_counter()
    for _ in range(n_iter):
        inject.fire("kernel.launch")
    per_call = (time.perf_counter() - t0) / n_iter
    overhead = per_call * n_calls
    assert overhead <= 0.01 * work_s, (
        f"disabled injection costs {overhead * 1e3:.3f}ms over {n_calls} "
        f"site calls vs {work_s * 1e3:.1f}ms of work")


# ---------------------------------------------------------------------------
# retry / backoff / demotion units
# ---------------------------------------------------------------------------


def test_backoff_delay_capped_and_deterministic():
    pol = retry.RetryPolicy(max_attempts=8, base_delay_s=0.001,
                            max_delay_s=0.004, jitter=0.5, seed=2)
    delays = [retry.backoff_delay(pol, a, token="t") for a in range(1, 8)]
    assert all(0 < d <= 0.004 for d in delays)
    assert delays == [retry.backoff_delay(pol, a, token="t")
                      for a in range(1, 8)]
    # exponential growth up to the cap (jitter only ever shrinks)
    assert retry.backoff_delay(
        retry.RetryPolicy(jitter=0.0), 2) == 2 * retry.backoff_delay(
        retry.RetryPolicy(jitter=0.0), 1)


def test_retry_call_retries_then_raises():
    attempts = []

    def flaky():
        attempts.append(1)
        if len(attempts) < 3:
            raise RuntimeError("transient")
        return "ok"

    pol = retry.RetryPolicy(max_attempts=3, base_delay_s=0.0)
    assert retry.call(flaky, policy=pol, retry_on=(RuntimeError,)) == "ok"
    assert len(attempts) == 3

    with pytest.raises(RuntimeError):
        retry.call(lambda: (_ for _ in ()).throw(RuntimeError("always")),
                   policy=pol, retry_on=(RuntimeError,))


def test_demotion_ladders():
    assert retry.demote("count", "pallas") == "lax"
    assert retry.demote("count", "lax") == "ref"
    assert retry.demote("count", "ref") is None
    assert retry.demote("list", "pallas") == "lax"
    assert retry.demote("list", "lax") is None
    # an off-ladder backend (None = unresolved, host, ...) has no rung
    # below it: the caller falls straight back to the host recursion
    assert retry.demote("count", None) is None
    assert retry.demote("count", "host") is None


# ---------------------------------------------------------------------------
# artifact quarantine (checkpoints + plan cache)
# ---------------------------------------------------------------------------


@pytest.fixture
def _propagate_repro_logs():
    # obs.logging.setup_logging (called by other tests) turns off
    # propagation on the "repro" logger; caplog listens on root.
    root = logging.getLogger("repro")
    prev = root.propagate
    root.propagate = True
    yield
    root.propagate = prev


def _corrupt_arrays(directory, mode="truncate"):
    import os
    step = store.latest_step(directory)
    path = os.path.join(directory, f"step_{step:010d}", "arrays.npz")
    with open(path, "rb") as f:
        blob = f.read()
    with open(path, "wb") as f:
        if mode == "truncate":
            f.write(blob[: len(blob) // 2])
        else:
            f.write(b"garbage" * 64)


@pytest.mark.parametrize("mode", ["truncate", "garbage"])
def test_corrupt_checkpoint_detected_and_quarantined(
    tmp_path, caplog, mode, _propagate_repro_logs
):
    d = str(tmp_path / "ck")
    store.save_checkpoint(d, 0, {"a": np.arange(100)})
    assert store.restore_checkpoint(d)["tree"]["a"].shape == (100,)
    _corrupt_arrays(d, mode)
    with pytest.raises(store.CorruptCheckpointError):
        store.restore_checkpoint(d)
    with caplog.at_level(logging.WARNING, logger="repro.checkpoint"):
        assert store.restore_checkpoint_safe(d) is None
    assert any("quarantined" in r.message for r in caplog.records)
    # the bad step moved aside (inspectable), the slot reads as absent
    assert store.latest_step(d) is None
    assert (tmp_path / "ck" / "quarantine").is_dir()
    # a fresh save rebuilds cleanly in the vacated slot
    store.save_checkpoint(d, 0, {"a": np.arange(7)})
    assert store.restore_checkpoint(d)["tree"]["a"].shape == (7,)


def test_corrupt_plan_cache_rebuilt_with_same_counts(
    tmp_path, caplog, _propagate_repro_logs
):
    g = make_graph(seed=8, n=40, edges=500)
    cache = str(tmp_path / "plans")
    cold = engine_jax.count(g, 5, plan_cache_dir=cache)
    # corrupt every cached plan entry on disk
    import os
    entries = [os.path.join(cache, e) for e in os.listdir(cache)
               if os.path.isdir(os.path.join(cache, e))]
    assert entries
    for e in entries:
        _corrupt_arrays(e)
    # a fresh process would read the corrupt entry from disk; simulate by
    # dropping the in-memory plan layer
    pipeline.clear_plan_cache()
    with caplog.at_level(logging.WARNING, logger="repro.checkpoint"):
        rebuilt = engine_jax.count(g, 5, plan_cache_dir=cache)
    assert rebuilt.count == cold.count
    assert any("quarantined" in r.message for r in caplog.records)
    # and the rebuild left a valid cache behind: third run is a warm hit
    stats = Stats()
    pipeline.cached_plan(g, "hybrid", cache_dir=cache, stats=stats)
    assert stats.plan_cache_hit


def test_injected_corruption_on_tune_read_reads_as_absent(tmp_path):
    from repro.tune import cache as tcache
    from repro.tune.records import TuningRecord

    tcache.configure(str(tmp_path / "tune"), xla_cache=False)
    try:
        rec = TuningRecord(kind="backend", device_kind="cpu",
                           jax_version="x", mode="count", l=2, T=32, W=1,
                           cap_bucket=-1, data={"backend": "lax"})
        tcache.put(rec)
        tcache.clear_memory()
        assert tcache.get(rec.key()) is not None  # round-trips from disk
        # a raise on the tune.read site degrades to a miss, never an error
        tcache.clear_memory()
        inject.configure("seed=1;tune.read=1.0")
        assert tcache.get(rec.key()) is None
        # a corrupt-kind rule flips blob bytes between read and verify:
        # the integrity trailer catches it and the record reads as absent
        inject.configure("seed=1;tune.read=1.0:corrupt")
        assert tcache.get(rec.key()) is None
        inject.configure(None)
        assert tcache.get(rec.key()) is not None  # record itself unharmed
    finally:
        tcache.configure(None)
        tcache.clear_memory()


# ---------------------------------------------------------------------------
# chaos determinism: engines under a seeded plan at every site
# ---------------------------------------------------------------------------


def test_chaos_count_exact_with_retries():
    # devices=1 routes through the Dispatcher (retry + demotion ladder);
    # the serve tier always takes this path
    g = make_graph()
    want = engine_jax.count(g, 5, devices=1).count
    inject.configure(CHAOS_PLAN)
    res = engine_jax.count(g, 5, devices=1)
    n_fired = sum(inject.fired().values())
    inject.configure(None)
    assert res.count == want
    assert res.stats.retries > 0
    assert n_fired > 0


@pytest.mark.parametrize("capacity", ["sized", "speculative"])
def test_chaos_listing_byte_identical(capacity):
    g = make_graph(seed=5)
    kwargs = {"capacity": capacity, "devices": 1}
    sink = listing.ArraySink(5)
    listing.stream_cliques(g, 5, sink, **kwargs)
    want = sink.result()
    inject.configure(CHAOS_PLAN)
    sink = listing.ArraySink(5)
    res = listing.stream_cliques(g, 5, sink, **kwargs)
    got = sink.result()
    inject.configure(None)
    assert np.array_equal(got, want)
    assert res.stats.retries > 0


def test_kernel_launch_certain_failure_demotes_to_exact_host():
    g = make_graph(seed=9, n=36, edges=420)
    want = engine_jax.count(g, 4).count
    sink = listing.ArraySink(4)
    listing.stream_cliques(g, 4, sink)
    want_rows = sink.result()
    # rate 1.0: every kernel launch fails, every attempt, forever -- the
    # ladder must walk pallas -> lax -> ref -> host and still be exact
    inject.configure("seed=5;kernel.launch=1.0")
    res = engine_jax.count(g, 4, devices=1)
    sink = listing.ArraySink(4)
    lres = listing.stream_cliques(g, 4, sink, devices=1)
    rows = sink.result()
    inject.configure(None)
    assert res.count == want
    assert res.stats.demotions > 0
    assert np.array_equal(rows, want_rows)
    assert lres.stats.demotions > 0


def _batches(g, k):
    plan = pipeline.build_plan(g, "hybrid")
    return [b for b in pipeline.stream_batches(plan, k, order="hybrid")
            if isinstance(b, pipeline.TileBatch)]


@pytest.mark.parametrize("error", [NotImplementedError, ValueError,
                                   TypeError])
def test_compile_error_propagates_from_count_dispatcher(monkeypatch, error):
    """A lowering/compile error is a fault of the program, not of the
    device: it must surface, never demote to another backend."""
    def refuse(self, batch, d, backend):
        raise error("the lowering refuses this kernel")

    monkeypatch.setattr(dsp.Dispatcher, "_launch_on", refuse)
    stats = Stats()
    disp = dsp.Dispatcher(3, 1, stats=stats)
    with pytest.raises(error, match="lowering refuses"):
        for batch in _batches(make_graph(), 5):
            disp.submit(batch)
        disp.finish()
    assert stats.demotions == 0 and stats.retries == 0


@pytest.mark.parametrize("site", ["_count_pass", "_list_once"])
@pytest.mark.parametrize("error", [NotImplementedError, ValueError])
def test_compile_error_propagates_from_list_dispatcher(monkeypatch, site,
                                                       error):
    def refuse(self, *args):
        raise error("the lowering refuses this kernel")

    monkeypatch.setattr(dsp.ListDispatcher, site, refuse)
    stats = Stats()
    disp = dsp.ListDispatcher(3, 1, sink=listing.ArraySink(5), stats=stats)
    try:
        with pytest.raises(error, match="lowering refuses"):
            for batch in _batches(make_graph(), 5):
                disp.submit(batch)
            disp.finish()
    finally:
        disp.close()
    assert stats.demotions == 0 and stats.retries == 0


@pytest.mark.parametrize("mode", ["count", "list"])
def test_runtime_error_and_injected_fault_ride_the_ladder(monkeypatch, mode):
    """What the device reports while running (a JaxRuntimeError) and an
    injected kernel.launch fault stay recoverable: retried or demoted,
    with output byte-identical to the fault-free run."""
    g = make_graph(seed=4)

    def observe():
        if mode == "count":
            res = engine_jax.count(g, 5, devices=1)
            return res.stats, res.count
        sink = listing.ArraySink(5)
        res = listing.stream_cliques(g, 5, sink, devices=1)
        return res.stats, sink.result()

    _, want = observe()
    # a JAX runtime failure on the first launch: retried in place
    cls = dsp.Dispatcher if mode == "count" else dsp.ListDispatcher
    site = "_launch_on" if mode == "count" else "_list_once"
    real = getattr(cls, site)
    calls = []

    def flaky(self, *args):
        calls.append(1)
        if len(calls) == 1:
            raise jax.errors.JaxRuntimeError("INTERNAL: device lost")
        return real(self, *args)

    monkeypatch.setattr(cls, site, flaky)
    stats, got = observe()
    assert stats.retries >= 1 and stats.demotions == 0
    assert np.array_equal(got, want)
    monkeypatch.setattr(cls, site, real)
    # an injected launch fault at every call: demoted down to the host
    inject.configure("seed=3;kernel.launch=1.0")
    stats, got = observe()
    inject.configure(None)
    assert stats.demotions > 0
    assert np.array_equal(got, want)


def test_chaos_serve_mixed_workload_byte_identical():
    """The PR's chaos gate: a mixed count+list serve workload under the
    all-sites plan returns byte-identical results to the fault-free run,
    with nonzero retry/demotion accounting and no hangs."""
    g1 = make_graph(seed=3)
    g2 = rmat_graph(6, 6, seed=2)
    work = [("g1", 4, "count"), ("g1", 5, "list"), ("g2", 5, "count"),
            ("g2", 4, "list"), ("g1", 5, "count"), ("g1", 4, "list")]

    def run():
        svc = CliqueService(chunk_tiles=16, fuse_rows=64)
        svc.register_graph("g1", g1)
        svc.register_graph("g2", g2)
        svc.pause()
        tickets = [(m, svc.submit(gn, k, m)) for gn, k, m in work]
        svc.resume()
        out = []
        for m, t in tickets:
            r = t.result(timeout=300)
            out.append(r.count if m == "count" else r.rows)
        stats = svc.engine_stats
        svc.close()
        return out, stats

    base, _ = run()
    inject.configure(CHAOS_PLAN)
    got, stats = run()
    n_fired = sum(inject.fired().values())
    inject.configure(None)
    for b, g in zip(base, got):
        if isinstance(b, np.ndarray):
            assert np.array_equal(g, b)
        else:
            assert g == b
    assert stats.retries > 0
    assert n_fired > 0


# ---------------------------------------------------------------------------
# request isolation + deadline enforcement + shutdown semantics
# ---------------------------------------------------------------------------


class _BoomSink(listing.CliqueSink):
    accepted = 0
    bytes_written = 0

    def emit(self, rows):
        raise RuntimeError("sink boom")

    def close(self):
        pass

    def result(self):
        return None


def test_one_request_failure_is_isolated():
    g = make_graph(seed=3)
    want = engine_jax.count(g, 5).count
    svc = CliqueService()
    svc.register_graph("g", g)
    t_bad = svc.submit("g", 5, "list", sink=_BoomSink())
    t_ok = svc.submit("g", 5, "count")
    with pytest.raises(RuntimeError, match="sink boom"):
        t_bad.result(timeout=120)
    assert t_ok.result(timeout=120).count == want  # cotenant unaffected
    # the service is still alive and serving new requests
    assert svc.submit("g", 4, "count").result(timeout=120).count == \
        engine_jax.count(g, 4).count
    assert svc.stats.isolated_failures == 1
    svc.close()


def test_admission_failure_is_isolated():
    g = make_graph(seed=3)
    svc = CliqueService()
    svc.register_graph("g", g)
    svc.register_graph("bad", object())  # cached_plan will reject this
    t_bad = svc.submit("bad", 4, "count")
    with pytest.raises(Exception):
        t_bad.result(timeout=120)
    assert svc.submit("g", 4, "count").result(timeout=120).count == \
        engine_jax.count(g, 4).count
    svc.close()


def test_enforced_deadline_cancels_cooperatively():
    g = make_graph(seed=3)
    want = engine_jax.count(g, 5).count
    svc = CliqueService()
    svc.register_graph("g", g)
    t = svc.submit("g", 6, "list", deadline_s=1e-4, enforce_deadline=True)
    with pytest.raises(DeadlineExceeded) as ei:
        t.result(timeout=120)
    # partials ride on the typed error (possibly empty, never None rows)
    assert ei.value.partial_rows is not None
    assert ei.value.partial_rows.shape[1] == 6
    assert ei.value.emitted == ei.value.partial_rows.shape[0]
    # the service keeps serving, and the cancel was counted
    assert svc.submit("g", 5, "count").result(timeout=120).count == want
    assert svc.stats.deadline_cancels == 1
    svc.close()


def test_unenforced_deadline_still_completes_exactly():
    g = make_graph(seed=3)
    svc = CliqueService()
    svc.register_graph("g", g)
    r = svc.submit("g", 5, "count", deadline_s=1e-5).result(timeout=120)
    assert r.count == engine_jax.count(g, 5).count
    assert r.deadline_missed
    svc.close()


def test_load_shedding_on_projected_miss():
    from repro.serve.request import Request, ServiceOverloaded
    from repro.serve.scheduler import BatchScheduler

    g = make_graph(seed=3)
    sched = BatchScheduler(shed_on_projected_miss=True, fuse_rows=4)
    # forge an observed throughput of ~5 tiles/s inside the rate window
    now = time.monotonic()
    sched._rate_samples.extend([(now - 20.0, 50), (now - 10.0, 50)])
    req = Request(g, 5, "count", deadline_s=0.05)
    req.mark_submitted()
    with pytest.raises(ServiceOverloaded):
        sched.admit(req)
    assert sched.stats.shed == 1
    # without a deadline the same request admits fine
    req2 = Request(g, 5, "count")
    req2.mark_submitted()
    sched.admit(req2)
    sched.fail_active(RuntimeError("test teardown"))
    sched.finish()


def test_shed_cold_start_and_stale_window_are_permissive():
    """Satellite regression: the shed estimator must never reject on a
    missing or stale rate.  Cold (no pulls yet) and post-idle (all
    samples aged out of the window) states admit deadline-bearing
    requests instead of shedding them on a decayed throughput guess."""
    from repro.serve.request import Request
    from repro.serve.scheduler import BatchScheduler

    g = make_graph(seed=3)
    # cold start: no observations at all -> permissive, no ZeroDivision
    sched = BatchScheduler(shed_on_projected_miss=True, fuse_rows=4)
    assert sched._observed_rate() is None
    req = Request(g, 5, "count", deadline_s=1e-6)
    req.mark_submitted()
    sched.admit(req)  # must not raise
    assert sched.stats.shed == 0
    sched.fail_active(RuntimeError("test teardown"))

    # post-idle: old samples fell out of the window -> permissive again.
    # Under the pre-fix lifetime tiles/(now - first_pull) estimator this
    # state read as a near-zero rate and shed the whole next burst.
    now = time.monotonic()
    sched._rate_samples.extend(
        [(now - 3600.0, 1000), (now - 3599.0, 1000)])
    assert sched._observed_rate(now) is None
    req2 = Request(g, 5, "count", deadline_s=1e-6)
    req2.mark_submitted()
    sched.admit(req2)
    assert sched.stats.shed == 0
    # too few recent tiles is also untrustworthy (below fuse_rows)
    sched._rate_samples.append((now - 1.0, 2))
    assert sched._observed_rate(now) is None
    sched.fail_active(RuntimeError("test teardown"))
    sched.finish()


def test_close_drain_false_resolves_active_and_queued():
    g = make_graph(seed=3)
    svc = CliqueService()
    svc.register_graph("g", g)
    svc.pause()  # everything below stays queued until close
    tickets = [svc.submit("g", 5, "count") for _ in range(6)]
    svc.close(drain=False)
    for t in tickets:
        with pytest.raises(ServiceClosed):
            t.result(timeout=30)  # resolves, never hangs
    with pytest.raises(ServiceClosed):
        svc.submit("g", 4, "count")
    svc.close()  # second close is idempotent
    svc.close(drain=False)


def test_close_drain_true_completes_inflight():
    g = make_graph(seed=3)
    want = engine_jax.count(g, 5).count
    svc = CliqueService()
    svc.register_graph("g", g)
    tickets = [svc.submit("g", 5, "count") for _ in range(3)]
    svc.close()
    for t in tickets:
        assert t.result(timeout=30).count == want


def test_list_dispatcher_close_mid_burst_no_torn_rows():
    """Regression for the teardown race: ``close()`` with decode jobs in
    flight must drain them to a barrier, never strand a sink write
    mid-row.  Every emitted row must be a complete, valid clique."""
    g = rmat_graph(8, 4, seed=7)
    k = 4
    batches = [b for b in pipeline.stream_batches(g, k, batch_size=16)
               if isinstance(b, pipeline.TileBatch)]
    assert len(batches) >= 4
    sink = listing.ArraySink(k)
    disp = dsp.ListDispatcher(k - 2, sink=sink, stats=Stats())
    for b in batches:
        disp.submit(b)
    disp.close()  # immediately, with decode work still in flight
    rows = sink.result()
    # all-or-nothing per decode job: each row is fully written (k distinct
    # vertices, no zero-padding torn off a partial write)
    if rows.shape[0]:
        assert rows.shape[1] == k
        assert all(len(set(r.tolist())) == k for r in rows)


def test_chaos_no_spurious_failures_under_serve_smoke_rate():
    """The CI chaos leg's contract in miniature: the loadgen-style rate
    (0.15 everywhere) must produce zero isolated failures -- consume-site
    retries and launch demotions absorb everything."""
    g = make_graph(seed=13)
    want = engine_jax.count(g, 5).count
    inject.configure("seed=7;*=0.15;kernel.launch=0.15")
    svc = CliqueService()
    svc.register_graph("g", g)
    tickets = [svc.submit("g", 5, "count") for _ in range(4)]
    for t in tickets:
        assert t.result(timeout=300).count == want
    assert svc.stats.isolated_failures == 0
    svc.close()
    inject.configure(None)
