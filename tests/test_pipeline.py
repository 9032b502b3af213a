"""Unified tile pipeline: parity with the Python reference oracle,
streaming-batcher invariants, scheduler mapping, oversize spill."""
import signal

import numpy as np
import pytest

from repro.core import ebbkc, engine_jax, pipeline
from repro.core import tiles as tiles_mod
from repro.data import erdos_renyi, planted_cliques, rmat_graph
from repro.runtime.clique_scheduler import schedule_batches, schedule_tiles

from conftest import random_graph


def parity_suite():
    return {
        "rmat": rmat_graph(8, 4, seed=7),
        "er": erdos_renyi(120, 0.12, seed=1),
        "plant": planted_cliques(150, 4, 9, p_noise=0.02, seed=5),
    }


def tiles_equal(a, b):
    return (a.anchor == b.anchor and np.array_equal(a.verts, b.verts)
            and a.rows == b.rows and a.nedges == b.nedges
            and a.colors == b.colors and a.edges_ranked == b.edges_ranked)


@pytest.mark.parametrize("mode", ["truss", "color", "hybrid"])
def test_iter_tiles_matches_reference(mode):
    for name, g in parity_suite().items():
        for k in range(3, 8):
            ref = list(tiles_mod.edge_tiles(g, k, mode=mode))
            got = list(pipeline.iter_tiles(g, k, mode=mode))
            assert len(ref) == len(got), (name, k, mode)
            for a, b in zip(ref, got):
                assert tiles_equal(a, b), (name, k, mode, a.anchor)


def test_color_mode_rule2_parity():
    g = parity_suite()["plant"]
    for use_rule2 in (True, False):
        ref = list(tiles_mod.edge_tiles(g, 5, mode="color",
                                        use_rule2=use_rule2))
        got = list(pipeline.iter_tiles(g, 5, mode="color",
                                       use_rule2=use_rule2))
        assert len(ref) == len(got)
        for a, b in zip(ref, got):
            assert tiles_equal(a, b)


@pytest.mark.parametrize("mode", ["truss", "color", "hybrid"])
def test_packed_batches_byte_identical(mode):
    """Streamed batches, concatenated per bin, match the reference
    extractor + packer byte for byte."""
    for name, g in parity_suite().items():
        k = 5
        binned = {}
        for t in tiles_mod.edge_tiles(g, k, mode=mode):
            T = next(b for b in pipeline.BINS if t.s <= b)
            binned.setdefault(T, []).append(t)
        ref = {T: engine_jax.pack_tiles(ts, T)
               for T, ts in sorted(binned.items())}
        got = {}
        for item in pipeline.stream_batches(g, k, order=mode, batch_size=64):
            assert isinstance(item, pipeline.TileBatch)
            got.setdefault(item.T, []).append(item)
        assert sorted(got) == sorted(ref), (name, mode)
        for T in ref:
            A = np.concatenate([b.A for b in got[T]])
            cand = np.concatenate([b.cand for b in got[T]])
            assert np.array_equal(A, ref[T].A), (name, mode, T)
            assert np.array_equal(cand, ref[T].cand), (name, mode, T)


def test_batcher_shape_and_coverage_invariants(rng):
    g = random_graph(rng, n_lo=25, n_hi=35, p_lo=0.4, p_hi=0.7)
    k = 4
    n_ref = sum(1 for _ in tiles_mod.edge_tiles(g, k, mode="hybrid"))
    seen = 0
    for item in pipeline.stream_batches(g, k, batch_size=8):
        assert isinstance(item, pipeline.TileBatch)
        B, T, W = item.A.shape
        assert B <= 8 and T in pipeline.BINS and W == T // 32
        assert item.cand.shape == (B, W)
        assert item.sizes.shape == (B,) and item.nedges.shape == (B,)
        assert item.anchors.shape == (B, 2)
        assert (item.sizes <= T).all() and (item.sizes > 0).all()
        # decode table for the emission subsystem: valid global vertex
        # ids in every live slot, zero padding beyond sizes
        assert item.verts.shape == (B, T)
        live = np.arange(T)[None, :] < item.sizes[:, None]
        assert ((item.verts >= 0) & (item.verts < g.n))[live].all()
        assert (item.verts[~live] == 0).all()
        seen += B
    assert seen == n_ref


def test_plan_reuse_skips_preprocessing(rng):
    g = random_graph(rng, n_lo=20, n_hi=30, p_lo=0.4, p_hi=0.7)
    plan = pipeline.build_plan(g, order="hybrid")
    table_before = plan.table("hybrid")
    r1 = ebbkc.count(g, 4, plan=plan)
    r2 = ebbkc.count(g, 5, plan=plan)
    assert plan.table("hybrid") is table_before  # cached, not rebuilt
    assert r1.count == ebbkc.count(g, 4).count
    assert r2.count == ebbkc.count(g, 5).count


MODES = ("truss", "color", "hybrid")
# three workers with a window of four chunks, and one worker with a window
# of one: the two corners of the FIFO
PRODUCER_CASES = [pytest.param(3, 4, mode, 5, id=mode) for mode in MODES] \
    + [pytest.param(3, 4, mode, k, id=f"{mode}-k{k}")
       for mode in MODES for k in (4, 6)] \
    + [pytest.param(1, 1, mode, k, id=f"one-worker-{mode}-k{k}")
       for mode in MODES for k in (4, 5, 6)]


@pytest.fixture
def deadline():
    """A time limit of this test's own: a hung pack pool fails the test
    instead of running the suite into its limit."""
    def expire(signum, frame):
        raise TimeoutError("the pack pool hung past the test's 90 s limit")

    old = signal.signal(signal.SIGALRM, expire)
    signal.alarm(90)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


def assert_same_stream(ref, got):
    """Byte-identical streams: every batch array, dtype, index and spill."""
    assert len(ref) == len(got)
    for a, b in zip(ref, got):
        assert type(a) is type(b)
        if isinstance(a, pipeline.TileBatch):
            assert (a.T, a.index) == (b.T, b.index)
            for f in ("A", "cand", "sizes", "nedges", "anchors", "verts"):
                x, y = getattr(a, f), getattr(b, f)
                assert x.dtype == y.dtype and np.array_equal(x, y), f
        else:
            assert tiles_equal(a, b)


@pytest.mark.parametrize("workers, prefetch, mode, k", PRODUCER_CASES)
def test_parallel_producer_matches_serial(workers, prefetch, mode, k,
                                          deadline):
    """pack_workers > 0 yields the byte-identical batch stream (content
    AND order), spill tiles included, for every ordering, packed in
    worker processes."""
    from repro.core.engine_np import Stats

    g = rmat_graph(8, 4, seed=7)
    for bins, batch_size in (((32, 64, 128, 256), 16), ((32,), 8)):
        ref = list(pipeline.stream_batches(g, k, order=mode, bins=bins,
                                           batch_size=batch_size))
        stats = Stats()
        got = list(pipeline.stream_batches(g, k, order=mode, bins=bins,
                                           batch_size=batch_size,
                                           pack_workers=workers,
                                           prefetch=prefetch, stats=stats))
        assert_same_stream(ref, got)
        assert stats.packed_batches == sum(
            isinstance(b, pipeline.TileBatch) for b in got) > 1
        assert stats.pack_proc_batches == stats.packed_batches


def test_pack_faults_fire_alike_on_both_executors(deadline):
    """An armed pack fault plan is absorbed in place inline and in worker
    processes: the same stream, the same faults fired and counted."""
    from repro.obs import metrics as obs_metrics
    from repro.resilience import inject

    g = rmat_graph(8, 4, seed=7)
    ref = list(pipeline.stream_batches(g, 5, batch_size=8))
    counter = obs_metrics.get_registry().counter(
        "repro_faults_injected_total", site="pack")
    seen = {}
    for workers in (0, 2):
        before = counter.value
        inject.configure("seed=3;pack=0.4")
        try:
            got = list(pipeline.stream_batches(g, 5, batch_size=8,
                                               pack_workers=workers,
                                               prefetch=3))
            seen[workers] = (inject.calls("pack"), inject.fired("pack"),
                             counter.value - before)
        finally:
            inject.configure(None)
        assert_same_stream(ref, got)
    assert seen[0] == seen[2]
    assert seen[2][1] > 0


def test_process_pool_lives_with_the_table(deadline):
    """Two jobs on one plan reuse one pool and ship the edge index once;
    after an edge batch the same worker processes pack the repaired plan
    with its new index, and count what a fresh plan counts."""
    from repro.core import packing
    from repro.core.engine_np import Stats
    from repro.core.graph import apply_edge_batch
    from repro.delta import repair_plan

    g = rmat_graph(8, 4, seed=7)
    plan = pipeline.build_plan(g, "hybrid")

    def job(p):
        stats = Stats()
        items = list(pipeline.stream_batches(p, 5, batch_size=8,
                                             pack_workers=2, stats=stats))
        assert stats.pack_proc_batches == stats.packed_batches > 1
        return items

    first = job(plan)
    pool = packing.pool(2)
    pids, sends = pool.pids(), pool.index_sends
    # a stream abandoned with chunks in flight leaves nothing behind
    stream = pipeline.stream_batches(plan, 5, batch_size=8, pack_workers=2)
    next(stream)
    stream.close()
    assert_same_stream(first, job(plan))
    assert packing.pool(2) is pool and pool.pids() == pids
    assert pool.index_sends == sends  # the second job shipped no index

    g2 = apply_edge_batch(g, insert=[(0, 200), (3, 150)],
                          delete=[tuple(g.edges[0]), tuple(g.edges[50])])
    plan2, info = repair_plan(plan, g2, "hybrid", churn_threshold=1.1)
    assert not info.rebuilt
    # a repaired table orders its tiles its own way, so its stream is
    # compared with its own inline stream, and its count with a fresh plan's
    assert_same_stream(list(pipeline.stream_batches(plan2, 5, batch_size=8)),
                       job(plan2))
    assert packing.pool(2) is pool and pool.pids() == pids
    assert pool.index_sends > sends
    got = engine_jax.count(g2, 5, plan=plan2, batch_size=8, pack_workers=2)
    assert got.stats.pack_proc_batches > 0
    assert got.count == ebbkc.count(g2, 5,
                                    plan=pipeline.build_plan(g2)).count


def test_crashed_pack_worker_raises(deadline):
    """A pack worker that dies fails the stream; the next stream gets a
    fresh pool."""
    import os

    from repro.core import packing

    g = rmat_graph(8, 4, seed=7)
    ref = list(pipeline.stream_batches(g, 5, batch_size=8))
    pool = packing.pool(2)
    os.kill(pool.pids()[0], signal.SIGKILL)
    with pytest.raises(packing.PackWorkerError):
        list(pipeline.stream_batches(g, 5, batch_size=8, pack_workers=2,
                                     prefetch=4))
    assert pool.broken
    assert packing.pool(2) is not pool
    assert_same_stream(ref, list(pipeline.stream_batches(
        g, 5, batch_size=8, pack_workers=2)))


def test_pack_workers_import_no_jax_and_exit_with_the_parent(tmp_path):
    """A worker boots from repro.core.packing without JAX, and a program
    that packs in processes leaves none behind when it exits."""
    import os
    import subprocess
    import sys

    from repro.core import packing

    boot = subprocess.run(
        [sys.executable, "-X", "importtime", "-c", packing.BOOT,
         packing._SRC], stdin=subprocess.DEVNULL, capture_output=True,
        timeout=60)
    assert boot.returncode == 0, boot.stderr.decode()
    mods = {line.rsplit("|", 1)[-1].strip()
            for line in boot.stderr.decode().splitlines()
            if line.startswith("import time:")}
    assert "repro.core.packing" in mods
    assert not {m for m in mods if m.split(".")[0] in ("jax", "jaxlib")}

    script = tmp_path / "job.py"
    script.write_text(
        "import sys\n"
        f"sys.path.insert(0, {packing._SRC!r})\n"
        "from repro.core import packing, pipeline\n"
        "from repro.data import rmat_graph\n"
        "g = rmat_graph(8, 4, seed=7)\n"
        "list(pipeline.stream_batches(g, 5, batch_size=8, pack_workers=2))\n"
        "print(*packing.pool(2).pids())\n")
    run = subprocess.run([sys.executable, str(script)], capture_output=True,
                         timeout=60)
    assert run.returncode == 0, run.stderr.decode()
    pids = [int(p) for p in run.stdout.split()]
    assert len(pids) == 2
    assert not [p for p in pids if os.path.exists(f"/proc/{p}")]


def test_parallel_producer_stats_and_timings():
    from repro.core.engine_np import Stats

    g = rmat_graph(8, 4, seed=7)
    stats = Stats()
    timings = {}
    n = sum(1 for _ in pipeline.stream_batches(
        g, 5, batch_size=16, pack_workers=2, prefetch=3,
        timings=timings, stats=stats))
    assert n > 1
    assert stats.pack_workers == 2
    assert stats.frontend_s > 0.0
    assert stats.frontend_s >= timings.get("pack", 0.0)
    assert 0.0 < stats.pack_queue_occupancy <= 1.0
    assert 1 <= stats.pack_queue_peak <= 3
    # serial path reports workers=0 and no queue
    s2 = Stats()
    list(pipeline.stream_batches(g, 5, batch_size=16, pack_workers=0,
                                 stats=s2))
    assert s2.pack_workers == 0 and s2.pack_queue_peak == 0
    assert s2.frontend_s > 0.0


def test_plan_cache_warm_queries_skip_decomposition(monkeypatch):
    """Acceptance: a warm plan-cached query never reaches the O(delta*m)
    truss decomposition, and Stats says so."""
    from repro.core import engine_jax as ej

    g = rmat_graph(7, 4, seed=3)
    ref4 = ebbkc.count(g, 4).count
    ref5 = ebbkc.count(g, 5).count
    pipeline.clear_plan_cache()
    r1 = ej.count(g, 4)
    assert not r1.stats.plan_cache_hit
    assert r1.stats.plan_build_s > 0.0
    assert r1.count == ref4

    def boom(*a, **kw):  # pragma: no cover - must never run
        raise AssertionError("truss decomposition re-ran on a warm query")

    monkeypatch.setattr(pipeline, "truss_decomposition", boom)
    # warm: same graph content, different k and even a different Graph
    # object (the key is content-addressed)
    r2 = ej.count(g, 5)
    assert r2.stats.plan_cache_hit and r2.stats.plan_build_s == 0.0
    assert r2.count == ref5
    g2 = rmat_graph(7, 4, seed=3)
    r3 = ej.count(g2, 4, devices=1)
    assert r3.stats.plan_cache_hit and r3.count == ref4
    # truss-order queries share the hybrid family table
    r4 = ej.count(g, 4, order="truss")
    assert r4.stats.plan_cache_hit
    # a cold cache really does rebuild (the tripwire fires)
    with pytest.raises(AssertionError, match="re-ran"):
        pipeline.clear_plan_cache()
        ej.count(g, 4)


def test_plan_save_load_roundtrip(tmp_path):
    g = rmat_graph(7, 4, seed=3)
    pipeline.clear_plan_cache()
    plan = pipeline.build_plan(g, order="hybrid")
    plan.table("color")  # persist both families
    path = str(tmp_path / "plan")
    pipeline.save_plan(plan, path)
    got = pipeline.load_plan(path)
    assert got is not None
    assert got.g.n == g.n and np.array_equal(got.g.edges, g.edges)
    assert got.td.tau == plan.td.tau  # decomposition restored, not rebuilt
    for family in ("truss", "color"):
        a, b = plan.table(family), got.table(family)
        for f in ("edge_id", "anchors", "offsets", "verts", "thresh",
                  "ekeys"):
            assert np.array_equal(getattr(a, f), getattr(b, f)), (family, f)
    for k in (4, 5):
        assert ebbkc.count(g, k, plan=got).count == ebbkc.count(g, k).count
    assert pipeline.load_plan(str(tmp_path / "nope")) is None


def test_plan_cache_dir_warms_across_processes(tmp_path, monkeypatch):
    """cache_dir simulates the restarted-process path: clear the
    in-process cache, reload from disk, decomposition still skipped."""
    from repro.core.engine_np import Stats

    g = rmat_graph(7, 4, seed=9)
    ref = ebbkc.count(g, 4).count
    cache = str(tmp_path / "plans")
    pipeline.clear_plan_cache()
    s1 = Stats()
    pipeline.cached_plan(g, "hybrid", cache_dir=cache, stats=s1)
    assert not s1.plan_cache_hit and s1.plan_build_s > 0.0
    pipeline.clear_plan_cache()  # "new process"
    monkeypatch.setattr(
        pipeline, "truss_decomposition",
        lambda *a, **kw: (_ for _ in ()).throw(AssertionError("re-ran")))
    s2 = Stats()
    plan = pipeline.cached_plan(g, "hybrid", cache_dir=cache, stats=s2)
    assert s2.plan_cache_hit and s2.plan_build_s == 0.0
    assert ebbkc.count(g, 4, plan=plan).count == ref


def test_plan_key_no_vertex_count_aliasing():
    """Satellite regression: the cache key must fold in the full graph
    identity, not just the edge bytes + family.

    Two graphs with byte-identical edge lists but different ``n``
    (trailing isolated vertices) are *different plans*: edge keys are
    ``u * n + v``, so a plan built for the smaller vertex set mis-probes
    adjacency when served for the larger graph.  The pre-fix edges-only
    key collides for the twins; ``pipeline.plan_key`` must not -- and
    serving the aliased plan must be demonstrably wrong, so this test
    fails loudly if the key ever regresses.
    """
    import hashlib

    from repro.core.graph import from_edges

    s = 5
    edges = np.argwhere(np.triu(np.ones((s, s), bool), 1)).astype(np.int64)
    g_small = from_edges(s, edges)        # K5, n = 5
    g_big = from_edges(s + 3, edges)      # K5 + 3 isolated vertices
    assert np.array_equal(g_small.edges, g_big.edges)

    def prefix_key(g, order):  # the pre-fix key: family + edge bytes only
        family = "color" if order == "color" else "truss"
        h = hashlib.sha256()
        h.update(f"plan-v{pipeline.PLAN_FORMAT}:{family}:".encode())
        h.update(np.ascontiguousarray(g.edges).tobytes())
        return h.hexdigest()[:24]

    # the old key aliases the twins; the fixed key separates them
    assert prefix_key(g_small, "hybrid") == prefix_key(g_big, "hybrid")
    assert pipeline.plan_key(g_small, "hybrid") != \
        pipeline.plan_key(g_big, "hybrid")
    # ...and the canonicalization contract is part of the key, so a
    # future from_edges change re-keys instead of aliasing stale plans
    assert pipeline.PLAN_CANON in ("dedup-lexsorted-v1",)

    # the aliasing is not harmless: a plan is only substitutable for the
    # graph identity it was keyed under.  The dynamic-graph update path
    # mutates vertices that exist only in the big twin; handed the
    # aliased small-n plan it hard-fails, while the correctly keyed
    # plan for the same request repairs cleanly and stays exact
    from repro.core.graph import apply_edge_batch
    from repro.delta import repair_plan

    g_mut = apply_edge_batch(g_big, insert=[(0, s), (1, s), (0, s + 1)])
    plan_small = pipeline.build_plan(g_small, "hybrid")
    with pytest.raises(ValueError):
        repair_plan(plan_small, g_mut, "hybrid")
    pipeline.clear_plan_cache()
    plan_big = pipeline.cached_plan(g_big, "hybrid")
    assert plan_big.g.n == g_big.n  # correct key -> correct identity
    repaired, _ = repair_plan(plan_big, g_mut, "hybrid",
                              churn_threshold=1.1)
    for k in (3, 4):
        assert ebbkc.count(g_mut, k, plan=repaired).count == \
            ebbkc.count(g_mut, k).count


def test_plan_cache_single_flight_race():
    """Satellite regression: two threads racing a cold key must elect
    exactly one builder -- the loser blocks on the latch and reports a
    cache hit with zero build time (the pre-fix path double-built and
    the loser's insert clobbered the winner's published plan)."""
    import threading

    from repro.core.engine_np import Stats

    g = rmat_graph(8, 4, seed=21)
    pipeline.clear_plan_cache()
    barrier = threading.Barrier(2)
    stats = [Stats(), Stats()]
    plans = [None, None]
    errs = []

    def worker(i):
        try:
            barrier.wait(timeout=30)
            plans[i] = pipeline.cached_plan(g, "hybrid", stats=stats[i])
        except Exception as exc:  # pragma: no cover - failure reporting
            errs.append(exc)

    threads = [threading.Thread(target=worker, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errs
    assert plans[0] is plans[1]  # one published plan object, shared
    built = [s for s in stats if s.plan_build_s > 0.0]
    hits = [s for s in stats if s.plan_cache_hit]
    assert len(built) == 1 and len(hits) == 1
    assert hits[0] is not built[0]
    assert ebbkc.count(g, 4, plan=plans[0]).count == ebbkc.count(g, 4).count


def test_scheduler_batches_partition(rng):
    g = random_graph(rng, n_lo=25, n_hi=35, p_lo=0.5, p_hi=0.8)
    batches = [b for b in pipeline.stream_batches(g, 4, batch_size=4)
               if isinstance(b, pipeline.TileBatch)]
    assert len(batches) > 1
    device_bins, stats = schedule_batches(batches, l=2, n_devices=3)
    flat = sorted(i for b in device_bins for i in b)
    # every packed batch lands in exactly one device bin
    assert flat == list(range(len(batches)))
    assert stats["device_loads"].shape == (3,)
    # schedule_tiles consumes the batch's per-tile metadata directly
    bins, st = schedule_tiles(batches[0], l=2, n_devices=2)
    assert sorted(i for b in bins for i in b) == list(range(batches[0].B))


def test_oversize_tiles_spill_to_host(rng):
    g = random_graph(rng, n_lo=42, n_hi=48, p_lo=0.96, p_hi=0.99)
    k = 4
    items = list(pipeline.stream_batches(g, k, bins=(32,)))
    spilled = [t for t in items if isinstance(t, tiles_mod.Tile)]
    assert spilled, "expected tiles wider than the 32-bin"
    ref = ebbkc.count(g, k).count
    r = engine_jax.count(g, k, interpret=True, bins=(32,))
    assert r.count == ref
    assert r.stats.spilled_tiles == len(spilled)
    # every spill is recorded exactly once, with its width, so host-
    # recursion work stays attributable separately from device batches
    assert sorted(r.stats.spill_sizes) == sorted(t.s for t in spilled)
    assert all(s > 32 for s in r.stats.spill_sizes)
    # without a spill list the compatibility binner keeps the old behavior
    with pytest.raises(ValueError):
        engine_jax.bin_tiles(g, k, bins=(32,))
    spill = []
    binned = engine_jax.bin_tiles(g, k, spill=spill, bins=(32,))
    assert len(spill) == len(spilled)
    assert sum(p.A.shape[0] for p in binned.values()) + len(spill) \
        == sum(1 for _ in tiles_mod.edge_tiles(g, k, mode="hybrid"))


def test_spill_interacts_with_multi_device_dispatch(rng):
    """Spill + dispatch: oversize tiles go to the host recursion exactly
    once while the packed remainder shards across all local devices, and
    the combined count still matches the host oracle."""
    import jax

    g = random_graph(rng, n_lo=42, n_hi=48, p_lo=0.96, p_hi=0.99)
    k = 4
    ref = ebbkc.count(g, k).count
    n_dev = jax.device_count()
    r = engine_jax.count(g, k, interpret=True, bins=(32,), devices=n_dev)
    assert r.count == ref
    assert r.stats.spilled_tiles == len(r.stats.spill_sizes) > 0
    assert all(s > 32 for s in r.stats.spill_sizes)
    # device accounting covers exactly the non-spilled tiles
    assert sum(r.stats.device_tiles.values()) \
        == r.tiles - r.stats.spilled_tiles
    # spilled work never lands in the device accounting
    assert all(d in range(n_dev) for d in r.stats.device_tiles)


def test_list_cliques_max_out_exact(rng):
    g = random_graph(rng, n_lo=16, n_hi=20, p_lo=0.6, p_hi=0.9)
    k = 4
    full, _ = ebbkc.list_cliques(g, k)
    assert len(full) > 7
    for cap in (0, 1, 3, 7, len(full), len(full) + 5):
        got, _ = ebbkc.list_cliques(g, k, max_out=cap)
        assert got.shape == (min(cap, len(full)), k)
        as_set = {tuple(r) for r in full.tolist()}
        assert all(tuple(r) in as_set for r in got.tolist())
    # the k <= 2 shortcuts honor the cap too
    got1, _ = ebbkc.list_cliques(g, 1, max_out=3)
    assert got1.shape == (3, 1)
    got2, _ = ebbkc.list_cliques(g, 2, max_out=3)
    assert got2.shape == (3, 2)
