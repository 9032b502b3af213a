"""Chip smoke test: count, list and serve k-cliques on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py               # one chip: phases a, b, c, d
    python chip_smoke.py --four-chips  # four chips: 1-chip vs 4-chip parity

Every phase goes through the entry points a user calls
(``engine_jax.count``, ``listing.stream_cliques``, ``CliqueService``) on
RMAT graphs made from a fixed seed (``launch.clique.load_graph``):

a. ``rmat:12``, k = 5: the device count equals the host engine's count,
   and the device listing equals the host listing row for row (both
   sorted lexicographically).
b. ``rmat:--scale`` (default 14), k = 5: plan build, count and list.  Every
   listed row is sorted, unique and a k-clique of the graph (checked
   against its CSR), and the rows number the count.
c. A ``CliqueService`` with that graph registered answers counts, a
   filtered top-N listing, one ``update_graph`` and a ``mode="delta"``
   read; each answer equals the direct engine call on the same snapshot,
   and the delta equals an independent host listing of the cliques
   through the inserted edges.
d. ``backend="pallas"`` raises the TPU lowering's own error and does not
   demote to another backend.

Each phase asserts the default backend ran, with no retry, no demotion
and no armed fault plan.  ``--four-chips`` runs only the parity check:
the count and the listing on chip 0, then the count with online LPT over
four chips, the count with the ``shard_map`` step over a 4x1 mesh, and
the listing over four chips; counts and row streams must be identical.

The times printed are smoke timings of one run, not benchmark metrics.
The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Where JAX finds no TPU the script exits non-zero and prints no result;
``--rehearse`` runs the phases on any platform (CPU rehearsal) and then
prints a ``rehearsal`` line instead, never ``ok``.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import tune  # noqa: E402
from repro.core import ebbkc, engine_jax, listing, pipeline  # noqa: E402
from repro.core.engine_np import Stats  # noqa: E402
from repro.core.graph import apply_edge_batch, from_edges  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.launch.clique import load_graph  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.resilience import inject, retry  # noqa: E402
from repro.serve import CliqueService, apply_vertex_filter  # noqa: E402

K = 5


class SmokeFailure(AssertionError):
    """A phase's check failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(*parts) -> None:
    print(*parts, flush=True)


def lex_sorted(rows: np.ndarray) -> np.ndarray:
    """Rows sorted lexicographically (first column most significant)."""
    if rows.shape[0] == 0:
        return rows
    return rows[np.lexsort(rows.T[::-1])]


def csr_has(g, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Vectorized adjacency test of vertex pairs against ``g``'s CSR."""
    keys = np.repeat(np.arange(g.n, dtype=np.int64), np.diff(g.indptr))
    keys = keys * np.int64(g.n) + g.indices
    want = a.astype(np.int64) * np.int64(g.n) + b.astype(np.int64)
    pos = np.clip(np.searchsorted(keys, want), 0, keys.size - 1)
    return keys[pos] == want


def check_rows(g, rows: np.ndarray, k: int) -> None:
    """Every row is strictly increasing, unique, and a k-clique of ``g``."""
    check(rows.ndim == 2 and rows.shape[1] == k, f"rows shape {rows.shape}")
    check(bool(np.all(rows[:, 1:] > rows[:, :-1])), "a row is not sorted")
    check(bool(rows.min(initial=0) >= 0 and rows.max(initial=0) < g.n),
          "a vertex id is out of range")
    for i in range(k):
        for j in range(i + 1, k):
            check(bool(csr_has(g, rows[:, i], rows[:, j]).all()),
                  f"a row's vertices in columns {i},{j} are not adjacent")
    # uniqueness: pack the ids exactly into int64 words, sort, compare
    bits = max(1, int(g.n - 1).bit_length())
    per = 63 // bits
    words = []
    for s in range(0, k, per):
        w = np.zeros(rows.shape[0], dtype=np.int64)
        for c in range(s, min(k, s + per)):
            w = (w << bits) | rows[:, c]
        words.append(w)
    order = np.lexsort(words[::-1])
    same = np.ones(max(rows.shape[0] - 1, 0), dtype=bool)
    for w in words:
        ws = w[order]
        same &= ws[1:] == ws[:-1]
    check(not same.any(), "a clique is listed twice")


class Collect(listing.CliqueSink):
    """Keeps the emitted rows and a digest of the row stream, in order."""
    def __init__(self, k: int) -> None:
        super().__init__()
        self.k = k
        self.chunks = []
        self.digest = hashlib.blake2b(digest_size=16)

    def emit(self, rows: np.ndarray) -> int:
        rows = np.ascontiguousarray(rows)
        self.digest.update(rows.tobytes())
        self.chunks.append(rows)
        return self._account(rows)

    def rows(self) -> np.ndarray:
        if not self.chunks:
            return np.zeros((0, self.k), dtype=np.int64)
        return np.concatenate(self.chunks)


class FilterSink(listing.CliqueSink):
    """Direct-call twin of a filtered top-N request: filter, then cap."""
    def __init__(self, k: int, vertex: int, max_out: int) -> None:
        super().__init__()
        self.vertex = vertex
        self.kept = listing.ArraySink(k, max_out=max_out)

    @property
    def full(self) -> bool:
        return self.kept.full

    def emit(self, rows: np.ndarray) -> int:
        return self.kept.emit(apply_vertex_filter(rows, self.vertex))


def expect_clean(stats, what: str, backend: str, t0: float) -> None:
    """The default backend ran, nothing retried or demoted, no faults."""
    check(stats.backend == backend,
          f"{what}: backend {stats.backend!r}, expected {backend!r}")
    check(stats.demotions == 0, f"{what}: {stats.demotions} demotions")
    check(stats.retries == 0, f"{what}: {stats.retries} retries")
    check(not inject.enabled(), f"{what}: a fault plan is armed")
    say(f"  {what}: backend={stats.backend} demotions={stats.demotions} "
        f"retries={stats.retries} spilled_tiles={stats.spilled_tiles} "
        f"overflowed_tiles={stats.overflowed_tiles} "
        f"seconds={time.perf_counter() - t0:.3f}")


def phase_oracle(dev, backend: str, scale: int) -> None:
    """(a) device count and rows equal the host engine's."""
    spec = f"rmat:{scale}"
    g = load_graph(spec)
    say(f"[a] oracle on {spec} (n={g.n} m={g.m}), k={K}")
    t0 = time.perf_counter()
    res = engine_jax.count(g, K, devices=[dev])
    expect_clean(res.stats, "device count", backend, t0)
    t0 = time.perf_counter()
    sink = listing.ArraySink(K)
    lres = listing.stream_cliques(g, K, sink, devices=[dev])
    rows = sink.result()
    expect_clean(lres.stats, "device list", backend, t0)
    t0 = time.perf_counter()
    want = ebbkc.count(g, K).count
    host_rows, _ = ebbkc.list_cliques(g, K, backend="host")
    say(f"  host count and list: seconds={time.perf_counter() - t0:.3f}")
    check(res.count == want, f"device count {res.count} != host {want}")
    check(np.array_equal(lex_sorted(rows), lex_sorted(host_rows)),
          f"device rows ({rows.shape[0]}) != host rows "
          f"({host_rows.shape[0]})")
    say(f"[a] PASS count={res.count} rows={rows.shape[0]} match host")


def load_scaled(scale: int):
    spec = f"rmat:{scale}"
    t0 = time.perf_counter()
    g = load_graph(spec)
    t1 = time.perf_counter()
    plan = pipeline.cached_plan(g, order="hybrid", stats=Stats())
    t2 = time.perf_counter()
    say(f"  {spec}: n={g.n} generated_edges={g.n * 8} m={g.m} "
        f"load_seconds={t1 - t0:.3f} plan_seconds={t2 - t1:.3f}")
    return spec, g, plan


def phase_scale(dev, backend: str, scale: int):
    """(b) count and list at scale; structural checks of every row."""
    say(f"[b] count and list at rmat:{scale}, k={K}")
    spec, g, plan = load_scaled(scale)
    t0 = time.perf_counter()
    res = engine_jax.count(g, K, plan=plan, devices=[dev])
    expect_clean(res.stats, "device count", backend, t0)
    t0 = time.perf_counter()
    col = Collect(K)
    lres = listing.stream_cliques(plan, K, col, devices=[dev])
    expect_clean(lres.stats, "device list", backend, t0)
    rows = col.rows()
    t0 = time.perf_counter()
    check(rows.shape[0] == res.count,
          f"listed {rows.shape[0]} rows, counted {res.count}")
    check_rows(g, rows, K)
    say(f"  row checks: seconds={time.perf_counter() - t0:.3f}")
    say(f"[b] PASS {spec} count={res.count} rows={rows.shape[0]} "
        f"sorted, unique, all {K}-cliques")
    return g, plan, res.count


def pick_inserts(g, n_edges: int) -> np.ndarray:
    """Non-edges (u, v) with large common neighbourhoods, so inserting
    them creates k-cliques.  ``u`` starts below the 128 largest degrees:
    an edge at a top hub touches most tiles, and the host-side delta read
    then takes minutes at rmat:14."""
    deg = g.degrees()
    out = []
    for u in np.argsort(-deg, kind="stable")[128: 128 + 4 * n_edges]:
        nu = g.neighbors(int(u))
        nbrs = np.concatenate([g.neighbors(int(w)) for w in nu])
        common = np.bincount(nbrs, minlength=g.n)
        common[u] = 0
        common[nu] = 0
        v = int(np.argmax(common))
        if common[v] < K:
            continue
        pair = (int(min(u, v)), int(max(u, v)))
        if pair not in out:
            out.append(pair)
        if len(out) == n_edges:
            break
    check(len(out) > 0, "no insertable edge found")
    return np.asarray(out, dtype=np.int64)


def cliques_through(g_new, edges: np.ndarray, k: int) -> np.ndarray:
    """Host oracle: every k-clique of ``g_new`` holding an edge of
    ``edges`` -- the (k-2)-cliques of each edge's common neighbourhood."""
    found = []
    for u, v in edges:
        common = np.intersect1d(g_new.neighbors(int(u)),
                                g_new.neighbors(int(v)))
        e = g_new.edges
        inside = np.isin(e[:, 0], common) & np.isin(e[:, 1], common)
        local = np.searchsorted(common, e[inside])
        sub = from_edges(common.size, local)
        tri, _ = ebbkc.list_cliques(sub, k - 2, backend="host")
        if tri.shape[0]:
            uv = np.broadcast_to(np.array([u, v]), (tri.shape[0], 2))
            found.append(np.sort(np.hstack([uv, common[tri]]), axis=1))
    if not found:
        return np.zeros((0, k), dtype=np.int64)
    return np.unique(np.concatenate(found), axis=0)


def phase_serve(dev, backend: str, g, plan, count5: int) -> None:
    """(c) service answers equal the direct engine calls."""
    say(f"[c] CliqueService over the phase-b graph (n={g.n} m={g.m})")
    hub = int(np.argmax(g.degrees()))
    t_all = time.perf_counter()
    svc = CliqueService(devices=[dev])
    try:
        svc.register_graph("g", g)
        t0 = time.perf_counter()
        tickets = [
            svc.submit("g", K, "count"),
            svc.submit("g", K - 1, "count"),
            svc.submit("g", K, "list", vertex_filter=hub, max_out=1000),
        ]
        got = [t.result(timeout=900) for t in tickets]
        say(f"  3 concurrent requests: seconds={time.perf_counter() - t0:.3f}")
        check(got[0].count == count5,
              f"served k={K} count {got[0].count} != engine {count5}")
        t0 = time.perf_counter()
        want4 = engine_jax.count(g, K - 1, plan=plan, devices=[dev])
        expect_clean(want4.stats, f"direct k={K - 1} count", backend, t0)
        check(got[1].count == want4.count,
              f"served k={K - 1} count {got[1].count} != {want4.count}")
        t0 = time.perf_counter()
        fs = FilterSink(K, hub, 1000)
        lres = listing.stream_cliques(plan, K, fs, devices=[dev])
        expect_clean(lres.stats, "direct filtered list", backend, t0)
        want_rows = fs.kept.result()
        check(want_rows.shape[0] > 0, "filtered listing is empty")
        check(np.array_equal(got[2].rows, want_rows),
              f"served filtered rows ({got[2].rows.shape[0]}) != direct "
              f"({want_rows.shape[0]})")
        say(f"  count k={K}={got[0].count} count k={K - 1}={got[1].count} "
            f"list v={hub} max_out=1000 rows={want_rows.shape[0]}: match")

        inserts = pick_inserts(g, 3)
        t0 = time.perf_counter()
        version = svc.update_graph("g", insert=inserts)
        say(f"  update_graph inserted {inserts.tolist()} -> version "
            f"{version}: seconds={time.perf_counter() - t0:.3f}")
        t0 = time.perf_counter()
        tickets = [
            svc.submit("g", K, "delta", since_version=0),
            svc.submit("g", K, "count"),
            svc.submit("g", K, "list", vertex_filter=int(inserts[0, 0]),
                       max_out=500),
        ]
        got = [t.result(timeout=900) for t in tickets]
        say(f"  3 post-update requests: seconds="
            f"{time.perf_counter() - t0:.3f}")
        g_new = apply_edge_batch(g, insert=inserts)
        gained = cliques_through(g_new, inserts, K)
        check(gained.shape[0] > 0, "the inserted edges gained no clique")
        check(np.array_equal(lex_sorted(got[0].rows), gained),
              f"served delta ({got[0].rows.shape[0]} rows) != host "
              f"oracle ({gained.shape[0]} rows)")
        check(got[1].count == count5 + gained.shape[0],
              f"post-update count {got[1].count} != {count5} + "
              f"{gained.shape[0]} gained")
        t0 = time.perf_counter()
        direct = engine_jax.count(g_new, K, devices=[dev])
        expect_clean(direct.stats, "direct post-update count", backend, t0)
        check(direct.count == got[1].count,
              f"post-update served {got[1].count} != direct {direct.count}")
        t0 = time.perf_counter()
        fs = FilterSink(K, int(inserts[0, 0]), 500)
        lres = listing.stream_cliques(g_new, K, fs, devices=[dev])
        expect_clean(lres.stats, "direct post-update list", backend, t0)
        check(np.array_equal(got[2].rows, fs.kept.result()),
              "post-update filtered rows != direct")
        say(f"  delta rows={gained.shape[0]} post-update count="
            f"{got[1].count} filtered rows={got[2].rows.shape[0]}: match")
    finally:
        svc.close(timeout=300)
    expect_clean(svc.engine_stats, "service engine", backend, t_all)
    check(svc.request_stats.retries == 0 and svc.request_stats.demotions == 0,
          "service requests retried or demoted")
    check(svc.stats.completed == 6, f"{svc.stats.completed} of 6 completed")
    say(f"[c] PASS {svc.stats.completed} requests + 1 update match the "
        f"direct calls")


def phase_pallas_refused(dev) -> None:
    """(d) an explicit Pallas request raises; nothing demotes to lax."""
    say("[d] backend='pallas' must raise the lowering's error")
    g = load_graph("rmat:8")
    t0 = time.perf_counter()
    try:
        res = engine_jax.count(g, K, devices=[dev], backend="pallas")
    except retry.RECOVERABLE as exc:
        raise SmokeFailure(f"pallas failed as a runtime error: {exc!r}")
    except Exception as exc:  # the lowering's own error is the pass
        first = str(exc).strip().splitlines()[0][:160] if str(exc) else ""
        say(f"  raised {type(exc).__name__}: {first} "
            f"seconds={time.perf_counter() - t0:.3f}")
        say("[d] PASS pallas refused, no demotion")
        return
    raise SmokeFailure(
        f"pallas ran (count={res.count}, backend={res.stats.backend}, "
        f"demotions={res.stats.demotions}); update phase d and the compile "
        f"tests if the kernels now compile")


def phase_four_chips(devs, backend: str, scale: int) -> None:
    """Counts and row streams on 4 chips equal chip 0's, byte for byte."""
    say(f"[4] 1-chip vs 4-chip parity at rmat:{scale}, k={K}")
    spec, g, plan = load_scaled(scale)

    def spread(stats, what):
        tiles = {int(d): int(t) for d, t in stats.device_tiles.items()}
        say(f"  {what} device_tiles={tiles}")
        check(sorted(tiles) == [0, 1, 2, 3] and min(tiles.values()) > 0,
              f"{what}: work did not reach all 4 devices: {tiles}")

    t0 = time.perf_counter()
    one = engine_jax.count(g, K, plan=plan, devices=[devs[0]])
    expect_clean(one.stats, "count on chip 0", backend, t0)
    t0 = time.perf_counter()
    col1 = Collect(K)
    r1 = listing.stream_cliques(plan, K, col1, devices=[devs[0]])
    expect_clean(r1.stats, "list on chip 0", backend, t0)
    n1, d1 = sum(c.shape[0] for c in col1.chunks), col1.digest.hexdigest()
    col1.chunks.clear()
    check(n1 == one.count, f"chip 0 listed {n1}, counted {one.count}")

    t0 = time.perf_counter()
    lpt = engine_jax.count(g, K, plan=plan, devices=list(devs))
    expect_clean(lpt.stats, "count, online LPT over 4 chips", backend, t0)
    spread(lpt.stats, "LPT count")
    check(lpt.count == one.count, f"LPT count {lpt.count} != {one.count}")

    t0 = time.perf_counter()
    mesh = make_local_mesh((4, 1), axes=("data", "model"))
    sm = engine_jax.count(g, K, plan=plan, mesh=mesh)
    expect_clean(sm.stats, "count, shard_map over a 4x1 mesh", backend, t0)
    spread(sm.stats, "shard_map count")
    check(sm.count == one.count, f"shard_map count {sm.count} != {one.count}")

    t0 = time.perf_counter()
    col4 = Collect(K)
    r4 = listing.stream_cliques(plan, K, col4, devices=list(devs))
    expect_clean(r4.stats, "list over 4 chips", backend, t0)
    spread(r4.stats, "4-chip list")
    n4, d4 = sum(c.shape[0] for c in col4.chunks), col4.digest.hexdigest()
    check(n4 == n1 and d4 == d1,
          f"4-chip rows ({n4}, {d4}) != chip-0 rows ({n1}, {d1})")
    say(f"[4] PASS {spec} count={one.count} on 1 chip, LPT and shard_map; "
        f"{n1} rows byte-identical on 1 and 4 chips (blake2b {d1})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 1-chip vs 4-chip parity check")
    ap.add_argument("--scale", type=int, default=14,
                    help="RMAT scale of phase b, c and --four-chips")
    ap.add_argument("--oracle-scale", type=int, default=12,
                    help="RMAT scale of the host-oracle phase a")
    ap.add_argument("--rehearse", action="store_true",
                    help="run on whatever platform JAX has (CPU "
                         "rehearsal); prints no ok line")
    args = ap.parse_args(argv)

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}, "
              f"{len(devs)} device(s)); refusing to run off the chip",
              file=sys.stderr, flush=True)
        return 2
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"chip_smoke: needs {need} devices, JAX found {len(devs)}",
              file=sys.stderr, flush=True)
        return 2

    backend = kops.resolve_backend("auto")
    say(f"device: {platform} {devs[0].device_kind} x{len(devs)}; default "
        f"backend={backend}; compile cache "
        f"{jax.config.jax_compilation_cache_dir}")
    say("times below are smoke timings of one run, not benchmark metrics")
    t_start = time.perf_counter()
    try:
        if args.four_chips:
            phase_four_chips(devs[:4], backend, args.scale)
        else:
            if args.scale < 16:
                say(f"scale cut: phases b and c run at rmat:{args.scale}, "
                    f"not rmat:16, to stay inside the run's time limit")
            phase_oracle(devs[0], backend, args.oracle_scale)
            g, plan, count5 = phase_scale(devs[0], backend, args.scale)
            phase_serve(devs[0], backend, g, plan, count5)
            if platform == "tpu":
                phase_pallas_refused(devs[0])
    except SmokeFailure as exc:
        print(f"chip_smoke: FAIL: {exc}", file=sys.stderr, flush=True)
        return 1
    say(f"all phases passed in {time.perf_counter() - t_start:.3f}s")
    device = {"platform": platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if platform != "tpu":
        say(json.dumps({"rehearsal": True, "device": device}))
        return 0
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    tune.enable_compilation_cache()
    sys.exit(main())
