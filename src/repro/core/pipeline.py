"""Unified streaming tile pipeline: CSR -> tau-bounded tiles -> packed batches.

This is the shared front-end for every EBBkC consumer (DESIGN.md section 2).
The paper's top-level edge branching produces one tau-bounded tile per edge
(Lemma 4.1); producing those tiles is the only data-dependent part of the
whole dataflow, so it must be vectorized end to end:

1. **Membership table** (:func:`TileTable` builders): one bulk ragged CSR
   expansion enumerates, for every edge at once, the common neighbors that
   survive the ordering filter (pi_tau rank for truss/hybrid, color-DAG
   position for color mode).  The table is *k-independent*: a query for any
   k only thresholds tile sizes (and color rules), so a
   :class:`PipelinePlan` amortizes all preprocessing across repeated
   queries on the same graph (the serving scenario).
2. **Capacity-based streaming batcher** (:func:`stream_batches`): tiles are
   routed to power-of-two size bins and packed ``batch_size`` at a time
   into fixed-shape ``(B, T, W)`` uint32 bitset batches -- host memory is
   bounded by one in-flight chunk per bin instead of the whole graph.
   Packing is vectorized: pairwise adjacency via one ``searchsorted`` over
   canonical edge keys, bit packing via ``np.packbits`` straight into the
   uint32 word layout the kernels consume.  Tiles wider than the largest
   bin are yielded as plain :class:`~repro.core.tiles.Tile` objects so the
   engine can spill them to the host recursion instead of aborting.
3. **Scheduler metadata**: every :class:`TileBatch` carries per-tile
   ``sizes``/``nedges`` arrays -- exactly the cost model inputs
   :func:`repro.runtime.clique_scheduler.schedule_tiles` consumes, so
   device bins map one-to-one onto packed batches.

The pure-Python extractor in :mod:`repro.core.tiles` is kept as the
reference oracle; parity tests assert byte-identical packed batches.
"""
from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import hashlib
import itertools
import os
import threading
import time
from typing import Deque, Dict, Iterator, List, Optional, Sequence, Tuple, \
    Union

import numpy as np

from . import packing
from .graph import Graph, greedy_coloring, color_vertex_order, ragged_expand
from .tiles import Tile
from .truss import TrussDecomposition, truss_decomposition
from ..obs import trace
from ..resilience import inject
from ..resilience import retry as fault_retry

#: power-of-two tile-size bins; tiles wider than the last bin spill to host
BINS = (32, 64, 128, 256)


def _group_offsets(E: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Segment boundaries of a value-sorted owner array.

    Returns (offsets (nt+1,), first index of each segment) -- the ragged
    tile layout shared by both membership-table builders.
    """
    if E.size:
        starts = np.concatenate(
            [[0], np.nonzero(np.diff(E) != 0)[0] + 1]).astype(np.int64)
        offsets = np.concatenate([starts, [E.size]]).astype(np.int64)
        return offsets, starts
    return np.zeros(1, dtype=np.int64), np.zeros(0, dtype=np.int64)


# ---------------------------------------------------------------------------
# k-independent membership tables
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TileTable:
    """Per-edge candidate-tile membership under one ordering family.

    ``family`` is "truss" (shared by truss and hybrid modes: members are the
    common neighbors reachable via edges ranked after e in pi_tau) or
    "color" (members are common out-neighbors in the color DAG).  Everything
    here is independent of k; :meth:`select` applies the k-dependent
    filters.
    """
    family: str
    edge_id: np.ndarray           # (nt,) source edge id per candidate tile
    anchors: np.ndarray           # (nt, 2) anchor vertices (S of Eq. 2)
    offsets: np.ndarray           # (nt+1,) ragged offsets into ``verts``
    verts: np.ndarray             # flat member vertices, canonical inner order
    thresh: np.ndarray            # (nt,) truss: rank(e); color: 0
    ekeys: np.ndarray             # sorted canonical edge keys (adjacency test)
    erank: Optional[np.ndarray]   # truss: pi_tau rank per edge id
    member_colors: Optional[np.ndarray] = None  # color: flat member colors
    ncolors: Optional[np.ndarray] = None        # color: distinct per tile
    rule1: Optional[np.ndarray] = None          # color: (nt,2) endpoint colors

    @property
    def ntiles(self) -> int:
        """Number of tiles in the table."""
        return int(self.edge_id.shape[0])

    def sizes(self) -> np.ndarray:
        """Per-tile candidate counts (``offsets`` diffs)."""
        return np.diff(self.offsets)

    def select(self, k: int, use_rule2: bool = True) -> np.ndarray:
        """Candidate tile ids surviving the k filters, canonical order."""
        keep = self.sizes() >= max(k - 2, 1)
        if self.family == "color":
            keep &= (self.rule1[:, 0] >= k) & (self.rule1[:, 1] >= k - 1)
            if use_rule2:
                keep &= self.ncolors >= k - 2
        return np.nonzero(keep)[0]


def _build_truss_table(g: Graph, td: TrussDecomposition,
                       eids: Optional[np.ndarray] = None) -> TileTable:
    """Truss-family membership table; ``eids`` restricts to a sorted
    subset of owner edges (the localized rebuild :mod:`repro.delta`
    splices into a repaired plan -- cost bounded by those edges'
    neighborhoods instead of m)."""
    ek = g.edge_keys()
    m = g.m
    sub = np.arange(m, dtype=np.int64) if eids is None \
        else np.asarray(eids, dtype=np.int64)
    if m == 0 or sub.size == 0:
        z = np.zeros(0, dtype=np.int64)
        return TileTable("truss", z, np.zeros((0, 2), np.int64),
                         np.zeros(1, np.int64), z, z, ek, td.rank)
    deg = np.diff(g.indptr)
    u, v = g.edges[sub, 0], g.edges[sub, 1]
    swap = deg[u] > deg[v]
    a = np.where(swap, v, u)
    b = np.where(swap, u, v)
    r_e = td.rank
    owner, pos = ragged_expand(deg[a])
    idx = g.indptr[a][owner] + pos
    w = g.indices[idx]
    own_e = sub[owner]
    # pi_tau rank of the CSR edge (a, w) at each expanded slot: one bulk
    # 2m-key probe when building the whole table, per-slot probes (cost
    # bounded by the subset's neighborhoods) for a localized rebuild
    if eids is None:
        src = np.repeat(np.arange(g.n, dtype=np.int64), deg)
        rank_aw = td.rank[g.edge_ids(src, g.indices)][idx]
    else:
        rank_aw = r_e[g.edge_ids(a[owner], w)]
    keep = (rank_aw > r_e[own_e]) & (w != b[owner])
    own_e, w, bb = own_e[keep], w[keep], b[owner][keep]
    hit, p = packing.edge_lookup(ek, m, g.n, np.minimum(bb, w), np.maximum(bb, w))
    hit &= r_e[p] > r_e[own_e]
    E, W = own_e[hit], w[hit]
    # canonical order: reverse pi_tau over tiles, ascending vertex id inside
    order = np.lexsort((W, -r_e[E]))
    E, W = E[order], W[order]
    offsets, starts = _group_offsets(E)
    tile_edge = E[starts]
    return TileTable("truss", tile_edge, g.edges[tile_edge],
                     offsets, W, r_e[tile_edge], ek, td.rank)


def _build_color_table(g: Graph, colors: np.ndarray) -> TileTable:
    ek = g.edge_keys()
    m = g.m
    if m == 0:
        z = np.zeros(0, dtype=np.int64)
        return TileTable("color", z, np.zeros((0, 2), np.int64),
                         np.zeros(1, np.int64), z, z, ek, None,
                         member_colors=z, ncolors=z,
                         rule1=np.zeros((0, 2), np.int64))
    vorder = color_vertex_order(colors)
    vid = np.empty(g.n, dtype=np.int64)
    vid[vorder] = np.arange(g.n)
    u0, v0 = g.edges[:, 0], g.edges[:, 1]
    swapc = vid[u0] > vid[v0]
    ulo = np.where(swapc, v0, u0)
    vhi = np.where(swapc, u0, v0)
    deg = np.diff(g.indptr)
    a = np.where(deg[ulo] <= deg[vhi], ulo, vhi)
    b = np.where(deg[ulo] <= deg[vhi], vhi, ulo)
    owner, pos = ragged_expand(deg[a])
    idx = g.indptr[a][owner] + pos
    w = g.indices[idx]
    # member iff vid[w] beyond both endpoints (DAG out-neighbor of each)
    keep = (vid[w] > vid[vhi][owner]) & (w != b[owner])
    owner, w = owner[keep], w[keep]
    bb = b[owner]
    hit, _ = packing.edge_lookup(ek, m, g.n, np.minimum(bb, w), np.maximum(bb, w))
    E, W = owner[hit], w[hit]
    # canonical order: edge id ascending, members by color-DAG position
    order = np.lexsort((vid[W], E))
    E, W = E[order], W[order]
    offsets, starts = _group_offsets(E)
    tile_edge = E[starts]
    mcol = colors[W]
    nt = tile_edge.size
    sizes = np.diff(offsets)
    tid_rep, _ = ragged_expand(sizes)
    if E.size:
        o2 = np.lexsort((mcol, tid_rep))
        c2, t2 = mcol[o2], tid_rep[o2]
        new = np.concatenate([[True], (t2[1:] != t2[:-1]) |
                              (c2[1:] != c2[:-1])])
        ncolors = np.bincount(t2[new], minlength=nt)
    else:
        ncolors = np.zeros(0, dtype=np.int64)
    rule1 = np.stack([colors[ulo[tile_edge]], colors[vhi[tile_edge]]], axis=1)
    return TileTable("color", tile_edge,
                     np.stack([ulo[tile_edge], vhi[tile_edge]], axis=1),
                     offsets, W, np.zeros(nt, dtype=np.int64), ek, None,
                     member_colors=mcol, ncolors=ncolors, rule1=rule1)


# ---------------------------------------------------------------------------
# PipelinePlan: cached preprocessing for repeated queries on one graph
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PipelinePlan:
    """Per-graph preprocessing cache (truss order, coloring, tables).

    Build once, query many times: ``stream_batches(plan, k)`` for any k
    reuses the decomposition and the membership table, so a serving process
    pays preprocessing once per graph snapshot.
    """
    g: Graph
    _td: Optional[TrussDecomposition] = None
    _colors: Optional[np.ndarray] = None
    _tables: Dict[str, TileTable] = dataclasses.field(default_factory=dict)

    @property
    def td(self) -> TrussDecomposition:
        """The graph's truss decomposition (computed lazily, cached)."""
        if self._td is None:
            self._td = truss_decomposition(self.g)
        return self._td

    @property
    def colors(self) -> np.ndarray:
        """Greedy vertex coloring (computed lazily, cached)."""
        if self._colors is None:
            self._colors, _ = greedy_coloring(self.g)
        return self._colors

    def table(self, mode: str) -> TileTable:
        """The (lazily built, cached) tile table for ``mode``'s family."""
        family = "color" if mode == "color" else "truss"
        if family not in self._tables:
            if family == "truss":
                self._tables[family] = _build_truss_table(self.g, self.td)
            else:
                self._tables[family] = _build_color_table(self.g, self.colors)
        return self._tables[family]


def build_plan(g: Graph, order: str = "hybrid") -> PipelinePlan:
    """Eagerly preprocess ``g`` for ``order`` (truss/hybrid/color)."""
    if order not in ("truss", "hybrid", "color"):
        raise ValueError(f"unknown edge-tile mode: {order}")
    plan = PipelinePlan(g=g)
    plan.table(order)
    return plan


def _as_plan(source: Union[Graph, PipelinePlan]) -> PipelinePlan:
    return source if isinstance(source, PipelinePlan) else PipelinePlan(source)


# ---------------------------------------------------------------------------
# plan persistence + keyed in-process cache (DESIGN.md section 8)
# ---------------------------------------------------------------------------

#: serialized-plan layout version; bump on any TileTable schema change so a
#: stale on-disk plan is rebuilt instead of misread
PLAN_FORMAT = 1

#: in-process plan cache capacity (plans, LRU-evicted); a plan holds the
#: graph plus O(sum tile sizes) table arrays, so keep the window small
PLAN_CACHE_CAPACITY = 8

#: canonicalization contract baked into every plan key: two graphs share
#: a key only when their *canonical* forms (self-loops dropped, edges
#: dedup'd and lexsorted u < v) match under the same contract version --
#: a future change to ``graph.from_edges`` canonicalization must bump
#: this tag so stale plans re-key instead of aliasing
PLAN_CANON = "dedup-lexsorted-v1"

_PLAN_CACHE: "collections.OrderedDict[str, PipelinePlan]" = \
    collections.OrderedDict()
_PLAN_CACHE_LOCK = threading.Lock()
# per-key single-flight build latches (cached_plan): key -> Event set
# when the winning builder has published (or abandoned) its plan
_PLAN_BUILDS: Dict[str, threading.Event] = {}


def plan_key(g: Graph, order: str = "hybrid") -> str:
    """Content-addressed cache key over the *whole* graph identity.

    Hashes the vertex count, edge count, canonicalization contract
    (:data:`PLAN_CANON`), ordering family, and the canonical edge list.
    ``n`` matters even with identical edges: edge keys are ``u * n + v``,
    so a plan built for a smaller vertex set mis-probes adjacency on a
    graph with trailing isolated vertices (the aliasing regression in
    ``test_pipeline.py``).  Truss and hybrid modes share one key (both
    consume the "truss" membership table); color mode keys separately.
    O(m) to compute -- negligible next to the O(delta*m) decomposition it
    lets a warm query skip.
    """
    family = "color" if order == "color" else "truss"
    h = hashlib.sha256()
    h.update(
        f"plan-v{PLAN_FORMAT}:{PLAN_CANON}:{family}:{g.n}:{g.m}:".encode())
    h.update(np.ascontiguousarray(g.edges).tobytes())
    return h.hexdigest()[:24]


def save_plan(plan: PipelinePlan, directory: str,
              lineage: Optional[Dict] = None) -> str:
    """Persist a plan's built structures via :mod:`repro.checkpoint.store`.

    Saves the graph plus whatever is already built (truss decomposition,
    coloring, membership tables) -- load never recomputes what was saved.
    Atomic like every checkpoint (tmp dir + os.replace + COMMITTED).
    ``lineage`` is an optional JSON dict recording how the plan came to be
    (graph version, parent plan key, repair-vs-rebuild decision -- written
    by :class:`repro.delta.PlanIndex`); it rides in the metadata and is
    readable without deserializing arrays via
    :func:`repro.checkpoint.store.read_metadata`.
    """
    from ..checkpoint import store

    tree: Dict[str, object] = {
        "graph": {"n": np.asarray(plan.g.n, np.int64),
                  "edges": plan.g.edges, "indptr": plan.g.indptr,
                  "indices": plan.g.indices}}
    if plan._td is not None:
        td = plan._td
        tree["truss_dec"] = {
            "order": td.order, "rank": td.rank, "support0": td.support0,
            "peel_support": td.peel_support, "trussness": td.trussness,
            "tau": np.asarray(td.tau, np.int64)}
    if plan._colors is not None:
        tree["colors"] = plan._colors
    tables: Dict[str, Dict[str, np.ndarray]] = {}
    for family, tb in plan._tables.items():
        d = {"edge_id": tb.edge_id, "anchors": tb.anchors,
             "offsets": tb.offsets, "verts": tb.verts,
             "thresh": tb.thresh, "ekeys": tb.ekeys}
        for opt in ("erank", "member_colors", "ncolors", "rule1"):
            val = getattr(tb, opt)
            if val is not None:
                d[opt] = val
        tables[family] = d
    if tables:
        tree["tables"] = tables
    metadata: Dict[str, object] = {
        "format": PLAN_FORMAT, "families": sorted(plan._tables)}
    if lineage is not None:
        metadata["lineage"] = lineage
    return store.save_checkpoint(directory, 0, tree, metadata=metadata)


def load_plan(directory: str) -> Optional[PipelinePlan]:
    """Restore a :func:`save_plan` plan; None if absent/stale-format.

    Corrupt or truncated stores (failed length+digest check, unreadable
    npz/meta, or a tree that no longer parses) also read as absent: the
    bad step is quarantined -- moved aside under ``<dir>/quarantine/``
    with a warning log -- so the caller rebuilds and re-saves instead of
    propagating a deserialization traceback (the same fall-back-to-absent
    contract as ``tune.records``).
    """
    from ..checkpoint import store

    got = store.restore_checkpoint_safe(directory, _corrupt_site="plan.load")
    if got is None or got["metadata"].get("format") != PLAN_FORMAT:
        return None
    try:
        flat = got["tree"]
        g = Graph(n=int(flat["graph/n"]), edges=flat["graph/edges"],
                  indptr=flat["graph/indptr"], indices=flat["graph/indices"])
        plan = PipelinePlan(g=g)
        if "truss_dec/rank" in flat:
            plan._td = TrussDecomposition(
                order=flat["truss_dec/order"], rank=flat["truss_dec/rank"],
                support0=flat["truss_dec/support0"],
                peel_support=flat["truss_dec/peel_support"],
                trussness=flat["truss_dec/trussness"],
                tau=int(flat["truss_dec/tau"]))
        if "colors" in flat:
            plan._colors = flat["colors"]
        for family in got["metadata"].get("families", []):
            p = f"tables/{family}/"
            plan._tables[family] = TileTable(
                family, flat[p + "edge_id"], flat[p + "anchors"],
                flat[p + "offsets"], flat[p + "verts"], flat[p + "thresh"],
                flat[p + "ekeys"], flat.get(p + "erank"),
                member_colors=flat.get(p + "member_colors"),
                ncolors=flat.get(p + "ncolors"), rule1=flat.get(p + "rule1"))
    except Exception as exc:
        store.quarantine(directory, reason=f"plan parse failed: {exc!r}")
        return None
    return plan


def _plan_cache_insert(key: str, plan: PipelinePlan) -> None:
    with _PLAN_CACHE_LOCK:
        _PLAN_CACHE[key] = plan
        _PLAN_CACHE.move_to_end(key)
        while len(_PLAN_CACHE) > PLAN_CACHE_CAPACITY:
            _PLAN_CACHE.popitem(last=False)


def clear_plan_cache() -> None:
    """Drop every in-process cached plan (tests / memory pressure)."""
    with _PLAN_CACHE_LOCK:
        _PLAN_CACHE.clear()


def cached_plan(g: Graph, order: str = "hybrid", *,
                cache_dir: Optional[str] = None,
                stats=None) -> PipelinePlan:
    """Plan for ``g``/``order`` off the keyed cache; build only on miss.

    Lookup order: in-process LRU (keyed by :func:`plan_key`) -> on-disk
    plan store under ``cache_dir`` (persisted across processes via
    :func:`save_plan`) -> build (and save when ``cache_dir`` is given).
    A warm hit skips the O(delta*m) truss/coloring preprocessing entirely;
    ``stats`` (a :class:`~repro.core.engine_np.Stats`) records
    ``plan_cache_hit`` and the cold-path ``plan_build_s``.

    Thread-safe with per-key single-flight building: concurrent misses on
    one key elect exactly one builder; the losers block on its latch and
    then take the published plan as a cache hit (``plan_cache_hit=True``,
    no ``plan_build_s``), so the O(delta*m) build runs once no matter how
    many threads race a cold key.  If the builder dies, a blocked loser
    takes over.  Plans themselves are read-only after their table is
    built.
    """
    if order not in ("truss", "hybrid", "color"):
        raise ValueError(f"unknown edge-tile mode: {order}")
    key = plan_key(g, order)
    family = "color" if order == "color" else "truss"
    while True:
        latch = None
        with _PLAN_CACHE_LOCK:
            plan = _PLAN_CACHE.get(key)
            if plan is not None and family in plan._tables:
                _PLAN_CACHE.move_to_end(key)
            else:
                plan = None
                latch = _PLAN_BUILDS.get(key)
                if latch is None:
                    # no builder in flight: this thread becomes it
                    _PLAN_BUILDS[key] = threading.Event()
        if plan is not None:
            if stats is not None:
                stats.plan_cache_hit = True
            trace.instant("plan/cache_hit", source="memory", order=order)
            return plan
        if latch is None:
            break
        # single-flight: another thread owns the build; wait for its
        # latch, then loop to take the published plan as a hit (or, if
        # the builder failed without publishing, become the builder)
        with trace.span("plan/build_wait", order=order):
            latch.wait()
    try:
        if cache_dir is not None:
            with trace.span("plan/load", order=order):
                try:
                    inject.fire("plan.load")
                    plan = load_plan(os.path.join(cache_dir, key))
                except inject.FaultInjected:
                    plan = None  # injected load fault -> a cache miss
            if plan is not None and family in plan._tables:
                if stats is not None:
                    stats.plan_cache_hit = True
                trace.instant("plan/cache_hit", source="disk", order=order)
                _plan_cache_insert(key, plan)
                return plan
        t0 = time.perf_counter()
        with trace.span("plan/build", order=order, n=g.n, m=g.m):
            plan = build_plan(g, order=order)
        if stats is not None:
            stats.plan_build_s += time.perf_counter() - t0
        if cache_dir is not None:
            save_plan(plan, os.path.join(cache_dir, key))
        _plan_cache_insert(key, plan)
        return plan
    finally:
        with _PLAN_CACHE_LOCK:
            latch = _PLAN_BUILDS.pop(key, None)
        if latch is not None:
            latch.set()


# ---------------------------------------------------------------------------
# vectorized chunk packing
# ---------------------------------------------------------------------------

def _gather(table: TileTable, ids: np.ndarray, T: int):
    """A chunk's self-contained packing input: members ``V`` (B, T) padded
    with zeros, sizes ``sz`` and, for the truss family, the tiles'
    thresholds (None for the color family)."""
    ids = np.asarray(ids, dtype=np.int64)
    sz = (table.offsets[ids + 1] - table.offsets[ids]).astype(np.int64)
    owner, pos = ragged_expand(sz)
    V = np.zeros((ids.size, T), dtype=np.int64)
    V[owner, pos] = table.verts[table.offsets[ids][owner] + pos]
    thresh = table.thresh[ids] if table.family == "truss" else None
    return V, sz, thresh


def _edge_index(g: Graph, table: TileTable) -> packing.EdgeIndex:
    return packing.EdgeIndex(
        table.ekeys, table.erank if table.family == "truss" else None,
        g.n, g.m)


def _chunk_dense(g: Graph, table: TileTable, ids: np.ndarray, T: int):
    """Dense bool adjacency for one chunk of candidate tiles.

    Returns (D (B,T,T) bool, V (B,T) padded member ids, sizes, nedges,
    pairs) with ``pairs = (tile, i, j, pair_rank)`` for i<j adjacent pairs
    (pair_rank is the pi_tau rank of the pair edge for the truss family).
    """
    V, sz, thresh = _gather(table, ids, T)
    D, nedges, pairs = packing.dense_pairs(V, sz, thresh,
                                           _edge_index(g, table))
    return D, V, sz, nedges, pairs


@dataclasses.dataclass
class TileBatch:
    """One fixed-shape packed batch plus per-tile scheduler metadata.

    ``verts`` is the decode table of the emission subsystem
    (:mod:`repro.core.listing`): local slot i of tile b is global vertex
    ``verts[b, i]`` (post-relabel for hybrid mode; slots >= ``sizes[b]``
    are padding).  Together with ``anchors`` it is everything needed to
    translate kernel-emitted local clique ids back to global ids.
    """
    T: int
    A: np.ndarray        # (B, T, W) uint32 adjacency bitsets
    cand: np.ndarray     # (B, W) uint32 candidate masks
    sizes: np.ndarray    # (B,) int32 member counts
    nedges: np.ndarray   # (B,) int32 tile edge counts (cost-model input)
    anchors: np.ndarray  # (B, 2) int64 anchor vertices
    verts: np.ndarray    # (B, T) int64 local slot -> global vertex id
    index: int = -1      # position in the job's stream (the spans' batch id)

    @property
    def B(self) -> int:
        """Batch size: number of packed tiles (rows) in this batch."""
        return int(self.A.shape[0])


def _tile_batch(T: int, table: TileTable, ids: np.ndarray, sz: np.ndarray,
                packed: Tuple, index: int) -> TileBatch:
    A, cand, V, nedges = packed
    return TileBatch(T, A, cand, sz.astype(np.int32),
                     nedges.astype(np.int32), table.anchors[ids].copy(), V,
                     index)


def _pack_batch(g: Graph, table: TileTable, ids: np.ndarray, T: int,
                mode: str, index: int = -1) -> TileBatch:
    V, sz, thresh = _gather(table, ids, T)
    packed = packing.pack_dense(V, sz, thresh, _edge_index(g, table),
                                mode == "hybrid")
    return _tile_batch(T, table, ids, sz, packed, index)


def _tiles_from_ids(g: Graph, table: TileTable, ids: np.ndarray,
                    mode: str) -> Iterator[Tile]:
    """Materialize reference-identical :class:`Tile` objects for ``ids``."""
    ids = np.asarray(ids, dtype=np.int64)
    chunk = 512
    for c0 in range(0, ids.size, chunk):
        sub = ids[c0:c0 + chunk]
        sz = (table.offsets[sub + 1] - table.offsets[sub]).astype(np.int64)
        T = max(8, int(-(-int(sz.max(initial=1)) // 8) * 8))
        D, V, _, nedges, (po, pi, pj, pr) = _chunk_dense(g, table, sub, T)
        colors_out: Optional[np.ndarray] = None
        if mode == "hybrid":
            colors, perm = packing.greedy_color(D, sz)
            D, V, colors_out = packing.relabel(D, V, colors, perm)
        elif mode == "color":
            mowner, mpos = ragged_expand(sz)
            colors_out = np.zeros((sub.size, T), dtype=np.int64)
            colors_out[mowner, mpos] = table.member_colors[
                table.offsets[sub][mowner] + mpos]
        edges_ranked: Optional[List[List[Tuple[int, int]]]] = None
        if mode == "truss":
            o = np.lexsort((pr, po))
            po_s, pi_s, pj_s = po[o], pi[o], pj[o]
            bounds = np.concatenate(
                [[0], np.cumsum(np.bincount(po_s, minlength=sub.size))])
            edges_ranked = [
                list(zip(pi_s[bounds[b]:bounds[b + 1]].tolist(),
                         pj_s[bounds[b]:bounds[b + 1]].tolist()))
                for b in range(sub.size)]
        row_bytes = np.packbits(D, axis=-1, bitorder="little")
        for b in range(sub.size):
            s = int(sz[b])
            rows = [int.from_bytes(row_bytes[b, r].tobytes(), "little")
                    for r in range(s)]
            anchor = (int(table.anchors[sub[b], 0]),
                      int(table.anchors[sub[b], 1]))
            verts = V[b, :s].copy()
            if mode == "truss":
                yield Tile(anchor, verts, rows, int(nedges[b]),
                           edges_ranked=edges_ranked[b])
            else:
                yield Tile(anchor, verts, rows, int(nedges[b]),
                           colors=[int(c) for c in colors_out[b, :s]])


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def iter_tiles(source: Union[Graph, PipelinePlan], k: int,
               mode: str = "hybrid", use_rule2: bool = True
               ) -> Iterator[Tile]:
    """Vectorized replacement for :func:`repro.core.tiles.edge_tiles`.

    Yields tiles identical (same order, members, rows, colors/ranks) to the
    Python reference extractor, built from the plan's membership table.
    """
    if mode not in ("truss", "hybrid", "color"):
        raise ValueError(f"unknown edge-tile mode: {mode}")
    plan = _as_plan(source)
    table = plan.table(mode)
    ids = table.select(k, use_rule2=use_rule2)
    yield from _tiles_from_ids(plan.g, table, ids, mode)


def stream_batches(source: Union[Graph, PipelinePlan], k: int,
                   order: str = "hybrid", use_rule2: bool = True,
                   batch_size: Optional[int] = None,
                   bins: Optional[Sequence[int]] = None,
                   timings: Optional[Dict[str, float]] = None,
                   pack_workers: Optional[int] = 0,
                   prefetch: Optional[int] = None,
                   stats=None) -> Iterator[Union[TileBatch, Tile]]:
    """Stream fixed-shape packed batches (plus oversize spill tiles).

    Tiles are routed to the smallest bin T >= size and packed
    ``batch_size`` at a time, so peak host memory is one chunk per bin.
    Tiles wider than ``bins[-1]`` are yielded as :class:`Tile` objects for
    the caller to spill to the host recursion.  When ``timings`` is given,
    "extract" (table build + select) and "pack" seconds are accumulated
    into it, and with a pack pool "pack_wait": the consumer's seconds
    blocked on the pool (its ``pack/wait`` spans).  Each packed batch
    carries its position in the stream as ``TileBatch.index``, the
    ``batch`` id of its spans here and in the dispatchers.

    ``pack_workers`` turns the serial packer into a producer/consumer
    pipeline: that many worker processes of the interpreter's
    :class:`~repro.core.packing.PackPool` (which outlives the stream) pack
    up to ``prefetch`` chunks ahead of the consumer (default ``2 *
    workers``), so host packing of batch i+N runs outside this interpreter
    while the consumer does whatever it does with batch i (device
    dispatch, in the engines).  ``0`` = pack inline (the serial reference
    behavior); ``None`` = :func:`~repro.core.packing.default_proc_workers`.
    The yielded sequence is **identical** in content and order every way
    -- work items are submitted and harvested strictly FIFO -- and peak
    host memory grows only by the prefetch window.  With ``stats`` given
    (a :class:`~repro.core.engine_np.Stats`), ``pack_workers``,
    ``frontend_s`` (extract + pack seconds; gather plus worker seconds
    when parallel), ``packed_batches``, ``pack_proc_batches`` and the
    prefetch-queue occupancy fields are recorded.
    """
    if order not in ("truss", "hybrid", "color"):
        raise ValueError(f"unknown edge-tile mode: {order}")
    # None = the historical defaults; the engines resolve tuned geometry
    # (repro.tune.search.resolve_geometry) before calling in, so this
    # module stays tuner-agnostic
    if batch_size is None:
        batch_size = 256
    bins = tuple(sorted(int(b) for b in (BINS if bins is None else bins)))
    if any(b % 32 for b in bins):
        raise ValueError("bins must be multiples of 32")
    plan = _as_plan(source)
    t0 = time.perf_counter()
    with trace.span("extract", order=order, k=k) as _sp:
        fault_retry.consume("extract")  # pure stage: retry-in-place
        table = plan.table(order)
        ids = table.select(k, use_rule2=use_rule2)
        sizes = (table.offsets[ids + 1] - table.offsets[ids]).astype(np.int64)
        binidx = np.searchsorted(np.asarray(bins), sizes)
        _sp.set(tiles=int(ids.size))
    extract_s = time.perf_counter() - t0
    if timings is not None:
        timings["extract"] = timings.get("extract", 0.0) + extract_s
    if stats is not None:
        stats.frontend_s += extract_s
    for tid in ids[binidx == len(bins)]:
        yield from _tiles_from_ids(plan.g, table, np.asarray([tid]), order)

    def bill_pack(dt: float) -> None:
        if timings is not None:
            timings["pack"] = timings.get("pack", 0.0) + dt
        if stats is not None:
            stats.frontend_s += dt

    # the work list (bin, chunk) is cheap to materialize -- only index
    # arrays -- and fixes the deterministic yield order up front
    work: List[Tuple[int, np.ndarray]] = []
    for bi, T in enumerate(bins):
        sel = ids[binidx == bi]
        for c0 in range(0, sel.size, batch_size):
            work.append((T, sel[c0:c0 + batch_size]))
    workers = packing.default_proc_workers() if pack_workers is None \
        else max(0, int(pack_workers))
    serial = workers == 0 or len(work) <= 1
    if stats is not None:
        # report what actually ran: the <=1-work-item fallback is serial
        stats.pack_workers = 0 if serial else workers
    if serial:
        for i, (T, chunk) in enumerate(work):
            fault_retry.consume("pack")  # pure stage: retry-in-place
            t1 = time.perf_counter()
            with trace.span("pack", batch=i, T=T, tiles=len(chunk)):
                batch = _pack_batch(plan.g, table, chunk, T, order, i)
            bill_pack(time.perf_counter() - t1)
            if stats is not None:
                stats.packed_batches += 1
            yield batch
        return

    pool = packing.pool(workers)
    index = _edge_index(plan.g, table)

    def pack_job(i: int, T: int, chunk: np.ndarray
                 ) -> Tuple[TileBatch, float]:
        # on a driving thread, which gathers a self-contained work item
        # (the worker holds only the edge index) and waits on a worker
        t1 = time.perf_counter()
        with trace.span("pack", batch=i, T=T, tiles=len(chunk)):
            V, sz, thresh = _gather(table, chunk, T)
            gather_s = time.perf_counter() - t1
            *packed, worker_s = pool.pack(index, V, sz, thresh,
                                          order == "hybrid")
        return _tile_batch(T, table, chunk, sz, packed, i), \
            gather_s + worker_s

    ex = concurrent.futures.ThreadPoolExecutor(
        max_workers=workers, thread_name_prefix="pack")

    def submit(i: int, T: int, chunk: np.ndarray):
        # the fault schedule's call counter stays in this thread, so a
        # plan fires on the same calls as the inline path's
        fault_retry.consume("pack")
        return i, ex.submit(pack_job, i, T, chunk)

    depth = max(2, 2 * workers) if prefetch is None else max(1, int(prefetch))
    occ_sum, occ_n, occ_peak = 0.0, 0, 0
    if timings is not None:
        timings.setdefault("pack_wait", 0.0)
    futs: Deque = collections.deque()
    try:
        it = enumerate(work)
        futs.extend(submit(i, T, chunk)
                    for i, (T, chunk) in itertools.islice(it, depth))
        while futs:
            occ_peak = max(occ_peak, len(futs))
            occ_sum += len(futs) / depth
            occ_n += 1
            i, fut = futs.popleft()
            if fut.done():
                batch, dt = fut.result()
            else:
                t1 = time.perf_counter()
                with trace.span("pack/wait", batch=i, depth=len(futs) + 1):
                    batch, dt = fut.result()
                if timings is not None:
                    timings["pack_wait"] += time.perf_counter() - t1
            nxt = next(it, None)
            if nxt is not None:
                futs.append(submit(nxt[0], *nxt[1]))
            bill_pack(dt)
            if stats is not None:
                stats.packed_batches += 1
                stats.pack_proc_batches += 1
            yield batch
    finally:
        # the pool outlives the stream; its queued work does not
        ex.shutdown(wait=False, cancel_futures=True)
        if stats is not None and occ_n:
            stats.pack_queue_occupancy = occ_sum / occ_n
            stats.pack_queue_peak = occ_peak
