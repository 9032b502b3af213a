"""Accelerator counting engine: tiles -> packed bitset batches -> kernels.

Pipeline (the TPU-native EBBkC of DESIGN.md section 2):
  1. vectorized tile extraction + capacity-batched packing
     (:mod:`repro.core.pipeline`) under the chosen ordering -- fixed-shape
     (B, T, T/32) uint32 batches stream off the host with bounded memory
     (lockstep SPMD wants tight bins; the truss ordering makes them tight,
     Lemma 4.1);
  2. oversize routing: tiles wider than the largest bin spill to the host
     bitset recursion (counted in ``Stats.spilled_tiles``) instead of
     aborting the query;
  3. early-termination routing (Section 5, vectorized): per-tile plexity is a
     popcount reduction; t<=2 tiles are answered by the closed-form
     2-plex formula (exact int64 Pascal-table arithmetic, branch-free);
  4. everything else goes to the Pallas kernels: MXU matmul base case for
     l==3, bitset DFS for l>=4.

``count_packed`` is the jit-able inner step used by the distributed launcher
(`repro.launch.clique`): tile batches are sharded over the mesh data axes and
the per-device partial counts are psum-reduced.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .engine_np import Stats, count_rec_C, count_rec_T
from .graph import Graph
from . import pipeline
from . import tiles as tiles_mod
from .bitops import pack_rows, pack_mask
from ..kernels import ops as kops
from ..kernels.common import pascal_table, popcount, unpack_bits
from ..tune import search as tune_search

_BINS = pipeline.BINS


def bucket_rows(x: np.ndarray) -> np.ndarray:
    """Zero-pad axis 0 up to the next power of two (batch-shape bucketing).

    Ragged tail chunks of a bin then reuse the pow2-batch executables the
    full chunks already compiled, instead of compiling one executable per
    distinct tail length.  Padding rows have ``cand == 0``, contributing
    exactly 0 to kernel counts, the closed-form 2-plex count, and the
    listing buffers (callers slice the padded rows off before decode).
    """
    B = x.shape[0]
    p = 1
    while p < B:
        p *= 2
    if p == B:
        return x
    return np.concatenate([x, np.zeros((p - B,) + x.shape[1:], x.dtype)])


@dataclasses.dataclass
class PackedTiles:
    """One fixed-shape batch of bitset tiles."""
    A: np.ndarray      # (B, T, W) uint32
    cand: np.ndarray   # (B, W) uint32


def fetched_bytes(arrays) -> int:
    """Bytes a host fetch of ``arrays`` copies from the device (the
    ``Stats.d2h_bytes`` increment); host arrays (a host-rung result)
    cost nothing."""
    return sum(int(x.nbytes) for x in arrays if isinstance(x, jax.Array))


def pack_tiles(tiles: List[tiles_mod.Tile], T: int) -> PackedTiles:
    """Pack ``tiles`` into one fixed-shape ``(B, T, W)`` bitset batch."""
    B = len(tiles)
    W = T // 32
    A = np.zeros((B, T, W), dtype=np.uint32)
    cand = np.zeros((B, W), dtype=np.uint32)
    for i, t in enumerate(tiles):
        A[i] = pack_rows(t.rows, T)
        cand[i] = pack_mask((1 << t.s) - 1, T)
    return PackedTiles(A, cand)


def bin_tiles(g: Graph, k: int, order: str = "hybrid",
              use_rule2: bool = True,
              plan: Optional[pipeline.PipelinePlan] = None,
              spill: Optional[List[tiles_mod.Tile]] = None,
              bins: Sequence[int] = _BINS) -> Dict[int, PackedTiles]:
    """Extract edge tiles and pack them into size bins (materialized).

    Thin compatibility wrapper over :func:`repro.core.pipeline.stream_batches`
    that concatenates the streamed chunks per bin.  Oversize tiles are
    appended to ``spill`` when given, else raise (the pre-pipeline
    behavior); :func:`count` always spills.
    """
    parts: Dict[int, List[pipeline.TileBatch]] = {}
    for item in pipeline.stream_batches(plan or g, k, order=order,
                                        use_rule2=use_rule2, bins=bins):
        if isinstance(item, tiles_mod.Tile):
            if spill is None:
                raise ValueError(
                    f"tile with {item.s} vertices exceeds max bin "
                    f"{max(bins)}; raise bins or spill to host")
            spill.append(item)
            continue
        parts.setdefault(item.T, []).append(item)
    return {T: PackedTiles(np.concatenate([b.A for b in bs]),
                           np.concatenate([b.cand for b in bs]))
            for T, bs in sorted(parts.items())}


# ---------------------------------------------------------------------------
# vectorized early termination (closed-form 2-plex counting)
# ---------------------------------------------------------------------------

def plex_stats(A: jax.Array, cand: jax.Array) -> Tuple[jax.Array, ...]:
    """Per tile: (nv, t, f) = size, plexity, #universal vertices."""
    T = A.shape[1]
    vbit = unpack_bits(cand, T)                       # (B, T)
    deg = popcount(A & cand[:, None, :]).sum(-1)      # (B, T)
    nv = popcount(cand).sum(-1)                       # (B,)
    big = jnp.int32(1 << 30)
    deg_v = jnp.where(vbit > 0, deg.astype(jnp.int32), big)
    mind = jnp.min(deg_v, axis=-1)
    mind = jnp.where(nv > 0, mind, 0)
    t = nv.astype(jnp.int32) - mind
    f = jnp.sum((deg.astype(jnp.int32) == nv[:, None].astype(jnp.int32) - 1)
                & (vbit > 0), axis=-1)
    return nv.astype(jnp.int32), t, f


def count_2plex_closed_np(nv: np.ndarray, f: np.ndarray, l: int) -> np.ndarray:
    """Closed-form Section 5.1 count; exact int64 on host (cheap, O(B*l))."""
    table = pascal_table(int(max(nv.max(initial=0), 1)))
    p = (nv - f) // 2
    total = np.zeros(nv.shape, dtype=np.int64)
    for c in range(0, l + 1):
        j = l - c
        cf = np.where(c <= f, table[f, np.minimum(c, f)], 0)
        cp = np.where(j <= p, table[p, np.minimum(j, p)], 0)
        total += cf * cp * (1 << j)
    return total


# ---------------------------------------------------------------------------
# public engine
# ---------------------------------------------------------------------------

def count_packed(A: jax.Array, cand: jax.Array, l: int,
                 method: str = "auto", et: bool = True,
                 interpret: Optional[bool] = None,
                 backend: Optional[str] = None):
    """Device step over one packed batch.

    Returns (hard (B,) uint32 kernel counts with 2-plex tiles masked to 0,
    nv, t, f) -- the host combines them with the exact int64 closed form.
    All-device, no int64 (TPU-friendly); jit/pjit-able as a unit.
    ``backend`` selects the kernel implementation (see
    :mod:`repro.kernels.ops`); ``interpret`` is the deprecated alias.
    """
    T = A.shape[1]
    B = A.shape[0]
    if l == 0:
        one = jnp.ones(B, dtype=jnp.uint32)
        z = jnp.zeros(B, dtype=jnp.int32)
        return one, z, z, z
    if l == 1:
        n = popcount(cand).sum(-1).astype(jnp.uint32)
        z = jnp.zeros(B, dtype=jnp.int32)
        return n, z, z, z
    if l == 2:
        from ..kernels.ref import edges_within_ref
        n = edges_within_ref(A, cand)
        z = jnp.zeros(B, dtype=jnp.int32)
        return n, z, z, z
    nv, t, f = plex_stats(A, cand)
    if et:
        is2 = t <= 2
        hard = kops.count_tiles(A, jnp.where(is2[:, None], jnp.uint32(0),
                                             cand), l,
                                method=method, backend=backend,
                                interpret=interpret)
    else:
        hard = kops.count_tiles(A, cand, l, method=method, backend=backend,
                                interpret=interpret)
    return hard, nv, t, f


def combine_counts(hard, nv, t, f, l: int, et: bool,
                   stats: Optional[Stats] = None) -> int:
    """Host-exact combination of the device step outputs; with ``stats``,
    the bytes it fetches from the device add to ``stats.d2h_bytes``."""
    if stats is not None:
        stats.d2h_bytes += fetched_bytes(
            (hard,) if not et or l <= 2 else (hard, nv, t, f))
    hard = np.asarray(hard).astype(np.int64)
    if not et or l <= 2:
        return int(hard.sum())
    nv = np.asarray(nv)
    t = np.asarray(t)
    f = np.asarray(f)
    is2 = t <= 2
    closed = count_2plex_closed_np(nv[is2], f[is2], l)
    return int(hard.sum() + closed.sum())


def count_spilled(tile: tiles_mod.Tile, order: str, l: int, stats: Stats,
                  et_t: int, use_rule2: bool) -> int:
    """Host bitset recursion for one oversize tile (mirrors the host path).

    Each spill is recorded once: ``spilled_tiles`` counts it and
    ``spill_sizes`` keeps its width, so host-recursion work stays
    attributable (and schedulable) separately from the device batches --
    the subtree's branch stats accumulate into the same ``stats`` but the
    spill itself is never double-counted across devices.
    """
    stats.spilled_tiles += 1
    stats.spill_sizes.append(tile.s)
    cand = (1 << tile.s) - 1
    if order == "truss":
        return count_rec_T(tile.edges_ranked, cand, tile.s, l, stats,
                           et_t=et_t)
    return count_rec_C(tile.rows, cand, l, stats, colors=tile.colors,
                       et_t=et_t, use_rule2=use_rule2)


def count(g: Graph, k: int, order: str = "hybrid", et_t: int = 3,
          use_rule2: bool = True, method: str = "auto",
          interpret: Optional[bool] = None, et_route: bool = True,
          plan: Optional[pipeline.PipelinePlan] = None,
          batch_size: Optional[int] = None,
          bins: Optional[Sequence[int]] = None,
          stage_times: Optional[Dict[str, float]] = None,
          devices=None, async_staging: bool = True,
          mesh: Optional[jax.sharding.Mesh] = None,
          backend: Optional[str] = None,
          pack_workers: Optional[int] = None,
          prefetch: Optional[int] = None,
          plan_cache: bool = True,
          plan_cache_dir: Optional[str] = None):
    """Full-graph k-clique count on the accelerator engine.

    Streams capacity-batched packed tiles from :mod:`repro.core.pipeline`;
    pass a prebuilt ``plan`` to amortize preprocessing across queries.
    With ``plan=None`` the engine consults the keyed plan cache
    (``pipeline.cached_plan``; disable with ``plan_cache=False``) so a
    repeated query on the same graph skips the O(delta*m) decomposition --
    ``stats.plan_cache_hit`` / ``stats.plan_build_s`` report which path
    ran, and ``plan_cache_dir`` adds an on-disk plan store shared across
    processes.  Packing runs on a parallel producer (``pack_workers``
    worker processes, default auto; ``0`` forces the serial packer) that
    keeps up to ``prefetch`` packed batches ahead of device dispatch.

    Oversize tiles are counted on the host (``stats.spilled_tiles`` /
    ``stats.spill_sizes``).  ``stage_times`` (optional dict) accumulates
    extract/pack/device/combine wall-clock seconds.  ``backend`` selects
    the kernel implementation family (``repro.kernels.ops`` registry;
    default auto = compiled lax off-TPU); the resolved name and first-call
    compile seconds are reported in ``stats.backend`` /
    ``stats.kernel_compile_s``.

    ``devices`` routes the packed batches through the multi-device
    dispatcher (:mod:`repro.runtime.dispatch`): an int n / ``"all"`` / a
    device list shards batches across those devices with per-device jit
    and double-buffered host->device staging (``async_staging=False``
    forces synchronous staging).  ``mesh`` instead shards every batch
    over that mesh in one ``shard_map`` step per batch.  With neither,
    the single-device inline path runs.  Counts are identical every way --
    device partials are combined exactly on the host.

    Geometry knobs left ``None`` (``batch_size``, ``bins``,
    ``pack_workers``, ``prefetch``) resolve through the persistent
    autotuner (:func:`repro.tune.search.resolve_geometry`): explicit
    argument > persisted geometry record > the historical hardcoded
    defaults.  The count is identical under every geometry.
    """
    from .ebbkc import Result
    stats = Stats()
    stats.backend = kops.resolve_backend(backend, interpret)
    if k == 1:
        return Result(g.n, stats)
    if k == 2:
        return Result(g.m, stats)
    if plan is None and plan_cache:
        plan = pipeline.cached_plan(g, order=order,
                                    cache_dir=plan_cache_dir, stats=stats)
    total = 0
    ntiles = 0
    max_tile = 0
    l = k - 2
    et = et_route and et_t >= 2
    geom = tune_search.resolve_geometry(
        "count", l, batch_size=batch_size, bins=bins,
        pack_workers=pack_workers, prefetch=prefetch)
    stream = pipeline.stream_batches(plan or g, k, order=order,
                                     use_rule2=use_rule2,
                                     batch_size=geom.batch_size,
                                     bins=geom.bins,
                                     timings=stage_times,
                                     pack_workers=geom.pack_workers,
                                     prefetch=geom.prefetch, stats=stats)
    if devices is not None or mesh is not None:
        from ..runtime.dispatch import Dispatcher
        disp = Dispatcher(l, devices, mesh=mesh, et=et, method=method,
                          interpret=interpret, backend=backend,
                          async_staging=async_staging,
                          stats=stats, stage_times=stage_times)
        spill_total = 0

        def on_spill(tile: tiles_mod.Tile) -> None:
            nonlocal spill_total
            spill_total += count_spilled(tile, order, l, stats, et_t,
                                         use_rule2)

        try:
            ntiles, max_tile = disp.consume(stream, on_spill=on_spill)
            total = spill_total + disp.finish()
        finally:
            stream.close()  # stops parallel-producer workers on error too
        stats.kernel_compile_s += kops.consume_compile_s()
        kops.drain_tune_events(stats)
        return Result(total, stats, ntiles, max_tile)
    for item in stream:
        if isinstance(item, tiles_mod.Tile):
            ntiles += 1
            max_tile = max(max_tile, item.s)
            total += count_spilled(item, order, l, stats, et_t, use_rule2)
            continue
        ntiles += item.B
        max_tile = max(max_tile, item.T)
        t0 = time.perf_counter()
        # batch-shape bucketing: ragged tail chunks pad to pow2 so they
        # reuse the executables of the full chunks (padding counts 0)
        hard, nv, t, f = count_packed(
            jnp.asarray(bucket_rows(item.A)),
            jnp.asarray(bucket_rows(item.cand)), l,
            method=method, et=et, interpret=interpret, backend=backend)
        if stage_times is not None:
            # async dispatch: block so device time is not billed to combine
            jax.block_until_ready((hard, nv, t, f))
        t1 = time.perf_counter()
        total += combine_counts(hard, nv, t, f, l, et, stats=stats)
        if stage_times is not None:
            stage_times["device"] = stage_times.get("device", 0.) + t1 - t0
            stage_times["combine"] = stage_times.get("combine", 0.) \
                + time.perf_counter() - t1
    stats.kernel_compile_s += kops.consume_compile_s()
    kops.drain_tune_events(stats)
    return Result(total, stats, ntiles, max_tile)
