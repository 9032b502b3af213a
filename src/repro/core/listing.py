"""Clique emission subsystem: device listing kernels -> global ids -> sinks.

The counting engine (:mod:`repro.core.engine_jax`) reduces every tile to a
scalar; this module is its *output* twin (DESIGN.md section 6).  The same
streaming tile pipeline feeds the Pallas listing kernel family
(:mod:`repro.kernels.clique_list`), which materializes each completed
l-clique's local vertex ids into a fixed-capacity per-tile buffer.  Decode
turns tile-local ids through the batch's ``verts`` membership table back
to global vertex ids, puts the anchors in front and sorts each row; the
rows then stream into a pluggable :class:`CliqueSink`.  A batch sized by
the count pass decodes on the device (:func:`decode_on_device`: compact
sorted rows come back instead of the padded buffer); every other batch
decodes on the host (:func:`decode_batch`).

Exactness invariants:

* **exact-once** -- each k-clique is produced by exactly one anchor edge
  (the paper's Eq. 2 attribution), so no de-duplication is ever needed;
* **never truncated** -- emit buffers are sized by a first count pass
  (rounded to a power of two to bound jit recompiles, capped at
  ``max_capacity``); a tile whose true count exceeds its buffer raises the
  kernel's overflow flag and is re-listed by the host bitset recursion
  (``Stats.overflowed_tiles``), exactly like oversize tiles spill
  (``Stats.spilled_tiles``).  The sink sees every clique either way;
* **deterministic order** -- rows arrive in stream order (spill tiles,
  then packed batches per size bin; tiles in batch order inside each
  batch; each row sorted ascending), invariant to device count and
  staging mode.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from .bitops import unpack_mask
from .engine_np import Stats, list_rec_C
from .graph import ragged_expand
from . import pipeline
from . import tiles as tiles_mod
from ..kernels import ops as kops
from ..obs import trace
from ..resilience import retry as fault_retry
from ..tune import search as tune_search

#: default cap on the per-tile emit buffer (rows); tiles whose true count
#: exceeds it overflow to the host spill path instead of growing VMEM
MAX_CAPACITY = 1 << 14


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------


class CliqueSink:
    """Pluggable consumer of decoded clique rows.

    ``emit`` receives an ``(n, k) int64`` array of global vertex ids (rows
    sorted ascending) and returns how many rows it accepted; ``full`` lets
    bounded sinks stop the producer early.  ``bytes_written`` accounts the
    payload bytes of accepted rows (surfaced as ``Stats.sink_bytes``).
    """

    def __init__(self) -> None:
        self.accepted = 0
        self.bytes_written = 0

    @property
    def full(self) -> bool:
        """True when the sink wants no more rows (stops the producer)."""
        return False

    def emit(self, cliques: np.ndarray) -> int:
        """Consume an ``(n, k)`` rows chunk; return rows accepted."""
        raise NotImplementedError

    def close(self) -> None:
        """Flush/finalize; called once after the stream ends."""
        pass

    def _account(self, arr: np.ndarray) -> int:
        self.accepted += arr.shape[0]
        self.bytes_written += arr.nbytes
        return arr.shape[0]


class CallbackSink(CliqueSink):
    """Invoke ``fn(rows)`` for every emitted chunk (streaming consumers)."""

    def __init__(self, fn: Callable[[np.ndarray], None]) -> None:
        super().__init__()
        self.fn = fn

    def emit(self, cliques: np.ndarray) -> int:
        """Forward a non-empty chunk to the callback; accept all rows."""
        if cliques.shape[0]:
            self.fn(cliques)
        return self._account(cliques)


class ArraySink(CliqueSink):
    """Bounded in-memory buffer; backs ``list_cliques(max_out=...)``."""

    def __init__(self, k: int, max_out: Optional[int] = None) -> None:
        super().__init__()
        self.k = int(k)
        self.max_out = max_out
        self._chunks: List[np.ndarray] = []

    @property
    def full(self) -> bool:
        """True once ``max_out`` rows have been accepted."""
        return self.max_out is not None and self.accepted >= self.max_out

    def emit(self, cliques: np.ndarray) -> int:
        """Buffer rows, truncating at ``max_out``; return rows kept."""
        if self.max_out is not None:
            cliques = cliques[: max(self.max_out - self.accepted, 0)]
        if cliques.shape[0]:
            self._chunks.append(cliques)
        return self._account(cliques)

    def result(self) -> np.ndarray:
        """All accepted rows as one ``(n, k) int64`` array."""
        if not self._chunks:
            return np.zeros((0, self.k), dtype=np.int64)
        return np.concatenate(self._chunks)


class NpzSink(CliqueSink):
    """Accumulate rows and write one NPZ (key ``cliques``) on ``close``."""

    def __init__(self, path: str, k: int, max_out: Optional[int] = None) -> None:
        super().__init__()
        self.path = path
        self._inner = ArraySink(k, max_out=max_out)

    @property
    def full(self) -> bool:
        """Delegates to the buffering inner sink."""
        return self._inner.full

    def emit(self, cliques: np.ndarray) -> int:
        """Buffer rows (via an inner :class:`ArraySink`); return kept."""
        n = self._inner.emit(cliques)
        self.accepted = self._inner.accepted
        self.bytes_written = self._inner.bytes_written
        return n

    def close(self) -> None:
        """Write the buffered rows to ``path`` (NPZ key ``cliques``)."""
        np.savez_compressed(self.path, cliques=self._inner.result())


# ---------------------------------------------------------------------------
# decode: tile-local kernel output -> sorted global id rows
# ---------------------------------------------------------------------------


def _rows_from_packed(A_tile: np.ndarray, s: int) -> List[int]:
    """(T, W) uint32 packed adjacency -> python-int bitset rows [0..s)."""
    return [unpack_mask(A_tile[i]) for i in range(s)]


def _decode_local(
    anchor: np.ndarray, verts: np.ndarray, local: np.ndarray
) -> np.ndarray:
    """One tile: (n, l) local ids -> (n, 2+l) sorted global rows."""
    if local.shape[0] == 0:
        return np.zeros((0, 2 + local.shape[1]), dtype=np.int64)
    glob = verts[local]
    out = np.concatenate(
        [np.broadcast_to(anchor, (local.shape[0], 2)), glob],
        axis=1,
    )
    return np.sort(out, axis=1)


def _list_tile_host(
    rows: Sequence[int],
    s: int,
    anchor: np.ndarray,
    verts: np.ndarray,
    l: int,
    et_t: int = 3,
) -> np.ndarray:
    """Host bitset recursion listing for one tile (spill/overflow path)."""
    local: List[tuple] = []
    list_rec_C(rows, (1 << s) - 1, l, (), local, et_t=et_t)
    loc = np.asarray(local, dtype=np.int64).reshape(-1, l)
    return _decode_local(np.asarray(anchor, dtype=np.int64), verts, loc)


def list_spilled(
    tile: tiles_mod.Tile, l: int, stats: Stats, et_t: int = 3
) -> np.ndarray:
    """List one oversize tile on the host (mirrors ``count_spilled``)."""
    stats.spilled_tiles += 1
    stats.spill_sizes.append(tile.s)
    with trace.span("spill/list", s=tile.s):
        return _list_tile_host(
            tile.rows,
            tile.s,
            np.asarray(tile.anchor, dtype=np.int64),
            tile.verts,
            l,
            et_t=et_t,
        )


def decode_batch(
    batch: pipeline.TileBatch,
    bufs: Optional[np.ndarray],
    counts: np.ndarray,
    overflow: np.ndarray,
    l: int,
    stats: Stats,
    et_t: int = 3,
    device_rows: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Decode one harvested (buffer, count, overflow) triple to global rows.

    Non-overflowed tiles decode vectorized straight from the kernel buffer
    -- or, where the device decoded them (``device_rows``: the ``(k, Rp)``
    int32 array :func:`fetch_decoded` returns; ``bufs`` is then unused), are
    widened from those; overflowed tiles are re-listed by the host
    recursion from the packed adjacency (never truncated) and spliced back
    in tile order.
    """
    counts = np.asarray(counts, dtype=np.int64)
    overflow = np.asarray(overflow)
    counts_eff = np.where(overflow > 0, 0, counts)
    if device_rows is not None:
        decoded = np.ascontiguousarray(
            device_rows[:, : int(counts_eff.sum())].T, dtype=np.int64)
    else:
        owner, pos = ragged_expand(counts_eff)
        local = bufs[owner, pos]  # (n, l) local ids
        glob = batch.verts[owner[:, None], local]
        decoded = np.concatenate([batch.anchors[owner], glob], axis=1)
        decoded = np.sort(decoded, axis=1) if decoded.shape[0] else decoded
    if not overflow.any():
        return decoded
    parts = np.split(decoded, np.cumsum(counts_eff)[:-1])
    for b in np.nonzero(overflow)[0]:
        stats.overflowed_tiles += 1
        s = int(batch.sizes[b])
        rows = _rows_from_packed(batch.A[b], s)
        with trace.span("overflow/relist", s=s):
            parts[b] = _list_tile_host(
                rows, s, batch.anchors[b], batch.verts[b], l, et_t=et_t
            )
    return np.concatenate(parts)


# ---------------------------------------------------------------------------
# decode on the device: sized batches come back as compact sorted rows
# ---------------------------------------------------------------------------

#: the device decode pads a batch's row count up a ladder of four steps
#: an octave (under a quarter of slack), never below one 128-lane row
DECODE_MIN_ROWS = 128


def decode_rows_bucket(rows: int) -> int:
    """Rows of the device decode's output for ``rows`` decoded rows: the
    smallest m * 2**e >= rows with m in 4..7 (at least ``DECODE_MIN_ROWS``),
    which bounds both the executables and the padding fetched."""
    r = max(int(rows), DECODE_MIN_ROWS)
    e = r.bit_length() - 3
    return -(-r // (1 << e)) << e


def decodes_on_device(batch: pipeline.TileBatch) -> bool:
    """True when ``batch``'s global ids fit the device decode's int32."""
    return int(batch.verts.max(initial=0)) < 2 ** 31 and \
        int(batch.anchors.max(initial=0)) < 2 ** 31


def decode_tables(batch: pipeline.TileBatch):
    """The batch's ``(verts, anchors)`` as bucket-padded int32 host arrays,
    padded like ``A`` and ``cand`` (``engine_jax.bucket_rows``)."""
    from .engine_jax import bucket_rows

    return (bucket_rows(batch.verts.astype(np.int32)),
            bucket_rows(batch.anchors.astype(np.int32)))


def _sort_columns(cols: List) -> List:
    """Odd-even transposition network: column j becomes the j-th smallest."""
    cols = list(cols)
    for rnd in range(len(cols)):
        for i in range(rnd % 2, len(cols) - 1, 2):
            lo, hi = cols[i], cols[i + 1]
            cols[i], cols[i + 1] = jnp.minimum(lo, hi), jnp.maximum(lo, hi)
    return cols


@functools.lru_cache(maxsize=None)
def _decode_step(l: int):
    """The jitted device decode for ``l``, compiled as ``jit_list_decode_l<l>``.

    (bufs (Bp, cap, l) int32, cnt (Bp,) uint32, ovf (Bp,) uint32,
    verts (Bp, T) int32, anchors (Bp, 2) int32) -> ((k, Bp*cap + cap)
    int32, () int32): tile b's rows (the kernel's own counts; overflowed
    tiles left out), each the anchors plus ``verts[b, local]`` sorted
    ascending down the k columns, compacted in tile order from column 0,
    rows on the lane axis; the row total.  Columns past it are padding.

    No gathers: a TPU gather costs tens of ns a row, so global ids come
    from a compare-select over the tile's T slots, and the compaction
    writes each tile's block at its offset.
    """

    def step(bufs, cnt, ovf, verts, anchors):
        Bp, cap, _ = bufs.shape
        T = verts.shape[1]
        n = jnp.where(ovf > 0, 0, cnt).astype(jnp.int32)
        starts = jnp.cumsum(n) - n
        slots = jnp.arange(T, dtype=jnp.int32)
        cols = [jnp.broadcast_to(anchors[:, c, None], (Bp, cap))
                for c in (0, 1)]
        for j in range(l):
            hit = bufs[:, :, j, None] == slots  # (Bp, cap, T)
            cols.append(jnp.where(hit, verts[:, None, :], 0).sum(axis=2))
        dense = jnp.stack(_sort_columns(cols), axis=1)  # (Bp, k, cap)

        # a tile's block runs past its rows; the next tile, which starts
        # where those rows end, writes over the excess
        def place(b, out):
            return jax.lax.dynamic_update_slice(out, dense[b], (0, starts[b]))

        out = jnp.zeros((l + 2, Bp * cap + cap), jnp.int32)
        return jax.lax.fori_loop(0, Bp, place, out, unroll=4), n.sum()

    step.__name__ = step.__qualname__ = f"list_decode_l{l}"
    return jax.jit(step)


@functools.partial(jax.jit, static_argnames=("rows",))
def _first_rows(dense, rows: int):
    """The first ``rows`` columns of the decode's output (all, if fewer)."""
    return dense[:, :rows]


@dataclasses.dataclass
class DeviceRows:
    """One batch decoded on the device, not yet fetched."""

    rows: jax.Array       # (k, Rp) int32; the first ``expected`` columns
    total: jax.Array      # () int32: the device's row count
    counts: np.ndarray    # (B,) the sizing pass's per-tile counts
    overflow: np.ndarray  # (B,) bool: counts past the emit buffer
    expected: int         # rows of the tiles that fit the buffer


def decode_on_device(out, tables, l: int, counts: np.ndarray,
                     capacity: int, B: int) -> DeviceRows:
    """Launch the device decode of a list kernel's ``out`` triple.

    ``tables`` are the batch's staged ``decode_tables``; ``counts`` the
    sizing pass's (Bp,) per-tile counts, ``capacity`` the buffer they
    sized.  Runs asynchronously; :func:`fetch_decoded` collects it.
    """
    counts = np.asarray(counts, dtype=np.int64)[:B]
    overflow = counts > capacity
    R = int(counts[~overflow].sum())
    dense, total = _decode_step(l)(*out, *tables)
    # fetch R rows on the ladder: a slice per ladder step compiles in a
    # blink where the decode itself would compile once per step
    return DeviceRows(_first_rows(dense, rows=decode_rows_bucket(R)), total,
                      counts, overflow, R)


def fetch_decoded(dev: DeviceRows) -> np.ndarray:
    """Fetch a device decode's (k, Rp) rows; check the device's row total
    against the host's sizing counts (a mismatch raises)."""
    rows, total = jax.device_get((dev.rows, dev.total))
    if int(total) != dev.expected:
        raise RuntimeError(
            f"device decode listed {int(total)} rows where the sizing pass "
            f"counted {dev.expected}: the list kernel and its count pass "
            f"disagree")
    return rows


# ---------------------------------------------------------------------------
# capacity sizing
# ---------------------------------------------------------------------------


def capacity_for(
    counts: np.ndarray, max_capacity: int = MAX_CAPACITY, policy: str = "pow2"
) -> int:
    """Emit-buffer rows for a batch, rounded up under ``policy``.

    ``"pow2"`` (default) keeps the number of distinct (T, capacity) kernel
    shapes -- and hence jit recompiles -- logarithmic; ``"mult64"`` rounds
    to the next multiple of 64, trading more signatures for up to 2x less
    buffer riding the DFS carry.  Which wins is hardware-dependent, so the
    geometry tuner (:mod:`repro.tune.search`) owns the choice.
    ``max_capacity`` bounds VMEM either way, overflowing the rare monster
    tile to the host spill path instead.
    """
    m = int(np.asarray(counts).max(initial=1))
    if policy == "mult64":
        cap = -(-m // 64) * 64
    elif policy == "pow2":
        cap = 1
        while cap < m:
            cap *= 2
    else:
        raise ValueError(
            f"unknown capacity policy {policy!r}; expected 'pow2' or 'mult64'"
        )
    return max(1, min(cap, int(max_capacity)))


# ---------------------------------------------------------------------------
# streaming engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ListResult:
    """What ``stream_cliques`` hands back (the sink holds the rows)."""

    stats: Stats
    tiles: int = 0
    max_tile: int = 0


def _emit(sink: CliqueSink, arr: np.ndarray, stats: Stats) -> None:
    fault_retry.consume("sink.write")
    stats.emitted_cliques += sink.emit(arr)


def host_list_triple(batch: pipeline.TileBatch, l: int):
    """List an entire batch on the host, as a kernel-shaped triple.

    The last rung of the listing demotion ladder (DESIGN.md section 12):
    when every device backend has failed for a batch, each tile is listed
    by the ``et_t=0`` bitset recursion -- which emits local cliques in the
    same order as the device list kernels -- and packed into
    ``(bufs, counts, overflow)`` exactly as a device harvest would return
    them (local int32 indices, ``overflow == 0``).  Any downstream decode
    path therefore produces rows byte-identical to a fault-free run.
    """
    per: List[np.ndarray] = []
    for b in range(batch.B):
        s = int(batch.sizes[b])
        rows = _rows_from_packed(batch.A[b], s)
        local: List[tuple] = []
        list_rec_C(rows, (1 << s) - 1, l, (), local, et_t=0)
        per.append(np.asarray(local, dtype=np.int32).reshape(-1, l))
    cap = max(1, max((p.shape[0] for p in per), default=1))
    bufs = np.zeros((batch.B, cap, l), dtype=np.int32)
    counts = np.zeros(batch.B, dtype=np.int64)
    for b, p in enumerate(per):
        bufs[b, : p.shape[0]] = p
        counts[b] = p.shape[0]
    return bufs, counts, np.zeros(batch.B, dtype=np.uint32)


def list_batch(
    batch: pipeline.TileBatch,
    l: int,
    stats: Stats,
    *,
    capacity: Optional[int] = None,
    max_capacity: int = MAX_CAPACITY,
    cap_policy: str = "pow2",
    interpret: Optional[bool] = None,
    backend: Optional[str] = None,
    et_t: int = 3,
) -> np.ndarray:
    """Single-device emit step: count pass -> sized list kernel -> decode
    (on the device where the count pass sized the buffer, as in the
    dispatcher).

    The batch axis is padded to a power of two before the kernels so
    ragged tail chunks reuse the full-batch executables; the padded
    zero-candidate lanes count 0, never overflow, and are sliced off
    before decode.
    """
    from .engine_jax import bucket_rows, fetched_bytes

    B = batch.B
    A = jnp.asarray(bucket_rows(batch.A))
    cand = jnp.asarray(bucket_rows(batch.cand))
    if capacity is None:
        with trace.span("device/sizing", batch=batch.index, B=B, T=batch.T):
            hard = kops.count_tiles(
                A, cand, l, backend=backend, interpret=interpret
            )
            counts = np.asarray(hard)
            stats.d2h_bytes += fetched_bytes((hard,))
        cap = capacity_for(counts, max_capacity, policy=cap_policy)
    else:
        cap = max(1, int(capacity))
    rows = None  # the device decode's rows, where it ran
    with trace.span("device/wait", batch=batch.index, B=B, T=batch.T,
                    capacity=cap):
        out = kops.list_tiles(
            A, cand, l, capacity=cap, backend=backend, interpret=interpret
        )
        if capacity is None and decodes_on_device(batch):
            tables = tuple(jnp.asarray(x) for x in decode_tables(batch))
            dev = decode_on_device(out, tables, l, counts, cap, B)
            rows = fetch_decoded(dev)
            bufs, cnt, ovf = None, dev.counts, dev.overflow
            stats.d2h_bytes += fetched_bytes((dev.rows, dev.total))
            stats.device_decoded_rows += dev.expected
        else:
            bufs, cnt, ovf = (np.asarray(x)[:B] for x in out)
            stats.d2h_bytes += fetched_bytes(out)
    with trace.span("decode", batch=batch.index, B=B, T=batch.T):
        return decode_batch(batch, bufs, cnt, ovf, l, stats, et_t=et_t,
                            device_rows=rows)


def stream_cliques(
    source,
    k: int,
    sink: CliqueSink,
    *,
    order: str = "hybrid",
    use_rule2: bool = True,
    et_t: int = 3,
    batch_size: Optional[int] = None,
    bins: Optional[Sequence[int]] = None,
    capacity: Optional[int] = None,
    max_capacity: Optional[int] = None,
    cap_policy: Optional[str] = None,
    devices=None,
    async_staging: bool = True,
    max_inflight: int = 2,
    interpret: Optional[bool] = None,
    backend: Optional[str] = None,
    stage_times: Optional[dict] = None,
    pack_workers: Optional[int] = None,
    prefetch: Optional[int] = None,
    plan_cache: bool = True,
    plan_cache_dir: Optional[str] = None,
) -> ListResult:
    """List all k-cliques of ``source`` (Graph or PipelinePlan) into ``sink``.

    The accelerator twin of ``ebbkc.list_cliques(backend="host")``: streams
    capacity-batched packed tiles, runs the listing kernels (sized by a
    first count pass unless ``capacity`` pins the buffer or selects the
    dispatcher's ``"speculative"`` ratchet mode), decodes the rows, and
    feeds the sink in deterministic stream order.  A sized batch whose
    global ids fit int32 decodes on the device (:func:`decode_on_device`,
    counted in ``Stats.device_decoded_rows``); a pinned or speculative
    capacity, a routed batch, a batch that a fault sent down to the host
    rung, ids of 2**31 or more, and the host re-lists of overflowed tiles
    take the host's :func:`decode_batch`.  The rows are the same bytes
    either way.  ``devices``
    routes batches through :class:`repro.runtime.dispatch.ListDispatcher`
    (per-device placement, double-buffered staging, FIFO harvest +
    decode-worker overlap -- same knobs as the counting engine).
    ``backend`` selects the kernel implementation (``repro.kernels.ops``
    registry; emitted rows are
    byte-identical across backends).  Requires k >= 3 (the k <= 2 cases
    have closed forms; see ``ebbkc.list_cliques``).

    Front-end knobs mirror ``engine_jax.count``: ``pack_workers`` /
    ``prefetch`` run packing on the parallel producer ahead of device
    dispatch (0 = serial; the emitted row stream is identical either
    way), and a Graph ``source`` consults the keyed plan cache
    (``plan_cache=False`` opts out; ``plan_cache_dir`` adds the on-disk
    store) so warm queries skip the O(delta*m) decomposition.

    Geometry knobs left ``None`` (``batch_size``, ``bins``,
    ``max_capacity``, ``cap_policy``, ``pack_workers``, ``prefetch``)
    resolve through the persistent autotuner
    (:func:`repro.tune.search.resolve_geometry`): explicit argument >
    persisted geometry record > the historical hardcoded defaults.  The
    emitted row stream is identical under every geometry.
    """
    if k < 3:
        raise ValueError("stream_cliques requires k >= 3")
    if isinstance(capacity, str):
        if capacity not in ("sized", "speculative"):
            raise ValueError(f"capacity must be None, 'sized', "
                             f"'speculative', or an int, got {capacity!r}")
        if devices is None:
            # dispatcher modes; the inline path's exact count-pass sizing
            # covers both aliases
            capacity = None
    stats = Stats()
    stats.backend = kops.resolve_backend(backend, interpret)
    res = ListResult(stats)
    l = k - 2
    geom = tune_search.resolve_geometry(
        "list",
        l,
        batch_size=batch_size,
        bins=bins,
        cap_policy=cap_policy,
        max_capacity=max_capacity,
        pack_workers=pack_workers,
        prefetch=prefetch,
    )
    if not isinstance(source, pipeline.PipelinePlan) and plan_cache:
        source = pipeline.cached_plan(source, order=order,
                                      cache_dir=plan_cache_dir, stats=stats)
    stream = pipeline.stream_batches(
        source,
        k,
        order=order,
        use_rule2=use_rule2,
        batch_size=geom.batch_size,
        bins=geom.bins,
        timings=stage_times,
        pack_workers=geom.pack_workers,
        prefetch=geom.prefetch,
        stats=stats,
    )
    if devices is not None:
        from ..runtime.dispatch import ListDispatcher

        disp = ListDispatcher(
            l,
            devices,
            sink=sink,
            stats=stats,
            capacity=capacity,
            max_capacity=geom.max_capacity,
            cap_policy=geom.cap_policy,
            interpret=interpret,
            backend=backend,
            async_staging=async_staging,
            max_inflight=max_inflight,
            et_t=et_t,
            stage_times=stage_times,
        )

        def on_spill(tile: tiles_mod.Tile) -> None:
            # host listing runs here (consumer thread); the emit goes
            # through the dispatcher's decode worker so the rows keep
            # their FIFO position relative to batch decodes
            disp.emit_rows(list_spilled(tile, l, stats, et_t=et_t))

        try:
            res.tiles, res.max_tile = disp.consume(stream, on_spill=on_spill)
            disp.finish()
        finally:
            # error path: stop the decode worker from emitting into the
            # caller's sink and cancel queued pack work; both are no-ops
            # after a clean finish
            disp.close()
            stream.close()
    else:
        try:
            for item in stream:
                if sink.full:
                    break
                if isinstance(item, tiles_mod.Tile):
                    res.tiles += 1
                    res.max_tile = max(res.max_tile, item.s)
                    _emit(sink, list_spilled(item, l, stats, et_t=et_t),
                          stats)
                    continue
                res.tiles += item.B
                res.max_tile = max(res.max_tile, item.T)
                arr = list_batch(
                    item,
                    l,
                    stats,
                    capacity=capacity,
                    max_capacity=geom.max_capacity,
                    cap_policy=geom.cap_policy,
                    interpret=interpret,
                    backend=backend,
                    et_t=et_t,
                )
                _emit(sink, arr, stats)
        finally:
            stream.close()  # shuts down any parallel-producer workers
    stats.sink_bytes += sink.bytes_written
    stats.kernel_compile_s += kops.consume_compile_s()
    kops.drain_tune_events(stats)
    return res
