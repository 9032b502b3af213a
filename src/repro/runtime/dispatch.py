"""Multi-device sharded dispatch for the streaming tile pipeline.

DESIGN.md section 4.  The edge-oriented branching of EBBkC makes the tile
stream embarrassingly parallel: every packed ``TileBatch`` is an independent
fixed-shape device call, so scaling past one chip is purely a placement and
staging problem.  This module turns the scheduler's LPT bins
(`clique_scheduler.schedule_batches`) into *real* devices:

* **Per-device dispatch** (default): each batch is committed to one local
  device with ``jax.device_put`` and counted by a per-device ``jit`` of
  ``engine_jax.count_packed`` (jit caches one executable per
  (shape, device) pair).  Placement is either *online LPT* -- each arriving
  batch goes to the least-loaded device under the scheduler cost model,
  which needs no lookahead and so composes with streaming -- or *offline
  LPT* via :func:`dispatch_scheduled`, which maps precomputed scheduler
  bins one-to-one onto devices.
* **shard_map path**: when the caller provides a mesh
  (``launch/mesh.py``), each batch is padded to the mesh batch axes and
  counted in a single SPMD step; outputs stay device-local and the host
  combines them exactly.
* **Double-buffered staging**: with ``async_staging=True`` (default) up to
  ``max_inflight`` batches per device are left un-harvested, so the host
  packs batch i+1 while the devices execute batch i.  The overlapped
  seconds are accounted in ``Stats.staging_overlap_s``.

Counts are exact and invariant to device count, placement, and staging
mode: every device step returns (hard, nv, t, f) partials and the host
reduces them in int64 (including the Section 5.1 early-termination closed
form), so a 1-device CPU CI run is byte-identical to an N-device run.
"""

from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import functools
import itertools
import threading
import time
from typing import Deque, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from ..core import engine_jax, pipeline
from ..core.engine_np import Stats
from ..obs import profile as obs_profile
from ..obs import trace
from ..resilience import inject
from ..resilience import retry as fault_retry
from .clique_scheduler import schedule_batches, tile_costs


def resolve_devices(
    devices: Union[None, int, str, Sequence] = None,
) -> List[jax.Device]:
    """Normalize a ``devices=`` knob to a concrete local device list.

    ``None`` / ``"all"`` -> every local device; an int n -> the first
    min(n, available) devices (graceful CPU-CI fallback: asking for 4 on a
    1-device host degrades to 1 device, never errors); a sequence of jax
    devices is passed through.
    """
    avail = jax.devices()
    if devices is None or devices == "all":
        return list(avail)
    if isinstance(devices, int):
        if devices < 1:
            raise ValueError("devices must be >= 1")
        return list(avail[: min(devices, len(avail))])
    devs = list(devices)
    if not devs:
        raise ValueError("empty device list")
    return devs


def batch_flops(n_tiles: int, T: int) -> int:
    """MXU-equivalent flop model of one packed batch (dense-tile matmul)."""
    return int(n_tiles) * 2 * int(T) ** 3


def batch_bytes(n_tiles: int, T: int) -> int:
    """Bytes staged to a device per packed tile: the (T, W) uint32
    adjacency bitset plus the (W,) candidate mask (W = T/32).  The
    roofline bandwidth denominator paired with :func:`batch_flops`."""
    W = int(T) // 32
    return int(n_tiles) * (int(T) * W + W) * 4


def _account_devices(stats: Stats, per_device_tiles, per_device_cost,
                     T: int) -> None:
    """Fold one batch's per-device tile counts and placed cost into ``stats``.

    The single accounting path shared by both dispatchers: builds a delta
    :class:`Stats` carrying only the per-device maps and folds it in via
    ``Stats.merge`` (the one merge routine -- see ``Stats._MERGE_KINDS``).
    """
    delta = Stats()
    for d, (c, cost) in enumerate(zip(per_device_tiles, per_device_cost)):
        if not c:
            continue
        delta.device_tiles[d] = int(c)
        delta.device_flops[d] = batch_flops(int(c), T)
        delta.device_bytes[d] = batch_bytes(int(c), T)
        delta.device_cost[d] = float(cost)
    stats.merge(delta)


def _mesh_batch_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """Axes a tile batch shards over: every non-'model' axis of the mesh."""
    axes = tuple(a for a in mesh.axis_names if a != "model")
    return axes if axes else tuple(mesh.axis_names[:1])


# (step identity, input shape, device ordinal) triples whose first call --
# compile + first run -- has already been billed to kernel_compile_s; the
# jitted steps below are process-wide (lru_cache pins their identity), so
# the seen-set must be process-wide too
_COMPILED_STEPS = set()


@functools.lru_cache(maxsize=None)
def _device_step(
    l: int,
    method: str,
    et: bool,
    interpret: Optional[bool],
    backend: Optional[str] = None,
    name: str = "count",
):
    """Process-wide jitted ``count_packed`` step, shared by all dispatchers.

    Memoized so repeated queries reuse one jit cache: jit compiles one
    executable per (input shape, device) pair, and a fresh ``jax.jit`` per
    dispatcher would re-trace the whole kernel on every query.  The
    compiled module is named ``jit_<name>_l<l>`` (``jit_count_l3``), so a
    profiler trace tells the steps apart.
    """

    def step(A, cand):
        return engine_jax.count_packed(
            A, cand, l, method=method, et=et, interpret=interpret, backend=backend
        )

    step.__name__ = step.__qualname__ = f"{name}_l{l}"
    return jax.jit(step)


@functools.lru_cache(maxsize=None)
def make_sharded_step(
    mesh: jax.sharding.Mesh,
    l: int,
    method: str = "auto",
    et: bool = True,
    interpret: Optional[bool] = None,
    backend: Optional[str] = None,
):
    """One jitted SPMD ``count_packed`` step over the mesh batch axes.

    Outputs keep the batch sharding (no psum): the host combines the
    per-shard partials exactly in int64, preserving the early-termination
    closed form.
    """
    P = jax.sharding.PartitionSpec
    axes = _mesh_batch_axes(mesh)

    def inner(A_loc, cand_loc):
        return engine_jax.count_packed(
            A_loc,
            cand_loc,
            l,
            method=method,
            et=et,
            interpret=interpret,
            backend=backend,
        )

    inner.__name__ = inner.__qualname__ = f"count_l{l}"
    fn = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(axes, None, None), P(axes, None)),
        out_specs=(P(axes), P(axes), P(axes), P(axes)),
        check_vma=False,
    )
    return jax.jit(fn), axes


def _pad_rows(x: np.ndarray, multiple: int) -> np.ndarray:
    """Zero-pad axis 0 of ``x`` up to a multiple of ``multiple``.

    Padding rows have ``cand == 0`` (no candidate vertices), which
    contributes exactly 0 to both the kernel and the closed-form count for
    every l >= 1, so padded and unpadded batches agree.
    """
    pad = (-x.shape[0]) % multiple
    if not pad:
        return x
    return np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])


@dataclasses.dataclass
class Routed:
    """One item of a multi-request stream: a pipeline item plus its route.

    Wrap ``pipeline.TileBatch`` / oversize ``Tile`` items in ``Routed``
    to interleave several logical requests through one dispatcher
    ``consume`` call.  ``route`` is forwarded verbatim: for a
    ``TileBatch`` it becomes the ``route=`` callback of ``submit`` (so
    this batch's results bypass the dispatcher-global accumulator/sink
    and are delivered to the owning request instead); for a spill tile it
    is passed as a second argument to ``on_spill``.  Bare (unwrapped)
    items keep the single-request behavior, so the two styles can mix in
    one stream.
    """

    item: object
    route: object = None


def _consume_stream(disp, stream, on_spill, stop=None) -> Tuple[int, int]:
    """Shared stream-consumption loop of both dispatchers' ``consume``.

    Submits packed batches, routes oversize spill tiles to ``on_spill``,
    and stops early when ``stop()`` turns true (the listing sink's
    ``full``).  Items may be wrapped in :class:`Routed` to tag them with
    a per-request route (multi-tenant streams); bare items behave as
    before.  Returns (tiles consumed, max tile width).
    """
    ntiles = 0
    max_tile = 0
    for item in stream:
        if stop is not None and stop():
            break
        route = None
        if isinstance(item, Routed):
            item, route = item.item, item.route
        if isinstance(item, pipeline.TileBatch):
            ntiles += item.B
            max_tile = max(max_tile, item.T)
            disp.submit(item, route=route)
            continue
        if on_spill is None:
            raise ValueError("oversize tile in stream but no on_spill "
                             "handler given")
        ntiles += 1
        max_tile = max(max_tile, item.s)
        if route is None:
            on_spill(item)
        else:
            on_spill(item, route)
    return ntiles, max_tile


@dataclasses.dataclass
class _InFlight:
    """One staged batch awaiting harvest (device arrays, not host data)."""

    device: int  # device ordinal; -1 for the shard_map path
    out: Tuple[jax.Array, jax.Array, jax.Array, jax.Array]
    rows: int = 0  # un-padded batch rows (slice bound for routed harvest)
    route: object = None  # per-request delivery callback, or None
    batch: object = None  # host TileBatch, kept for resilient re-execution


class Dispatcher:
    """Streams packed tile batches across the local device set.

    See the module docstring for the execution model.  Typical use::

        disp = Dispatcher(l, devices="all", stats=stats)
        for item in pipeline.stream_batches(plan, k):
            if isinstance(item, pipeline.TileBatch):
                disp.submit(item)
            else:
                ...  # spill to host recursion
        total = disp.finish()
    """

    def __init__(
        self,
        l: int,
        devices: Union[None, int, str, Sequence] = None,
        *,
        mesh: Optional[jax.sharding.Mesh] = None,
        et: bool = True,
        method: str = "auto",
        interpret: Optional[bool] = None,
        backend: Optional[str] = None,
        async_staging: bool = True,
        max_inflight: int = 2,
        stats: Optional[Stats] = None,
        stage_times: Optional[dict] = None,
        retry_policy: Optional[fault_retry.RetryPolicy] = None,
    ):
        from ..kernels import ops as kops

        if l < 1:
            raise ValueError("dispatch requires l >= 1 (k >= 3)")
        self.l = l
        self.et = et
        self.mesh = mesh
        self.async_staging = async_staging
        self.max_inflight = max(1, int(max_inflight))
        self.stats = stats if stats is not None else Stats()
        # resolve once and bake the *resolved* name into the cached step:
        # the jit cache key must distinguish REPRO_BACKEND states, and what
        # actually executes must match what stats.backend reports
        backend = kops.resolve_backend(backend, interpret)
        self.stats.backend = backend
        self.stage_times = stage_times
        self.retry_policy = retry_policy or fault_retry.DEFAULT_POLICY
        # kept for building demoted steps down the backend ladder
        self._method = method
        self._interpret = interpret
        self._backend = backend
        self.total = 0
        self.tiles = 0
        self.placements: List[int] = []
        self._inflight: Deque[_InFlight] = collections.deque()
        self._overlap_mark = 0.0
        if mesh is not None:
            self.devices = list(mesh.devices.flat)
            self._step, axes = make_sharded_step(
                mesh, l, method=method, et=et, interpret=interpret, backend=backend
            )
            self._n_shards = int(np.prod([mesh.shape[a] for a in axes]))
            ns, ps = jax.sharding.NamedSharding, jax.sharding.PartitionSpec
            self._in_shardings = (
                ns(mesh, ps(axes, None, None)),
                ns(mesh, ps(axes, None)),
            )
        else:
            self.devices = resolve_devices(devices)
            self._n_shards = 1
            self._in_shardings = None
            self._step = _device_step(l, method, et, interpret, backend)
        self._loads = np.zeros(len(self.devices))

    def _run_step(self, A, cand, device: int, step=None):
        """Invoke the jitted step; time the first call per
        (step, shape, device) signature into ``stats.kernel_compile_s``
        (compile + first run).  The seen-set is process-wide, matching the
        process-wide jit cache: a warm executable must neither block nor
        re-bill its run time as compile on later dispatcher instances.
        ``step`` overrides the baked-in step (demoted-backend retries)."""
        step = self._step if step is None else step
        sig = (id(step), A.shape, device)
        if sig in _COMPILED_STEPS:
            return step(A, cand)
        t0 = time.perf_counter()
        with trace.span("kernel/compile", sig=self._sig(A.shape[0], A.shape[1])):
            out = jax.block_until_ready(step(A, cand))
        dt = time.perf_counter() - t0
        self.stats.kernel_compile_s += dt
        obs_profile.note_kernel(self._sig(A.shape[0], A.shape[1]), compile_s=dt)
        _COMPILED_STEPS.add(sig)
        return out

    def _sig(self, B: int, T: int) -> str:
        """Kernel-signature label for profiling attribution."""
        return f"count[l={self.l},T={T},B={B},backend={self.stats.backend}]"

    @property
    def n_devices(self) -> int:
        """Number of devices this dispatcher places batches on."""
        return len(self.devices)

    def _note_retry(self, attempt: int, exc: BaseException) -> None:
        """Per-batch attempt accounting hook (``retry.call`` on_retry)."""
        self.stats.retries += 1
        trace.instant("resilience/retry", attempt=attempt,
                      error=type(exc).__name__)

    def _launch_on(self, batch: pipeline.TileBatch, d: int,
                   backend: Optional[str]):
        """Stage ``batch`` on device ``d`` and launch one count step.

        ``backend=None`` uses the dispatcher's baked-in step; a backend
        name builds (and jit-caches) the demoted step for that rung.
        Fires the ``device.stage`` and ``kernel.launch`` fault sites.
        """
        if backend is None and self.mesh is None:
            step = None
        else:
            step = _device_step(self.l, self._method, self.et,
                                self._interpret, backend or self._backend)
        inject.fire("device.stage")
        # batch-shape bucketing: ragged tail chunks pad to pow2 and reuse
        # the full chunks' executables (padding counts 0)
        A = jax.device_put(engine_jax.bucket_rows(batch.A), self.devices[d])
        cand = jax.device_put(engine_jax.bucket_rows(batch.cand),
                              self.devices[d])
        inject.fire("kernel.launch")
        return self._run_step(A, cand, d, step=step)

    def _launch(self, batch: pipeline.TileBatch, d: int, *,
                block: bool = False):
        """Launch with retry, then demotion down the backend ladder.

        Each rung (the resolved backend, then its ``fault_retry.demote``
        successors: pallas -> lax -> ref) is retried under
        ``retry_policy``; an exhausted ladder falls back to the host
        recursion (:meth:`_host_partials`), which cannot fail.  Every
        rung returns exact partials, so retried and demoted batches stay
        byte-identical to a fault-free run.  ``block=True`` additionally
        waits for the result (harvest-side recovery re-entering the same
        FIFO slot).
        """
        backend = None  # None = the dispatcher's resolved backend
        while True:
            try:
                out = fault_retry.call(
                    lambda b=backend: self._launch_on(batch, d, b),
                    policy=self.retry_policy,
                    token="count.launch", on_retry=self._note_retry)
                if block:
                    jax.block_until_ready(out)
                return out
            except fault_retry.RECOVERABLE as exc:
                self.stats.demotions += 1
                nxt = fault_retry.demote(
                    "count", backend if backend is not None else self._backend)
                trace.instant("resilience/demote", frm=backend or self._backend,
                              to=nxt or "host", error=type(exc).__name__)
                if nxt is None:
                    return self._host_partials(batch)
                backend = nxt

    def _host_partials(self, batch: pipeline.TileBatch):
        """Count ``batch`` on the host recursion (the ladder's last rung).

        Returns numpy ``(hard, nv, t, f)`` partials that
        ``engine_jax.combine_counts`` finishes to the exact same totals
        as a device step: ``hard`` carries the true per-tile count and
        ``t`` is pinned above the 2-plex threshold, so the
        early-termination closed form adds nothing.
        """
        from ..core import listing
        from ..core.engine_np import count_rec_C

        hard = np.zeros(batch.B, dtype=np.int64)
        for b in range(batch.B):
            s = int(batch.sizes[b])
            rows = listing._rows_from_packed(batch.A[b], s)
            hard[b] = count_rec_C(rows, (1 << s) - 1, self.l, self.stats)
        zeros = np.zeros(batch.B, dtype=np.int64)
        return hard, zeros, np.full(batch.B, 3, dtype=np.int64), zeros

    def submit(
        self,
        batch: pipeline.TileBatch,
        device: Optional[int] = None,
        route=None,
    ) -> None:
        """Stage one packed batch and launch its device step (non-blocking).

        ``device`` forces a placement (offline scheduling); otherwise the
        batch goes to the least-loaded device under the scheduler cost
        model (online LPT).

        ``route``, when given, redirects this batch's results: at harvest
        the raw per-tile partials are fetched, sliced back to the batch's
        un-padded ``B`` rows, and passed to ``route(hard, nv, t, f)`` as
        int64 numpy arrays instead of being folded into ``self.total``
        (use ``engine_jax.combine_counts`` on any row segment to finish
        them exactly).  This is the multi-tenant seam: batches from
        different requests share devices and warm executables while their
        counts route back to their owners.  Routes run on the thread that
        triggers the harvest (the submitting/draining thread).

        Thread safety: all ``submit``/``drain``/``finish`` calls must come
        from one thread; only the ``route`` callbacks themselves may hand
        work to other threads.

        Resilience: a failed stage/launch is retried under
        ``retry_policy``, then demoted down the backend ladder
        (:meth:`_launch`); the batch keeps its FIFO position either way.
        """
        t_stage = time.perf_counter()
        if self.mesh is not None:
            d = -1
        else:
            d = int(np.argmin(self._loads)) if device is None else int(device)
        with trace.span("device/stage", batch=batch.index, B=batch.B,
                        T=batch.T, device=d):
            costs = tile_costs(batch.sizes, batch.nedges, self.l)
            if self.mesh is not None:
                def launch_mesh():
                    inject.fire("device.stage")
                    A = _pad_rows(batch.A, self._n_shards)
                    cand = _pad_rows(batch.cand, self._n_shards)
                    A, cand = jax.device_put((A, cand), self._in_shardings)
                    inject.fire("kernel.launch")
                    return A.shape[0], self._run_step(A, cand, d)

                try:
                    padded, out = fault_retry.call(
                        launch_mesh, policy=self.retry_policy,
                        token="count.mesh",
                        on_retry=self._note_retry)
                except fault_retry.RECOVERABLE:
                    # the SPMD step has no per-device ladder; fall straight
                    # back to the (exact) host recursion
                    self.stats.demotions += 1
                    padded, out = batch.B, self._host_partials(batch)
                shard_rows = max(1, padded // self._n_shards)
                shard = np.minimum(np.arange(batch.B) // shard_rows,
                                   self._n_shards - 1)
                per_dev = np.bincount(shard, minlength=self._n_shards)
                per_cost = np.bincount(shard, weights=costs,
                                       minlength=self._n_shards)
            else:
                cost = float(costs.sum())
                self._loads[d] += cost
                out = self._launch(batch, d)
                per_dev = np.zeros(self.n_devices, dtype=np.int64)
                per_dev[d] = batch.B
                per_cost = np.zeros(self.n_devices)
                per_cost[d] = cost
        if self.stage_times is not None:
            st = self.stage_times
            st["stage"] = st.get("stage", 0.0) + time.perf_counter() - t_stage
        self.placements.append(d)
        self.tiles += batch.B
        _account_devices(self.stats, per_dev, per_cost, batch.T)
        if not self._inflight:
            # in-flight window (re)opens now; overlap accrues from here
            self._overlap_mark = time.perf_counter()
        self._inflight.append(_InFlight(d, out, batch.B, route, batch))
        if not self.async_staging:
            self._drain()
        else:
            while len(self._inflight) > self.max_inflight * self.n_devices:
                self._harvest_one()

    def _harvest_one(self) -> None:
        p = self._inflight.popleft()
        # the "device" clock brackets what the device/harvest span does
        with trace.span("device/harvest", batch=p.batch.index,
                        device=p.device):
            t0 = time.perf_counter()
            # wall time since the last accounting mark during which work
            # was in flight and the host was free (packing / combining,
            # not blocked): an upper bound on the device execution hidden
            # behind host work (the device may have finished early; the
            # profiler trace measures true device busy time).  Counting
            # whole dispatch-to-harvest residencies instead would
            # double-count concurrent in-flight batches.  Synchronous
            # staging hides nothing by construction.
            if self.async_staging:
                self.stats.staging_overlap_s += max(
                    0.0, t0 - self._overlap_mark)
            # injected harvest faults are pure (the device result still
            # exists) and absorbed in place; a REAL wait failure means the
            # staged result is lost -- recompute the same batch
            # synchronously in its FIFO slot, so totals and routed
            # partials are unchanged
            fault_retry.consume("device.harvest", on_retry=self._note_retry)
            try:
                jax.block_until_ready(p.out)
            except fault_retry.RECOVERABLE as exc:
                self._note_retry(1, exc)
                p.out = self._launch(p.batch, max(p.device, 0), block=True)
            t1 = time.perf_counter()
        self._overlap_mark = t1  # blocked interval [t0, t1] is not overlap
        with trace.span("combine", batch=p.batch.index,
                        routed=p.route is not None):
            if p.route is None:
                self.total += engine_jax.combine_counts(
                    *p.out, self.l, self.et, stats=self.stats)
            else:
                # multi-tenant: hand the un-padded partial rows to the owner
                # (shape padding appends rows, so a head slice removes it)
                self.stats.d2h_bytes += engine_jax.fetched_bytes(p.out)
                p.route(*(np.asarray(x)[: p.rows] for x in p.out))
        t2 = time.perf_counter()
        if self.stage_times is not None:
            st = self.stage_times
            st["device"] = st.get("device", 0.0) + (t1 - t0)
            st["combine"] = st.get("combine", 0.0) + (t2 - t1)

    def _drain(self) -> None:
        while self._inflight:
            self._harvest_one()

    def consume(self, stream, on_spill=None) -> Tuple[int, int]:
        """Drive this dispatcher from a ``pipeline.stream_batches`` iterator.

        The single consumption point shared by counting and listing: both
        engines hand the dispatcher the (possibly parallel-producer)
        stream and the dispatcher pulls from its bounded prefetch queue,
        submitting packed batches and routing oversize spill tiles to
        ``on_spill``.  The stream may interleave several requests by
        wrapping items in :class:`Routed` -- routed batches deliver their
        partials to their own route callback instead of ``self.total``,
        and routed spills call ``on_spill(tile, route)``.  Returns
        (tiles consumed, max tile width); call :meth:`finish` (one-shot)
        or :meth:`drain` (long-lived service) afterwards.
        """
        return _consume_stream(self, stream, on_spill)

    def drain(self) -> None:
        """Block until every submitted batch is harvested (routes included).

        The long-lived-service twin of :meth:`finish`: it flushes the
        in-flight window without touching the backend compile/tune
        accounting, so the dispatcher stays usable for further
        ``submit`` calls.
        """
        self._drain()

    def finish(self) -> int:
        """Drain all in-flight work; returns the accumulated exact count.

        Routed batches are not part of the returned total -- their counts
        went to their route callbacks.
        """
        from ..kernels import ops as kops

        self._drain()
        self.stats.kernel_compile_s += kops.consume_compile_s()
        kops.drain_tune_events(self.stats)
        return self.total


#: initial emit-buffer rows for the speculative capacity ratchet (pow2;
#: small enough that a wrong first guess wastes little, large enough that
#: sparse tile batches never retry)
SPECULATIVE_CAP0 = 64


def _is_ready(x) -> bool:
    """Non-blocking readiness probe of a device array (True = safe to
    fetch without stalling)."""
    return x.is_ready()


class ListDispatcher:
    """Emit-mode twin of :class:`Dispatcher` for the listing subsystem.

    Streams packed tile batches across the local device set and harvests
    (count, overflow, buffer) triples instead of scalar partials.  Three
    capacity modes size the per-tile emit buffer:

    * ``capacity=None`` / ``"sized"`` (default) -- exact per-batch sizing
      by a pipelined count pass: ``submit`` launches the count pass
      asynchronously and queues the batch as *pending*; the listing
      kernel is launched as soon as that batch's counts land on the host
      (probed non-blockingly via ``jax.Array.is_ready`` each submit, or
      forced when the in-flight window fills).  Minimal buffer memory,
      two device passes.
    * ``capacity="speculative"`` -- the listing kernel launches
      immediately at a per-tile-width capacity ratchet (the pow2 ceiling
      of every true count seen so far for that T, starting at
      ``SPECULATIVE_CAP0``).  The kernel always returns true counts, so a
      guess that proves too small is retried once on the device at the
      exact pow2 size (``Stats.emit_retries``) -- the answer is
      identical, only the work moves.  One device pass per batch instead
      of two, but the buffer rides in the DFS ``while_loop`` carry, so an
      over-ratcheted capacity taxes every loop iteration -- measured
      slower than "sized" on the lax/CPU backend, hence opt-in.
    * ``capacity=<int>`` -- pinned buffer; overflowed tiles re-list on
      the host (never truncated), as always.

    Harvest/decode of completed triples overlaps device execution of
    later batches in every mode.

    Where decode runs: in sized mode an un-routed batch whose global ids
    fit int32 is decoded on its device right after its list kernel
    (``listing.decode_on_device``: the batch's ``verts``/``anchors`` are
    staged with it, and the decode worker fetches compact sorted rows,
    checks their total against the count pass and splices in host
    re-lists of overflowed tiles).  Speculative and pinned capacities,
    routed batches, batches served by the host rung, and ids of 2**31 or
    more decode on the host (``listing.decode_batch``), as does a batch
    whose decoded rows are lost after launch.

    Ordering guarantee: pending batches are promoted strictly FIFO,
    harvested strictly FIFO, and decoded/emitted by **one** decode-worker
    thread consuming a FIFO queue, so decoded rows reach the sink
    deterministically **in batch order** no matter how many devices
    executed them or how staging overlapped (asserted by
    ``tests/test_dispatch.py::test_list_dispatcher_sink_order_deterministic``
    and stress-tested under adversarial readiness schedules in
    ``tests/test_determinism.py``).  The decode worker also owns the
    blocking wait for each device triple, so decode, overflow re-lists,
    and sink writes all overlap both device execution and the consumer
    thread's submit/promote work; its backlog is bounded
    (``max_inflight * n_devices`` jobs) because each job pins its device
    buffers.  Overflowed tiles are re-listed on the host at decode time
    (never truncated); the shard_map mesh path is counting-only.
    """

    def __init__(
        self,
        l: int,
        devices: Union[None, int, str, Sequence] = None,
        *,
        sink=None,
        stats: Optional[Stats] = None,
        capacity: Optional[int] = None,
        max_capacity: Optional[int] = None,
        cap_policy: str = "pow2",
        et_t: int = 3,
        interpret: Optional[bool] = None,
        backend: Optional[str] = None,
        async_staging: bool = True,
        max_inflight: int = 2,
        stage_times: Optional[dict] = None,
        retry_policy: Optional[fault_retry.RetryPolicy] = None,
    ):
        from ..core import listing
        from ..kernels import ops as kops

        if l < 1:
            raise ValueError("dispatch requires l >= 1 (k >= 3)")
        if isinstance(capacity, str) and capacity not in ("sized",
                                                          "speculative"):
            raise ValueError(f"capacity must be None, 'sized', "
                             f"'speculative', or an int, got {capacity!r}")
        self.l = l
        self.sink = sink
        self.stats = stats if stats is not None else Stats()
        # resolved once, like Dispatcher: cached step and stats must agree
        backend = kops.resolve_backend(backend, interpret)
        self.stats.backend = backend
        self.capacity = capacity
        self.max_capacity = (
            listing.MAX_CAPACITY if max_capacity is None else int(max_capacity)
        )
        self.cap_policy = cap_policy  # emit-buffer rounding (tuned knob)
        # speculative mode: pow2 capacity ratchet per tile width.  Written
        # by the decode worker (true counts), read by submit; a stale read
        # is harmless -- it only costs one device retry.
        self._cap_ratchet: dict = {}
        self.et_t = et_t
        self.interpret = interpret
        self.backend = backend
        self.retry_policy = retry_policy or fault_retry.DEFAULT_POLICY
        self.async_staging = async_staging
        self.max_inflight = max(1, int(max_inflight))
        self.stage_times = stage_times
        self.tiles = 0
        self.placements: List[int] = []
        self.devices = resolve_devices(devices)
        # et=False: ``hard`` is then the raw per-tile count for EVERY tile
        # (no 2-plex masking), which is exactly the emit-buffer size input
        self._count_step = _device_step(l, "auto", False, interpret, backend,
                                        name="list_size")
        self._loads = np.zeros(len(self.devices))
        # count pass in flight, list kernel not yet launched (FIFO)
        self._pending: Deque[Tuple[int, pipeline.TileBatch, tuple]] = (
            collections.deque()
        )
        # list kernel in flight, not yet harvested (FIFO)
        self._inflight: Deque[Tuple[int, pipeline.TileBatch, tuple]] = (
            collections.deque()
        )
        # ONE decode worker: harvest hands (batch, triple) jobs to it, so
        # blocking on device results, decoding, overflow re-lists, and
        # sink emission all overlap the consumer thread's submit/promote
        # work -- and a single worker consuming a FIFO queue preserves
        # the deterministic sink order by construction
        self._decode_ex = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="emit-decode"
        )
        self._decoding: Deque[concurrent.futures.Future] = collections.deque()
        self._decode_depth = max(2, self.max_inflight * len(self.devices))
        # stats/stage_times are written by both the consumer thread
        # (sizing waits) and the decode worker (decode/emit seconds)
        self._acct_lock = threading.Lock()

    @property
    def n_devices(self) -> int:
        """Number of devices this dispatcher places batches on."""
        return len(self.devices)

    def _note_retry(self, attempt: int, exc: BaseException) -> None:
        """Per-batch attempt accounting (submit thread + decode worker)."""
        with self._acct_lock:
            self.stats.retries += 1
        trace.instant("resilience/retry", attempt=attempt,
                      error=type(exc).__name__)

    def _note_demotion(self, frm: Optional[str], to: Optional[str]) -> None:
        """Count one rung of the backend ladder in Stats/trace."""
        with self._acct_lock:
            self.stats.demotions += 1
        trace.instant("resilience/demote", frm=frm or self.backend,
                      to=to or "host")

    def _stage(self, batch: pipeline.TileBatch, d: int, tables: bool = False):
        """Fire the stage site and device_put the bucketing-padded batch,
        with its decode tables (``listing.decode_tables``) if ``tables``.

        The padded zero-candidate lanes are sliced off again in the
        decode job (padding rows count 0 and never overflow).
        """
        from ..core import listing

        inject.fire("device.stage")
        host = (engine_jax.bucket_rows(batch.A),
                engine_jax.bucket_rows(batch.cand))
        if tables:
            host += listing.decode_tables(batch)
        staged = jax.device_put(host, self.devices[d])
        return staged[0], staged[1], (tuple(staged[2:]) if tables else None)

    def _count_pass(self, A, cand):
        """Fire the launch site and start the async sizing count pass."""
        inject.fire("kernel.launch")
        return self._count_step(A, cand)[0]

    def _list_once(self, A, cand, cap: int, backend: str):
        """Fire the launch site and start one list kernel."""
        from ..kernels import ops as kops

        inject.fire("kernel.launch")
        return kops.list_tiles(A, cand, self.l, capacity=cap,
                               backend=backend, interpret=self.interpret)

    def _launch_list(self, batch: pipeline.TileBatch, A, cand, cap: int):
        """Launch one list kernel with retry, then backend demotion.

        Rungs: the resolved backend, then its ``fault_retry.demote``
        successor (pallas -> lax; ``ref`` implements counting only).  An
        exhausted ladder falls back to ``listing.host_list_triple`` --
        the host recursion in kernel emission order -- so the returned
        triple decodes byte-identically no matter which rung served it.
        """
        from ..core import listing

        backend = self.backend
        while True:
            try:
                return fault_retry.call(
                    lambda b=backend: self._list_once(A, cand, cap, b),
                    policy=self.retry_policy,
                    token="list.launch", on_retry=self._note_retry)
            except fault_retry.RECOVERABLE:
                nxt = fault_retry.demote("list", backend)
                self._note_demotion(backend, nxt)
                if nxt is None:
                    return listing.host_list_triple(batch, self.l)
                backend = nxt

    def _relaunch_sync(self, batch: pipeline.TileBatch, cap: int):
        """Harvest-side recovery: re-stage and re-list a lost batch.

        Returns a triple (device or host) for the same FIFO slot; never
        raises -- a dead device falls through to the host recursion.
        """
        try:
            A = jax.device_put(engine_jax.bucket_rows(batch.A),
                               self.devices[0])
            cand = jax.device_put(engine_jax.bucket_rows(batch.cand),
                                  self.devices[0])
        except fault_retry.RECOVERABLE:
            from ..core import listing

            self._note_demotion(self.backend, None)
            return listing.host_list_triple(batch, self.l)
        return self._launch_list(batch, A, cand, cap)

    def submit(
        self,
        batch: pipeline.TileBatch,
        device: Optional[int] = None,
        route=None,
    ) -> None:
        """Stage one batch and launch its (first) device pass.

        ``device`` forces a placement (offline scheduling); otherwise
        online LPT picks the least-loaded device.

        ``route``, when given, replaces the default decode-and-emit for
        this batch: on the decode worker, ``route(batch, bufs, cnt,
        ovf)`` receives the raw listing triple sliced back to the batch's
        un-padded ``B`` rows and must return the number of rows it
        emitted (use ``listing.decode_batch`` on any row segment to
        materialize them).  Routed batches never touch ``self.sink``
        (which may then be None) -- this is the multi-tenant seam:
        batches fused from several requests run as one device call while
        each request's rows reach its own sink.  Route callbacks run on
        the single decode worker in strict FIFO batch order, so the
        per-request delivery order is as deterministic as the default
        sink path.

        Thread safety: all ``submit``/``drain``/``finish`` calls must
        come from one thread; routes run on the decode worker thread.

        Resilience: staging and launches are retried under
        ``retry_policy`` and demoted down the listing backend ladder
        (pallas -> lax -> ``listing.host_list_triple``); a batch keeps
        its FIFO queue position whichever rung serves it, so the decoded
        row stream is byte-identical to a fault-free run.
        """
        from ..core import listing

        if route is None and self.sink is None:
            raise ValueError("emit mode requires a CliqueSink (or per-"
                             "batch route callbacks)")
        t_stage = time.perf_counter()
        d = int(np.argmin(self._loads)) if device is None else int(device)
        with trace.span("device/stage", batch=batch.index, B=batch.B,
                        T=batch.T, device=d):
            cost = float(tile_costs(batch.sizes, batch.nedges, self.l).sum())
            self._loads[d] += cost
            self.placements.append(d)
            self.tiles += batch.B
            per_dev = np.zeros(self.n_devices, dtype=np.int64)
            per_dev[d] = batch.B
            per_cost = np.zeros(self.n_devices)
            per_cost[d] = cost
            with self._acct_lock:
                _account_devices(self.stats, per_dev, per_cost, batch.T)
            sized = self.capacity is None or self.capacity == "sized"
            # sized, un-routed batches decode on the device (_promote)
            on_device = sized and route is None and \
                listing.decodes_on_device(batch)
            try:
                A, cand, tables = fault_retry.call(
                    lambda: self._stage(batch, d, on_device),
                    policy=self.retry_policy, token="list.stage",
                    on_retry=self._note_retry)
            except fault_retry.RECOVERABLE:
                A = cand = tables = None
                self._note_demotion(self.backend, None)
            if sized:
                hard = None
                if A is not None:
                    try:
                        # async count pass; readiness is probed at
                        # promotion time
                        hard = fault_retry.call(
                            lambda: self._count_pass(A, cand),
                            policy=self.retry_policy,
                            token="list.sizing", on_retry=self._note_retry)
                    except fault_retry.RECOVERABLE:
                        # sizing rung dead: the whole batch is listed on
                        # the host at promotion time (keeps FIFO order)
                        self._note_demotion(self.backend, None)
                self._pending.append((d, batch, (A, cand, hard), route,
                                      tables))
            else:
                if self.capacity == "speculative":  # ratchet guess
                    cap = min(self._cap_ratchet.get(batch.T, SPECULATIVE_CAP0),
                              self.max_capacity)
                else:
                    cap = max(1, int(self.capacity))
                if A is None:
                    out = listing.host_list_triple(batch, self.l)
                else:
                    out = self._launch_list(batch, A, cand, cap)
                self._inflight.append((d, batch, (A, cand), out, route, None))
        if self.stage_times is not None:
            with self._acct_lock:
                self.stage_times["stage"] = (
                    self.stage_times.get("stage", 0.0)
                    + time.perf_counter() - t_stage
                )
        self._promote(block=False)
        if not self.async_staging:
            self._drain()
        else:
            while (
                len(self._pending) + len(self._inflight)
                > self.max_inflight * self.n_devices
            ):
                self._harvest_one()

    def _promote(self, block: bool) -> None:
        """Launch list kernels for pending count-sized batches, strictly
        FIFO (``capacity="sized"`` mode only; the other modes launch in
        ``submit``).

        With ``block=False`` only batches whose count pass already landed
        are promoted; ``block=True`` forces at least the queue head
        through (used when the harvest side runs dry).
        """
        from ..core import listing

        while self._pending:
            d, batch, (A, cand, hard), route, tables = self._pending[0]
            if hard is None:
                # sizing (or staging) already exhausted its ladder in
                # submit: list the batch on the host, keeping FIFO order
                self._pending.popleft()
                out = listing.host_list_triple(batch, self.l)
                self._inflight.append((d, batch, (A, cand), out, route,
                                       None))
                block = False
                continue
            if not block and not _is_ready(hard):
                break
            t0 = time.perf_counter()
            counts = None
            with trace.span("device/sizing", batch=batch.index, B=batch.B,
                            T=batch.T):
                try:
                    counts = np.asarray(hard)  # blocks only until THIS batch
                except fault_retry.RECOVERABLE as exc:
                    # count pass lost in flight: host-list the whole batch
                    self._note_retry(1, exc)
                    self._note_demotion(self.backend, None)
            with self._acct_lock:
                if counts is not None:
                    self.stats.d2h_bytes += engine_jax.fetched_bytes((hard,))
                if self.stage_times is not None:
                    self.stage_times["device"] = (
                        self.stage_times.get("device", 0.0)
                        + time.perf_counter() - t0
                    )
            self._pending.popleft()
            dev = None
            if counts is None:
                out = listing.host_list_triple(batch, self.l)
            else:
                cap = listing.capacity_for(
                    counts, self.max_capacity, policy=self.cap_policy
                )
                with trace.span("device/launch", batch=batch.index,
                                capacity=cap):
                    out = self._launch_list(batch, A, cand, cap)
                    if tables is not None and isinstance(out[0], jax.Array):
                        dev = self._launch_decode(batch, out, tables, counts,
                                                  cap)
            self._inflight.append((d, batch, (A, cand), out, route, dev))
            block = False  # only the head is ever forced

    def _launch_decode(self, batch: pipeline.TileBatch, out, tables, counts,
                       cap: int):
        """Start the device decode of a device rung's triple; None (the
        host decodes the triple) if the launch fails."""
        from ..core import listing

        try:
            return listing.decode_on_device(out, tables, self.l, counts, cap,
                                            batch.B)
        except fault_retry.RECOVERABLE as exc:
            self._note_retry(1, exc)
            return None

    def _decode_job(self, batch: pipeline.TileBatch, acand: tuple,
                    out: tuple, route=None, dev=None) -> None:
        """Run one decode job on the decode worker.

        Where ``_promote`` launched a device decode (``dev``), fetches its
        compact sorted rows, splices in host re-lists of overflowed tiles
        and feeds the sink.  Otherwise blocks for the device triple, then
        either decodes it on the host (``listing.decode_batch``, incl.
        overflow re-lists) and feeds the sink, or -- for routed batches --
        hands the sliced triple to the owning request's ``route``.  Only
        this thread ever touches the sink or ``emitted_cliques`` /
        ``overflowed_tiles``, so FIFO submission == deterministic sink
        order with no further synchronization.

        Resilience: injected harvest faults are absorbed in place; lost
        decoded rows fall back to the triple; a real fetch failure of the
        triple (lost after launch) re-lists the same batch synchronously
        in its FIFO slot, demoting down the ladder to the kernel-order
        host recursion if needed -- so the decoded rows never change."""
        from ..core import listing

        t0 = time.perf_counter()
        # slice off the bucketing padding (zero-candidate lanes) before
        # ratchet/decode -- padding rows count 0 and never overflow
        relaunched = False
        fetched = 0  # device bytes copied to the host for this batch
        rows = None  # the device decode's fetched rows
        with trace.span("device/wait", batch=batch.index):
            fault_retry.consume("device.harvest", on_retry=self._note_retry)
            if dev is not None:
                try:
                    rows = listing.fetch_decoded(dev)
                    bufs, cnt, ovf = None, dev.counts, dev.overflow
                    fetched += engine_jax.fetched_bytes((dev.rows, dev.total))
                except fault_retry.RECOVERABLE as exc:
                    # decoded rows lost: fetch and decode the triple instead
                    self._note_retry(1, exc)
            if rows is None:
                try:
                    bufs, cnt, ovf = (np.asarray(x)[: batch.B] for x in out)
                    fetched += engine_jax.fetched_bytes(out)
                except fault_retry.RECOVERABLE as exc:
                    self._note_retry(1, exc)
                    relaunched = True
                    cap = min(self._cap_ratchet.get(batch.T, SPECULATIVE_CAP0),
                              self.max_capacity)
                    out2 = self._relaunch_sync(batch, cap)
                    try:
                        bufs, cnt, ovf = (np.asarray(x)[: batch.B]
                                          for x in out2)
                        fetched += engine_jax.fetched_bytes(out2)
                    except fault_retry.RECOVERABLE:
                        self._note_demotion(self.backend, None)
                        bufs, cnt, ovf = listing.host_list_triple(batch,
                                                                  self.l)
        if self.capacity == "speculative" or relaunched:
            # the kernel reported true counts, so a too-small guess is
            # retried once on the device at the exact rounded size --
            # identical triples, never a host re-list unless the true
            # count exceeds max_capacity (as in every mode)
            true_cap = listing.capacity_for(
                cnt, self.max_capacity, policy=self.cap_policy
            )
            self._cap_ratchet[batch.T] = max(
                self._cap_ratchet.get(batch.T, 1), true_cap
            )
            if ovf.any() and true_cap > bufs.shape[1]:
                A, cand = acand
                with trace.span("device/relist", B=batch.B, T=batch.T,
                                capacity=true_cap):
                    if relaunched:
                        out2 = self._relaunch_sync(batch, true_cap)
                    else:
                        out2 = self._launch_list(batch, A, cand, true_cap)
                    try:
                        bufs, cnt, ovf = (
                            np.asarray(x)[: batch.B] for x in out2
                        )
                        fetched += engine_jax.fetched_bytes(out2)
                    except fault_retry.RECOVERABLE as exc:
                        self._note_retry(1, exc)
                        self._note_demotion(self.backend, None)
                        bufs, cnt, ovf = listing.host_list_triple(
                            batch, self.l
                        )
                with self._acct_lock:
                    self.stats.emit_retries += 1
        t1 = time.perf_counter()
        fault_retry.consume("decode", on_retry=self._note_retry)
        with trace.span("decode", batch=batch.index, B=batch.B, T=batch.T,
                        routed=route is not None):
            if route is not None:
                emitted = int(route(batch, bufs, cnt, ovf))
            else:
                arr = listing.decode_batch(
                    batch, bufs, cnt, ovf, self.l, self.stats, et_t=self.et_t,
                    device_rows=rows)
                fault_retry.consume("sink.write", on_retry=self._note_retry)
                emitted = self.sink.emit(arr)
        t2 = time.perf_counter()
        with self._acct_lock:
            self.stats.emitted_cliques += emitted
            if rows is not None:
                self.stats.device_decoded_rows += dev.expected
            self.stats.d2h_bytes += fetched
            if self.stage_times is not None:
                st = self.stage_times
                st["device"] = st.get("device", 0.0) + (t1 - t0)
                st["emit"] = st.get("emit", 0.0) + (t2 - t1)

    def emit_rows(self, arr: np.ndarray) -> None:
        """Enqueue host-produced rows (spill tiles) through the decode
        worker, keeping their FIFO position relative to batch decodes."""

        def job() -> None:
            fault_retry.consume("sink.write", on_retry=self._note_retry)
            emitted = self.sink.emit(arr)
            with self._acct_lock:
                self.stats.emitted_cliques += emitted

        self._decoding.append(self._decode_ex.submit(job))

    def _harvest_one(self) -> None:
        if not self._inflight:
            self._promote(block=True)
        _, batch, acand, out, route, dev = self._inflight.popleft()
        # decode + emit run on the decode worker, overlapping device
        # execution AND this thread's submit/promote work
        self._decoding.append(
            self._decode_ex.submit(self._decode_job, batch, acand, out, route,
                                   dev)
        )
        # promote any counts that landed meanwhile, then bound the decode
        # backlog (it holds references to device buffers)
        self._promote(block=False)
        while len(self._decoding) > self._decode_depth:
            self._wait_decode()

    def _wait_decode(self) -> None:
        """Block on the oldest decode job (the backlog's back-pressure)."""
        fut = self._decoding.popleft()
        if fut.done():
            fut.result()
            return
        with trace.span("decode/wait", depth=len(self._decoding) + 1):
            fut.result()

    def _drain(self) -> None:
        while self._pending or self._inflight:
            self._harvest_one()
        while self._decoding:
            self._wait_decode()

    def consume(self, stream, on_spill=None) -> Tuple[int, int]:
        """Emit-mode twin of :meth:`Dispatcher.consume`.

        Pulls from the (possibly parallel-producer) stream, submitting
        packed batches and routing oversize spill tiles to ``on_spill``
        (which must route their rows through :meth:`emit_rows` so stream
        order is preserved).  The stream may interleave several requests
        by wrapping items in :class:`Routed` (see :meth:`submit`); routed
        spills call ``on_spill(tile, route)``.  Stops early once the
        dispatcher-global sink reports ``full`` (per-request early stop
        is the routes' business).  Returns (tiles consumed, max tile
        width).
        """
        stop = None
        if self.sink is not None:
            stop = lambda: self.sink.full  # noqa: E731
        return _consume_stream(self, stream, on_spill, stop=stop)

    def drain(self) -> None:
        """Block until every submitted batch is decoded and delivered.

        The long-lived-service twin of :meth:`finish`: flushes pending
        count passes, in-flight list kernels, and the decode-worker
        backlog (so all routes/sink writes for prior submits have run),
        but keeps the decode worker alive for further ``submit`` calls.
        """
        self._drain()

    def finish(self) -> int:
        """Drain all in-flight batches; returns rows accepted by the sink.

        Shuts down the decode worker -- use :meth:`drain` instead to keep
        the dispatcher serving.  Returns 0 when running sink-less (all
        batches routed).
        """
        from ..kernels import ops as kops

        self._drain()
        self._decode_ex.shutdown(wait=True)
        self.stats.kernel_compile_s += kops.consume_compile_s()
        kops.drain_tune_events(self.stats)
        return 0 if self.sink is None else self.sink.accepted

    def close(self) -> None:
        """Best-effort teardown for error paths: cancel queued decode
        jobs and stop the worker WITHOUT draining devices, so the sink
        stops receiving rows once the caller is handling a failure.

        Queued (never-started) jobs are cancelled, but the one decode job
        the single worker may be running is drained to its row boundary:
        ``shutdown(cancel_futures=True)`` alone would return while that
        job is mid-``sink.emit``, letting the caller tear the sink down
        under a concurrent write (torn row).  Draining the future deque
        is the barrier -- cancelled futures resolve instantly, the
        running one completes its emit first.  Idempotent; a no-op after
        a clean :meth:`finish`."""
        self._decode_ex.shutdown(wait=False, cancel_futures=True)
        while self._decoding:
            fut = self._decoding.popleft()
            try:
                fut.result()
            except concurrent.futures.CancelledError:
                continue
            except Exception:
                # error-path teardown: the primary failure is already
                # being handled by the caller
                pass


def dispatch_scheduled(
    batches: Sequence[pipeline.TileBatch],
    l: int,
    devices: Union[None, int, str, Sequence] = None,
    *,
    mesh: Optional[jax.sharding.Mesh] = None,
    et: bool = True,
    method: str = "auto",
    interpret: Optional[bool] = None,
    backend: Optional[str] = None,
    async_staging: bool = True,
    max_inflight: int = 2,
    stats: Optional[Stats] = None,
    stage_times: Optional[dict] = None,
) -> Tuple[int, dict]:
    """Offline-LPT dispatch of a materialized batch list.

    ``schedule_batches`` LPT-assigns whole batches to ``n_devices`` bins;
    each bin becomes one real device, and bins are drained round-robin so
    every device receives work from the first wave of submissions.
    Returns (total, info) where info carries the scheduler stats plus the
    realized per-batch ``placements``.
    """
    disp = Dispatcher(
        l,
        devices,
        mesh=mesh,
        et=et,
        method=method,
        interpret=interpret,
        backend=backend,
        async_staging=async_staging,
        max_inflight=max_inflight,
        stats=stats,
        stage_times=stage_times,
    )
    if mesh is not None:
        for b in batches:
            disp.submit(b)
        info = {"n_devices": disp.n_devices, "mesh": True}
    else:
        device_bins, sched = schedule_batches(batches, l, disp.n_devices)
        for wave in itertools.zip_longest(*device_bins):
            for d, bi in enumerate(wave):
                if bi is not None:
                    disp.submit(batches[bi], device=d)
        info = dict(sched)
        info["n_devices"] = disp.n_devices
        info["device_bins"] = device_bins
    total = disp.finish()
    info["placements"] = disp.placements
    info["tiles"] = disp.tiles
    return total, info
