"""Backend registry + jit'd dispatch wrappers over the clique kernels.

Every kernel family (counting, listing, triangles, edge candidates) is
served by one of several interchangeable backends:

* ``"pallas"`` -- the Pallas kernels (:mod:`repro.kernels.clique_count` /
  :mod:`repro.kernels.clique_list`): compiled Mosaic on TPU, interpret
  mode elsewhere (the kernel body executes in Python -- correct but slow;
  CPU CI uses it as the reference implementation of the device path).
* ``"lax"`` -- the compiled :mod:`repro.kernels.lax_backend`: the same
  word-wise bitset DFS expressed in pure ``jax.lax`` and jit-compiled to
  native XLA:CPU/GPU code.  Byte-identical outputs, no interpreter.
* ``"ref"`` -- the pure-jnp expansion oracles (:mod:`repro.kernels.ref`,
  counting only; memory O(B * T^(l-2)), tests/cross-checks).
* ``"auto"`` (default) -- lax on every platform.  On a TPU the Mosaic
  lowering refuses the Pallas kernels (see :func:`compiling_backends`),
  and off a TPU Pallas only runs in interpret mode.
* ``"autotune"`` -- per-(device kind, mode, l, T, capacity bucket)
  microbenchmark between the backends that compile for the device
  (:func:`compiling_backends`).  Winners are cached
  in-process and, when a tune-cache directory is configured
  (:mod:`repro.tune.cache`), persisted across processes as
  :class:`~repro.tune.records.TuningRecord` files -- a warm process never
  re-measures.

Selection precedence: explicit ``backend=`` argument > the
``REPRO_BACKEND`` environment variable (read per call; lets CI flip the
whole suite without touching call sites) > the deprecated ``interpret=``
alias (``interpret=True/False`` selects the Pallas backend with that
interpret flag, the pre-registry API) > ``"auto"``.  Inside an autotune
resolution the same ladder continues: a concrete ``REPRO_BACKEND`` value
beats a persisted record beats the live microbenchmark -- so the env knob
overrides stale tuning state even when a call site pins
``backend="autotune"``.

The module also accounts kernel compile time: the first invocation per
(function, backend, shape) signature is timed synchronously and accrued to
a process-wide counter that engines drain into ``Stats.kernel_compile_s``
via :func:`consume_compile_s`.
"""
from __future__ import annotations

import os
import time
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from . import clique_count as _cc
from . import clique_list as _cl
from . import intersect as _is
from . import lax_backend as _lax
from . import triangle_mm as _tm
from . import ref as _ref
from ..obs import profile as obs_profile
from ..obs import trace

BACKENDS = ("auto", "pallas", "lax", "ref", "autotune")

#: env var consulted when no explicit ``backend=`` is passed
BACKEND_ENV = "REPRO_BACKEND"

#: in-process autotune winners, keyed (device_kind, mode, l, T, cap_bucket)
#: -- the capacity bucket and device kind are part of the key (PR-6 fix):
#: a winner measured at one capacity regime or on one device kind is never
#: served to another
_AUTOTUNE_CACHE: Dict[Tuple[str, str, int, int, int], str] = {}
_COMPILE_S = 0.0
_SEEN_SIGNATURES = set()


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def compiling_backends() -> Tuple[str, ...]:
    """Kernel backends that compile for the default device, best first.

    On a TPU the Mosaic lowering refuses every Pallas kernel: the
    ``(1, W)`` candidate blocks and rank-1 output blocks break the
    (8, 128) block tiling, popcount sums reduce over unsigned integers,
    and the explicit DFS stack is written by scatter.  Until the kernels
    are relaid out, ``lax`` is the only backend there.  Elsewhere Pallas
    runs in interpret mode, which compiles but is slow.
    """
    if jax.default_backend() == "tpu":
        return ("lax",)
    return ("lax", "pallas")


def resolve_backend(backend: Optional[str] = None,
                    interpret: Optional[bool] = None) -> str:
    """Resolve the backend knob to a registry name (see module docstring).

    ``"auto"`` resolves to the first of :func:`compiling_backends`;
    ``"autotune"`` is returned as-is (the per-shape winner is only known
    once l and T are).  An explicit ``"pallas"`` is returned on every
    platform: on a TPU its launch then raises the lowering's own error.
    """
    for cand in (backend, os.environ.get(BACKEND_ENV) or None):
        if cand is None:
            continue
        if cand not in BACKENDS:
            raise ValueError(
                f"unknown kernel backend {cand!r}; expected one of {BACKENDS}")
        if cand != "auto":
            return cand
        break  # explicit "auto": skip the interpret alias
    else:
        if interpret is not None:
            return "pallas"  # deprecated alias: pin the Pallas kernels
    return compiling_backends()[0]


def autotune_backend(mode: str, l: int, T: int,
                     capacity: Optional[int] = None,
                     trials: int = 2) -> str:
    """Backend winner for one kernel signature, cheapest source first.

    Resolution ladder (the tail of the module-docstring precedence):

    1. a *concrete* ``REPRO_BACKEND`` value -- the env knob overrides any
       cached or persisted winner, even under an explicit
       ``backend="autotune"`` argument;
    2. the in-process winner cache, keyed
       ``(device_kind, mode, l, T, capacity bucket)``;
    3. a persisted :class:`~repro.tune.records.TuningRecord` from the
       configured tune-cache directory (cross-process warm start);
    4. the live microbenchmark over :func:`compiling_backends`
       (:func:`repro.tune.search.microbench_backend`), whose winner is
       written back through layers 2-3.

    Lookups and microbenchmark seconds accrue to the tuning-event
    accumulator (:func:`repro.tune.cache.note_event`) that engines drain
    into ``Stats.tune_s`` / ``tune_cache_hit``.
    """
    global _COMPILE_S
    env = os.environ.get(BACKEND_ENV) or None
    if env is not None and env in BACKENDS and env not in ("auto", "autotune"):
        return env
    from .. import tune
    from ..tune import search as tune_search

    key = (tune.device_kind(), mode, int(l), int(T),
           tune.capacity_bucket(capacity if mode == "list" else None))
    got = _AUTOTUNE_CACHE.get(key)
    if got is not None:
        tune.note_event(lookup=True)
        trace.instant("tune/cache_hit", source="memory", mode=mode, T=T)
        return got
    rkey = tune.backend_key(mode, l, T,
                            capacity if mode == "list" else None)
    rec = tune.get(rkey)
    if rec is not None and rec.data.get("winner") in compiling_backends():
        best = rec.data["winner"]
        _AUTOTUNE_CACHE[key] = best
        tune.note_event(lookup=True)
        trace.instant("tune/cache_hit", source="record", mode=mode, T=T)
        return best
    # park compile seconds accrued by earlier *real* kernel calls so the
    # drain below discards only the microbenchmark's own compiles
    pending = consume_compile_s()
    t0 = time.perf_counter()
    with trace.span("tune/microbench", mode=mode, l=l, T=T, trials=trials):
        best, times = tune_search.microbench_backend(mode, l, T,
                                                     capacity=capacity,
                                                     trials=trials)
    tune_s = time.perf_counter() - t0
    # the microbenchmark compiled both candidates through the registry;
    # drain those first-call seconds so they are not billed to whatever
    # engine query happened to trigger the autotune, then restore the
    # parked pre-autotune accrual
    consume_compile_s()
    _COMPILE_S += pending
    _AUTOTUNE_CACHE[key] = best
    tune.note_event(seconds=tune_s, lookup=True, miss=True)
    tune.put(tune.TuningRecord(
        "backend", key[0], tune.jax_version(), mode, int(l), T=int(T),
        W=int(T) // 32,
        cap_bucket=tune.capacity_bucket(capacity if mode == "list" else None),
        data={"winner": best, "times": times, "trials": trials,
              "tune_s": tune_s}))
    return best


def clear_autotune_cache() -> None:
    """Drop in-process autotune winners (persisted records survive)."""
    _AUTOTUNE_CACHE.clear()


def consume_compile_s() -> float:
    """Drain the first-call (compile + first run) seconds accumulator."""
    global _COMPILE_S
    v, _COMPILE_S = _COMPILE_S, 0.0
    return v


def consume_tune_events() -> tuple:
    """Drain tuning events -> ``(tune_s, lookups, misses)``.

    Engines call this next to :func:`consume_compile_s` and derive
    ``Stats.tune_cache_hit = lookups > 0 and misses == 0``.
    """
    from .. import tune

    return tune.consume_events()


def drain_tune_events(stats) -> None:
    """Drain tuning events into a ``Stats`` at an engine drain point.

    A drain that saw no events leaves ``tune_cache_hit`` untouched -- the
    engines and the dispatchers share one Stats and both drain, so only
    the drain that actually collected the query's lookups gets to decide
    the flag (hit = every lookup answered from a cache layer).
    """
    tune_s, lookups, misses = consume_tune_events()
    stats.tune_s += tune_s
    if lookups or misses:
        stats.tune_cache_hit = misses == 0


def _arg_device(x) -> str:
    try:
        return ",".join(sorted(str(d) for d in x.devices()))
    except Exception:
        return "host"


def _timed_first_call(key: tuple, fn, *args):
    """Time the first call per signature into the compile accumulator.

    Inside a jit trace (tracer arguments) timing is skipped -- the caller
    (e.g. the dispatcher's per-device jit) accounts its own compile.
    """
    global _COMPILE_S
    if any(isinstance(a, jax.core.Tracer) for a in args):
        return fn(*args)
    key = key + (_arg_device(args[0]),)
    if key in _SEEN_SIGNATURES:
        return fn(*args)
    sig = "/".join(str(p) for p in key)
    t0 = time.perf_counter()
    with trace.span("kernel/compile", sig=sig):
        out = jax.block_until_ready(fn(*args))
    dt = time.perf_counter() - t0
    _COMPILE_S += dt
    obs_profile.note_kernel(sig, compile_s=dt)
    _SEEN_SIGNATURES.add(key)
    return out


def count_tiles(A: jax.Array, cand: jax.Array, l: int,
                method: str = "auto", backend: Optional[str] = None,
                interpret: Optional[bool] = None) -> jax.Array:
    """Count l-cliques per tile. (B,T,W) uint32 x (B,W) uint32 -> (B,) uint32.

    ``method``: "auto" routes the Pallas backend's l==3 to the MXU matmul
    kernel and other l to the bitset DFS kernel; "dfs" / "mxu" force a
    Pallas kernel path; "ref" forces the expansion oracle.  ``backend``
    selects the implementation family (see module docstring); ``interpret``
    is the deprecated pre-registry alias for ``backend="pallas"``.
    """
    T = A.shape[1]
    b = resolve_backend(backend, interpret)
    if method == "ref" or b == "ref":
        return _ref.clique_count_tiles_ref(A, cand, l)
    if l <= 2:
        # closed forms, no kernel needed on any backend
        return _ref.clique_count_tiles_ref(A, cand, l)
    if b == "autotune":
        b = autotune_backend("count", l, T)  # counting: capacity n/a
    if b == "lax" and method == "auto":
        return _timed_first_call(("count", "lax", l, A.shape),
                                 lambda a, c: _lax.count_tiles(a, c, l),
                                 A, cand)
    # Pallas family (or an explicit method= kernel pin)
    itp = _auto_interpret(interpret)
    if method == "mxu" or (method == "auto" and l == 3):
        if l != 3:
            raise ValueError("mxu path implements the l==3 base case only")
        return _timed_first_call(
            ("tri", "pallas", itp, A.shape),
            lambda a, c: _tm.triangle_count_tiles(a, c, interpret=itp),
            A, cand)
    return _timed_first_call(
        ("count", "pallas", itp, l, A.shape),
        lambda a, c: _cc.clique_count_tiles(a, c, l, interpret=itp),
        A, cand)


def list_tiles(A: jax.Array, cand: jax.Array, l: int, capacity: int,
               backend: Optional[str] = None,
               interpret: Optional[bool] = None):
    """List l-cliques per tile into fixed-capacity local-id buffers.

    (B,T,W) uint32 x (B,W) uint32 -> (out (B,capacity,l) int32,
    count (B,) uint32 true totals, overflow (B,) uint32).  Overflowed
    tiles keep the true count but only the first ``capacity`` cliques;
    callers must route them to the host spill path, never truncate.
    Buffers are byte-identical across backends.
    """
    b = resolve_backend(backend, interpret)
    if b == "ref":
        raise ValueError("the ref backend implements counting only")
    if b == "autotune":
        b = autotune_backend("list", l, A.shape[1], capacity=capacity)
    if b == "lax":
        return _timed_first_call(
            ("list", "lax", l, capacity, A.shape),
            lambda a, c: _lax.list_tiles(a, c, l, capacity),
            A, cand)
    itp = _auto_interpret(interpret)
    return _timed_first_call(
        ("list", "pallas", itp, l, capacity, A.shape),
        lambda a, c: _cl.clique_list_tiles(a, c, l, capacity, interpret=itp),
        A, cand)


def triangles(A: jax.Array, cand: jax.Array,
              interpret: Optional[bool] = None) -> jax.Array:
    return _tm.triangle_count_tiles(A, cand,
                                    interpret=_auto_interpret(interpret))


def edge_candidates(A: jax.Array, pairs: jax.Array,
                    interpret: Optional[bool] = None):
    return _is.edge_candidates(A, pairs, interpret=_auto_interpret(interpret))
