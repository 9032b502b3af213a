"""Persistent tuning cache + JAX compilation cache wiring.

One directory (the ``--tune-cache DIR`` knob, or the ``REPRO_TUNE_CACHE``
environment variable) holds the tuning records:
``<dir>/records/<digest>/`` -- one :class:`~repro.tune.records.TuningRecord`
per kernel-signature/geometry key, written atomically through the
format-versioned :mod:`repro.checkpoint.store` writer (tmp dir +
``os.replace`` + ``COMMITTED`` marker).  Concurrent writers of the same
key race benignly: ``os.replace`` is atomic, the last committed record
wins, and a reader never observes a partial write.

JAX's persistent compilation cache is separate
(:func:`enable_compilation_cache`, called by every entry-point script):
it lives where ``JAX_COMPILATION_CACHE_DIR`` says, else at the fixed
:data:`REPO_COMPILE_CACHE` inside the checkout, so every XLA executable
the kernels key on ``(mode, l, T, W, capacity, B)`` is compiled once per
checkout, not once per process.

Read path: an in-process dict in front of the on-disk store.  A corrupt,
stale-format, or foreign record reads as absent -- the caller falls back
to a live microbenchmark and overwrites it.  No record is ever trusted
across a :data:`repro.tune.records.FORMAT` bump, a jax upgrade, or a
device-kind change (all three are part of the key).
"""
from __future__ import annotations

import os
import threading
from typing import Dict, Optional

from . import records as _rec
from ..obs import metrics as _metrics
from .records import TuningRecord

#: environment knob: equivalent to calling :func:`configure` at startup
ENV_TUNE_CACHE = "REPRO_TUNE_CACHE"

#: JAX's own knob for its persistent compilation cache directory
ENV_COMPILE_CACHE = "JAX_COMPILATION_CACHE_DIR"

#: where the compilation cache lives when ``JAX_COMPILATION_CACHE_DIR`` is
#: not set: one fixed path inside the checkout (gitignored), because the
#: path is part of what a later process must find again
REPO_COMPILE_CACHE = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir,
    ".jax_cache"))

_LOCK = threading.Lock()
_DIR: Optional[str] = None
_ENV_CHECKED = False
_MEM: Dict[str, TuningRecord] = {}


def configure(directory: Optional[str], *, xla_cache: bool = True) -> None:
    """Activate a persistent tune-cache directory for this process.

    With ``xla_cache`` (the default) the persistent compilation cache is
    turned on too (:func:`enable_compilation_cache`; its directory does
    not depend on ``directory``).  ``xla_cache=False`` skips it, for tests
    that must not mutate global jax config.  ``None`` deactivates the
    on-disk record layer (the in-process dict survives).
    """
    global _DIR, _ENV_CHECKED
    with _LOCK:
        _ENV_CHECKED = True  # explicit configure beats the env knob
        if directory is None:
            _DIR = None
            return
        directory = os.path.abspath(directory)
        os.makedirs(os.path.join(directory, "records"), exist_ok=True)
        _DIR = directory
    if xla_cache:
        enable_compilation_cache()


def active_dir() -> Optional[str]:
    """The configured cache directory, consulting ``REPRO_TUNE_CACHE`` once."""
    global _ENV_CHECKED
    with _LOCK:
        if _DIR is not None or _ENV_CHECKED:
            return _DIR
        _ENV_CHECKED = True
    env = os.environ.get(ENV_TUNE_CACHE)
    if env:
        configure(env)
    return _DIR


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the
    cache, as JAX reads it itself, and no other is set.  Otherwise the
    cache goes to :data:`REPO_COMPILE_CACHE`.  Either way the size and
    compile-time thresholds drop to zero, so even the small fixed-shape
    clique kernels persist (by default JAX only caches compiles > 1s).
    Idempotent, and safe to call after backend initialization -- the
    cache is consulted per compile, not at startup.
    """
    import jax

    directory = os.environ.get(ENV_COMPILE_CACHE) or REPO_COMPILE_CACHE
    os.makedirs(directory, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return directory


def clear_memory() -> None:
    """Drop the in-process record layer (tests; disk records survive)."""
    with _LOCK:
        _MEM.clear()


# ---------------------------------------------------------------------------
# tuning-event accounting (drained into Stats.tune_s / tune_cache_hit)
# ---------------------------------------------------------------------------

_TUNE_S = 0.0
_TUNE_LOOKUPS = 0
_TUNE_MISSES = 0


def note_event(seconds: float = 0.0, lookup: bool = False,
               miss: bool = False) -> None:
    """Accrue one tuning event (same pattern as ops' compile accumulator).

    ``lookup`` marks a record consultation that *answered* from a cache
    layer; ``miss`` marks one that had to fall back to a live measurement
    (whose wall-clock lands in ``seconds``).  Geometry reads that find no
    record note nothing -- an untuned run is not a cache miss.
    """
    global _TUNE_S, _TUNE_LOOKUPS, _TUNE_MISSES
    with _LOCK:
        _TUNE_S += seconds
        if lookup:
            _TUNE_LOOKUPS += 1
        if miss:
            _TUNE_MISSES += 1
    if seconds:
        _metrics.REGISTRY.counter(
            "repro_tune_seconds_total",
            help="wall seconds spent in live tuning measurements",
        ).inc(seconds)
    if lookup:
        _metrics.REGISTRY.counter(
            "repro_tune_lookups_total",
            help="tuning-record consultations answered from a cache layer",
        ).inc()
    if miss:
        _metrics.REGISTRY.counter(
            "repro_tune_misses_total",
            help="tuning lookups that fell back to a live measurement",
        ).inc()


def consume_events() -> tuple:
    """Drain the accumulator -> ``(tune_s, lookups, misses)``.

    Engines call this where they drain ``ops.consume_compile_s`` and derive
    ``Stats.tune_cache_hit = lookups > 0 and misses == 0``.
    """
    global _TUNE_S, _TUNE_LOOKUPS, _TUNE_MISSES
    with _LOCK:
        out = (_TUNE_S, _TUNE_LOOKUPS, _TUNE_MISSES)
        _TUNE_S, _TUNE_LOOKUPS, _TUNE_MISSES = 0.0, 0, 0
    return out


def _record_dir(base: str, key: str) -> str:
    return os.path.join(base, "records", _rec.key_digest(key))


def get(key: str) -> Optional[TuningRecord]:
    """Record for ``key``: in-process layer, then the on-disk store.

    Any unreadable / stale-format / wrong-key record reads as None -- the
    caller re-measures and overwrites.  Never raises.
    """
    with _LOCK:
        got = _MEM.get(key)
    if got is not None:
        return got
    base = active_dir()
    if base is None:
        return None
    from ..checkpoint import store

    from ..resilience import inject

    try:
        inject.fire("tune.read")
        ckpt = store.restore_checkpoint(_record_dir(base, key),
                                        _corrupt_site="tune.read")
    except Exception:
        return None  # corrupt on-disk record: fall back to live measurement
    if ckpt is None:
        return None
    rec = TuningRecord.from_meta(ckpt.get("metadata"))
    if rec is None or rec.key() != key:
        return None
    with _LOCK:
        _MEM[key] = rec
    return rec


def put(rec: TuningRecord) -> None:
    """Persist one record (atomic, best-effort) and cache it in-process.

    Uses the checkpoint store's commit protocol; a concurrent writer of
    the same key is resolved by ``os.replace`` (last committed wins).
    Disk errors are swallowed -- the tuning cache is an accelerator, never
    a correctness dependency.
    """
    import numpy as np

    key = rec.key()
    with _LOCK:
        _MEM[key] = rec
    base = active_dir()
    if base is None:
        return
    from ..checkpoint import store

    try:
        store.save_checkpoint(
            _record_dir(base, key), 0,
            {"format": np.int64(_rec.FORMAT)}, metadata=rec.to_meta())
    except OSError:
        pass  # lost a same-key race or a full disk; next process re-measures
