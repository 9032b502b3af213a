"""Unified observability layer: tracing, metrics, logging, profiling.

``repro.obs`` sits at the bottom of the import DAG (stdlib only; jax is
imported lazily by ``trace.configure(enabled=True)``), so every other tier --
``core``, ``runtime``, ``serve``, ``tune``, the launchers and benchmarks
-- can instrument itself without new dependencies or cycles.

Quickstart::

    from repro.obs import trace, metrics

    trace.configure(enabled=True)
    with trace.span("pack", T=64):
        ...
    trace.export("trace.json")          # open in https://ui.perfetto.dev

    # while tracing is on, spans also land in a running jax.profiler
    # session, as ``repro/<name>`` annotations

    metrics.REGISTRY.counter("repro_batches_total").inc()

See DESIGN.md section 11 for the span taxonomy, metric naming convention
and overhead budget.
"""

from . import export, logging, metrics, profile, trace
from .logging import get_logger, setup_logging
from .metrics import REGISTRY, get_registry

__all__ = [
    "trace",
    "metrics",
    "export",
    "profile",
    "logging",
    "setup_logging",
    "get_logger",
    "REGISTRY",
    "get_registry",
]
