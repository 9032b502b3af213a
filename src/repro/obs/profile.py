"""Kernel compile attribution.

``kernels/ops.py`` and the dispatchers report each kernel signature's
first-call compile time (op, l, T, B, backend) here; it flows to the
metrics registry as the labelled counter
``repro_kernel_compile_seconds_total``.  Device time per kernel comes
from a ``jax.profiler`` trace, not from host clocks: with tracing on,
the program's spans land in that trace too (``repro.obs.trace``).
"""

from __future__ import annotations

from typing import Optional

from .metrics import REGISTRY, Registry

__all__ = ["note_kernel"]


def note_kernel(
    sig: str,
    compile_s: float,
    registry: Optional[Registry] = None,
) -> None:
    """Add one first-call compile time to the signature's counter."""
    (registry or REGISTRY).counter(
        "repro_kernel_compile_seconds_total",
        help="first-call compile time per kernel signature",
        sig=sig,
    ).inc(compile_s)
