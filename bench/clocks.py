"""The program's stage clocks, for readers of metrics newer than a program.

A stage clock that a job's program did not keep (an older program) makes
the reader find nothing, so the metric is left out of the result line;
``bench.readers.stage`` would read it as 0.
"""
from __future__ import annotations

from bench.readers import per_job


def clock(run, mode: str, name: str):
    """Mean over the window's ``mode`` jobs of their stage clock ``name``."""
    if any(name not in j.stages for j in run.jobs):
        return None
    return per_job(run, mode, lambda j: j.stages[name])
