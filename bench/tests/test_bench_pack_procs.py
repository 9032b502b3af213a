"""The readers of pack_in_procs.count and pack_in_procs.list: the share of
a job's packed batches that a worker process packed, from the program's
Stats.pack_proc_batches and Stats.packed_batches."""
from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import main as bench_main  # noqa: E402

READ = {mode: bench_main.reader(f"pack_in_procs.{mode}")
        for mode in ("count", "list")}


def run_of(mode, jobs):
    """A run whose jobs' Stats carry ``(procs, packed)`` (None: a program
    without the counters, as the parent runs)."""
    def job(counts):
        stats = types.SimpleNamespace()
        if counts is not None:
            stats.pack_proc_batches, stats.packed_batches = counts
        return types.SimpleNamespace(stats=stats, rows=0, stages={})
    return types.SimpleNamespace(mode=mode, jobs=[job(c) for c in jobs],
                                 trace=None)


@pytest.mark.parametrize("mode", ["count", "list"])
@pytest.mark.parametrize("jobs, want", [(((300, 300), (300, 300)), 100.0),
                                        (((300, 300), (150, 300)), 75.0),
                                        (((0, 300),), 0.0),
                                        (((0, 0),), 0.0)])
def test_reads_the_share_packed_in_processes(mode, jobs, want):
    assert READ[mode](run_of(mode, jobs)) == pytest.approx(want)


@pytest.mark.parametrize("mode", ["count", "list"])
@pytest.mark.parametrize("jobs, run_mode", [((None, None), None),
                                            (((300, 300), None), None),
                                            (((300, 300),), "other"),
                                            ((), None)],
                         ids=["parent", "one-job-without", "other-mode",
                              "no-jobs"])
def test_reads_nothing_without_the_counters(mode, jobs, run_mode):
    other = "list" if mode == "count" else "count"
    run = run_of(other if run_mode == "other" else mode, jobs)
    assert READ[mode](run) is None


@pytest.mark.parametrize("workers", [0, 2], ids=["inline", "processes"])
def test_reads_a_count_job_of_the_program(workers):
    """Stats of a real (CPU) count job carry the counters: 100% where the
    stream packs in worker processes, 0% where it packs inline."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core import engine_jax
    from repro.data import rmat_graph

    g = rmat_graph(8, 4, seed=7)
    res = engine_jax.count(g, 5, devices=1, batch_size=8,
                           pack_workers=workers, plan_cache=False)
    job = types.SimpleNamespace(stats=res.stats, rows=0, stages={})
    assert res.stats.packed_batches > 1
    run = types.SimpleNamespace(mode="count", jobs=[job], trace=None)
    assert READ["count"](run) == pytest.approx(100.0 if workers else 0.0)
