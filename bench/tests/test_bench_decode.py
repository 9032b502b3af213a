"""The reader of decoded_on_device.list: the share of listed rows that the
device decoded, from the program's Stats.device_decoded_rows."""
from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import main as bench_main  # noqa: E402

READ = bench_main.reader("decoded_on_device.list")


def list_run(decoded, rows=1000, mode="list"):
    """A list run of two jobs whose Stats carry ``decoded`` (None: a
    program without the counter, as the parent runs)."""
    def job(d):
        stats = types.SimpleNamespace(d2h_bytes=16 * rows)
        if d is not None:
            stats.device_decoded_rows = d
        return types.SimpleNamespace(stats=stats, rows=rows, stages={})
    return types.SimpleNamespace(mode=mode, jobs=[job(d) for d in decoded],
                                 trace=None)


@pytest.mark.parametrize("decoded, want", [((1000, 1000), 100.0),
                                           ((1000, 500), 75.0),
                                           ((0, 0), 0.0)])
def test_reads_the_share_decoded_on_the_device(decoded, want):
    assert READ(list_run(decoded)) == pytest.approx(want)


@pytest.mark.parametrize("run", [list_run((None, None)),
                                 list_run((1000, None)),
                                 list_run((1000, 1000), mode="count"),
                                 types.SimpleNamespace(mode="list", jobs=[],
                                                       trace=None)],
                         ids=["parent", "one-job-without", "count", "no-jobs"])
def test_reads_nothing_without_the_counter(run):
    assert READ(run) is None


def test_reads_a_listing_job_of_the_program():
    """Stats of a real (CPU) listing job carry the counter: every row of a
    sized listing is decoded on the device."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core import listing
    from repro.data import rmat_graph

    g = rmat_graph(7, 4, seed=3)
    sink = listing.ArraySink(4)
    res = listing.stream_cliques(g, 4, sink, devices=1, plan_cache=False)
    job = types.SimpleNamespace(stats=res.stats, rows=len(sink.result()),
                                stages={})
    assert job.rows > 0
    run = types.SimpleNamespace(mode="list", jobs=[job], trace=None)
    assert READ(run) == pytest.approx(100.0)
