"""The four-chip count cell: its configuration, its place in the benchmark,
and the two placement readers on fake runs."""
from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import main as bench_main  # noqa: E402
from bench.trace_reduce import Reduced  # noqa: E402

SPEC = bench_main.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "g500-count-k5-4chip"
CONFIG = "graph500-s16-ef16-4chip"
READERS = ("chip_busy_spread.count", "placement_spread.count")


def config(name):
    entry = next(c for c in SPEC["configs"] if c["name"] == name)
    return entry, bench_main.load_json(os.path.join(ROOT, entry["file"]))


def fake_run(busy, cost, *, mode="count", traced=True, chips=4):
    """A run with one device plane per entry of ``busy`` and two jobs whose
    Stats carry ``cost`` as device_cost (``cost=None``: no counter)."""
    trace = None
    if traced:
        trace = Reduced(window_s=10.0, devices={
            d: types.SimpleNamespace(busy_s=b, modules={}, ops={}, gaps=[])
            for d, b in enumerate(busy)})
    stats = None if cost is None else types.SimpleNamespace(device_cost=cost)
    job = types.SimpleNamespace(answer=7, stats=stats, stages={}, wall_s=5.0,
                                rows=0)
    return types.SimpleNamespace(mode=mode, chips=chips, jobs=[job, job],
                                 window_s=10.0, setup_s=30.0,
                                 setup_stats=None, trace=trace)


BALANCED = {0: 10.0, 1: 10.0, 2: 10.0, 3: 10.0}


@pytest.mark.parametrize("case, busy, cost, kw, want", [
    ("balanced", [1.0] * 4, BALANCED, {}, (0.0, 0.0)),
    ("one hot chip", [1.0, 1.0, 1.0, 3.0], {0: 10.0, 1: 10.0, 2: 10.0, 3: 30.0},
     {}, (100.0, 100.0)),
    ("a chip with no work", [1.0, 1.0, 1.0, 0.0], {0: 10.0, 1: 10.0, 2: 10.0},
     {}, (100.0 / 3, 100.0 / 3)),
    ("one device", [1.0], {0: 40.0}, {"chips": 1}, (None, None)),
    ("untraced", [1.0] * 4, BALANCED, {"traced": False}, (None, 0.0)),
    ("list mode", [1.0] * 4, BALANCED, {"mode": "list"}, (None, None)),
    ("jobs without the counter", [1.0] * 4, None, {}, (0.0, None)),
    ("an empty counter", [1.0] * 4, {}, {}, (0.0, None)),
])
def test_placement_readers(case, busy, cost, kw, want):
    run = fake_run(busy, cost, **kw)
    for name, expected in zip(READERS, want):
        got = bench_main.reader(name)(run)
        if expected is None:
            assert got is None, (case, name)
        else:
            assert got == pytest.approx(expected), (case, name)


def test_configuration_is_the_one_chip_graph_on_four_chips():
    entry, cfg = config(CONFIG)
    _, one = config("graph500-s16-ef16")
    for key in ("generator", "params", "graph_seed", "published"):
        assert cfg[key] == one[key], key
    assert cfg["chips"] == 4
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == ["scale"]
    assert set(one["guarantees"]) < set(cfg["guarantees"])
    assert len(entry["source"]) <= 200 and "\n" not in entry["source"]


def test_the_cell_is_the_only_four_chip_cell():
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in four] == [CELL]
    assert four[0]["config"] == CONFIG
    assert four[0]["traffic"] == "count-k5-all"
    assert len(four) <= max(1, len(SPEC["workloads"]) // 2)
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    _, _, traffic = bench_main.find_cell(SPEC, CELL)
    assert traffic["devices"] == "all" and traffic["mode"] == "count"
    e2e = {m["name"] for m in bench_main.metrics_for(SPEC, cell, trace=False)}
    assert e2e == {"count_job_s", "setup_s"}
    layer = {m["name"] for m in bench_main.metrics_for(SPEC, cell, trace=True)}
    assert layer == set(READERS)
