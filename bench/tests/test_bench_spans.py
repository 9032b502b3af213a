"""Idle gaps named by the program's spans, and the readers of the program's clocks."""
from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import main as bench_main  # noqa: E402
from bench import spans, trace_reduce  # noqa: E402
from bench.tests.test_bench_trace import RECORDED, SYNTHETIC, event, meta  # noqa: E402

# Window 100..1100 us, one count job over 150..1050 on the dispatching
# line, whose program spans are pack/wait 160..240 and 760..1000, stage
# 240..260, harvest 350..650 and combine 650..700.  A sink on the decode
# line over 600..800.  A pack worker's spans over 100..1100 and 500..560
# must never label a gap.  Device 0 runs ops at 200..300, 250..400,
# 700..750 and 1050..1200; device 1 at 0..150.
PROGRAM = f"""
planes {{ id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {event(1, 100, 1000)} {event(2, 150, 900)} {event(4, 160, 80)}
    {event(5, 240, 20)} {event(6, 350, 300)} {event(7, 650, 50)}
    {event(4, 760, 240)} }}
  lines {{ id: 2 name: "decode" timestamp_ns: 0 {event(3, 600, 200)} }}
  lines {{ id: 3 name: "pack" timestamp_ns: 0
    {event(8, 100, 1000)} {event(8, 500, 60)} }}
  {meta(["bench/window", "bench/job:count", "bench/sink", "repro/pack/wait",
         "repro/device/stage", "repro/device/harvest", "repro/combine",
         "repro/pack"])}
}}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {event(1, 200, 100)} {event(2, 250, 150)} {event(1, 700, 50)}
    {event(2, 1050, 150)} }}
  {meta(["fusion.1", "while.2"])}
}}
planes {{ id: 3 name: "/device:TPU:1"
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {event(1, 0, 150)} }}
  {meta(["fusion.1"])}
}}
"""


def from_text(text):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(text)


@pytest.fixture(scope="module")
def reduced():
    return spans.reduce(from_text(PROGRAM))


def test_gaps_take_the_dispatching_threads_innermost_span(reduced):
    # device 0: 100..200 (midpoint 150, before any program span), 400..700
    # (550: harvest, not the worker's 500..560 pack), 750..1050 (900:
    # pack/wait); device 1: 150..1100 (625: the sink, 200 us, is shorter
    # than the harvest, 300 us)
    assert reduced.idle_gaps == pytest.approx({
        "bench/job:count": 100e-6, "repro/device/harvest": 300e-6,
        "repro/pack/wait": 300e-6, "bench/sink": 950e-6})
    assert "repro/pack" not in reduced.idle_gaps


def test_program_span_seconds_on_the_dispatching_thread(reduced):
    assert reduced.jobs == pytest.approx([900e-6])
    assert reduced.span_s == pytest.approx({
        "repro/pack/wait": 320e-6, "repro/device/stage": 20e-6,
        "repro/device/harvest": 300e-6, "repro/combine": 50e-6})
    # spans plus unspanned time add up to the job's wall
    assert reduced.unspanned_s + sum(reduced.span_s.values()) == \
        pytest.approx(900e-6)


def test_the_line_reads_each_layer(reduced):
    line = reduced.line()
    assert line["pack_wait_s"] == pytest.approx(320e-6)
    assert line["stage_s"] == pytest.approx(20e-6)
    assert line["combine_s"] == pytest.approx(50e-6)
    assert line["harvest_s"] == pytest.approx(300e-6)
    # harvest 350..650 against device 0's op up to 400: 400..650 idle;
    # device 1 idles the whole span; the mean over the chips
    assert line["harvest_idle_s"] == pytest.approx((250e-6 + 300e-6) / 2)
    assert line["bare_job_idle_share"] == pytest.approx(100 / 1650)
    assert line["idle_gaps"][0] == ["bench/sink", pytest.approx(950e-6)]


@pytest.mark.parametrize("text", [SYNTHETIC, PROGRAM.replace("repro/", "other/")],
                         ids=["trace_reduce", "no-program-spans"])
def test_without_program_spans_the_gaps_are_trace_reduces(text):
    got = spans.reduce(from_text(text))
    want = trace_reduce.reduce(from_text(text))
    assert got.window_s == want.window_s
    assert got.idle_gaps == pytest.approx(dict(want.breakdown()["idle_gaps"]))
    assert got.span_s == {}


def test_recorded_trace_reads_as_before():
    """The harness's recorded CPU trace: no device, no program spans."""
    got = spans.reduce(RECORDED)
    want = trace_reduce.reduce(RECORDED)
    assert got.window_s == want.window_s and len(got.jobs) == 1
    assert got.idle_gaps == {} == dict(want.breakdown()["idle_gaps"])
    assert got.harvest_idle_s is None and got.span_s == {}


def clock_run(mode, stages, d2h=None):
    stats = types.SimpleNamespace(d2h_bytes=d2h) if d2h is not None else None
    job = types.SimpleNamespace(stages=stages, stats=stats, rows=1000)
    return types.SimpleNamespace(mode=mode, jobs=[job, job], trace=None)


@pytest.mark.parametrize("mode", ["count", "list"])
def test_clock_readers(mode):
    run = clock_run(mode, {"pack_wait": 1.5, "stage": 0.25, "combine": 2.0,
                           "device": 1.0}, d2h=8000)
    assert bench_main.reader(f"pack_wait_s.{mode}")(run) == 1.5
    assert bench_main.reader(f"stage_s.{mode}")(run) == 0.25
    other = "list" if mode == "count" else "count"
    assert bench_main.reader(f"pack_wait_s.{other}")(run) is None
    if mode == "count":
        assert bench_main.reader("combine_s.count")(run) == 2.0
        assert bench_main.reader("d2h_bytes_per_row.list")(run) is None
    else:
        assert bench_main.reader("d2h_bytes_per_row.list")(run) == 8.0


@pytest.mark.parametrize("name", ["pack_wait_s.count", "stage_s.count",
                                  "stage_s.list", "d2h_bytes_per_row.list"])
def test_a_program_without_the_clock_reads_nothing(name):
    run = clock_run(name.split(".")[1], {"device": 1.0, "combine": 2.0})
    assert bench_main.reader(name)(run) is None
