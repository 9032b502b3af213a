"""Name the host step behind every idle gap on the chip, from the program's spans.

    python3 bench/spans.py --workload <name> --seed <n> --seconds <s>

Runs one cell as ``bench/main.py --trace 1`` does, with the program's span
tracer on (``repro.obs.trace.configure(enabled=True)``), so that inside the
profiler session every span is also a host annotation named
``repro/<span name>``.  After the run's own result line it prints one
JSON line that :func:`reduce` reads from the trace; ``--trace-out`` also
keeps the raw trace.

The dispatching thread is the host line that holds ``bench/job:<mode>``:
the thread that launches device work.  Each idle gap on a device is
labelled with the innermost span open at its midpoint, chosen from the
``bench/`` annotations on any thread (as ``trace_reduce`` does) together
with the ``repro/`` spans on the dispatching thread; a pack worker's or
the decode worker's spans never label a gap.  On a trace without
``repro/`` spans the labels are exactly ``trace_reduce``'s.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import os
import sys
import time
from typing import Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import trace_reduce  # noqa: E402
from bench.trace_reduce import Interval  # noqa: E402

PROGRAM = "repro/"
JOB = "bench/job:"


@dataclasses.dataclass
class Spans:
    """What the program's spans on the dispatching thread show (seconds)."""
    window_s: float
    jobs: List[float]            # wall of each job inside the window
    span_s: Dict[str, float]     # repro/ span seconds on the dispatching thread
    unspanned_s: float           # job time no repro/ span covers there
    idle_gaps: Dict[str, float]  # idle seconds by label, summed over devices
    harvest_idle_s: Optional[float]  # idle inside device/harvest, chip mean

    def per_job(self, name: str) -> Optional[float]:
        """Seconds of the ``repro/<name>`` spans per job (None without jobs)."""
        if not self.jobs:
            return None
        return self.span_s.get(PROGRAM + name, 0.0) / len(self.jobs)

    def line(self) -> dict:
        """The JSON line ``main`` prints."""
        n = max(1, len(self.jobs))
        idle = sum(self.idle_gaps.values())
        bare = sum(s for k, s in self.idle_gaps.items() if k.startswith(JOB))
        return {
            "spans": True,
            "jobs": len(self.jobs), "job_s": self.jobs,
            "pack_wait_s": self.per_job("pack/wait"),
            "stage_s": self.per_job("device/stage"),
            "combine_s": self.per_job("combine"),
            "harvest_s": self.per_job("device/harvest"),
            "harvest_idle_s": None if self.harvest_idle_s is None
            else self.harvest_idle_s / n,
            "span_s_per_job": {k: v / n for k, v in sorted(
                self.span_s.items(), key=lambda kv: -kv[1])},
            "unspanned_s_per_job": self.unspanned_s / n,
            "idle_gaps": [[k, v] for k, v in sorted(
                self.idle_gaps.items(), key=lambda kv: -kv[1])],
            "bare_job_idle_share": bare / idle if idle > 0 else None,
        }


def _intersect(a: List[Interval], b: List[Interval]) -> float:
    """Length of the overlap of two unions of intervals."""
    a, b = trace_reduce.union(a), trace_reduce.union(b)
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _host_events(profile):
    """(bench/ spans of every host line, the window, the dispatching line's
    repro/ spans and jobs)."""
    bench: List[Tuple[float, float, str]] = []
    window: Optional[Interval] = None
    lines = []  # (jobs, repro spans) of each host line
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            jobs: List[Interval] = []
            program: List[Tuple[float, float, str]] = []
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name.startswith(PROGRAM):
                    program.append((s, e, ev.name))
                elif ev.name == trace_reduce.WINDOW:
                    window = (s, e)
                elif ev.name.startswith(trace_reduce.PREFIX):
                    bench.append((s, e, ev.name))
                    if ev.name.startswith(JOB):
                        jobs.append((s, e))
            lines.append((jobs, program))
    if window is None:
        raise ValueError(f"the trace holds no {trace_reduce.WINDOW!r} "
                         f"annotation")
    jobs, program = max(lines, key=lambda jp: len(jp[0]), default=([], []))
    if not jobs:  # no dispatching thread: no program span labels a gap
        program = []
    return bench, window, jobs, program


def reduce(profile) -> Spans:
    """Reduce a ``ProfileData`` (or a path to one) with the program's spans."""
    if isinstance(profile, str):
        profile = trace_reduce.load(profile)
    bench, (lo, hi), jobs, program = _host_events(profile)
    jobs = trace_reduce.clip(jobs, lo, hi)
    span_s: Dict[str, float] = collections.Counter()
    for s, e, name in program:
        for a, b in trace_reduce.clip([(s, e)], lo, hi):
            span_s[name] += (b - a) * 1e-9
    covered = [iv for s, e, _ in program for iv in
               trace_reduce.clip([(s, e)], lo, hi)]
    job_ns = sum(e - s for s, e in jobs)
    unspanned = (job_ns - _intersect(covered, jobs)) * 1e-9
    harvest = [(s, e) for s, e, name in program
               if name == PROGRAM + "device/harvest"]
    labels_from = bench + program
    idle: Dict[str, float] = collections.Counter()
    harvest_idle: List[float] = []
    for plane in profile.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        by_name = {line.name: line for line in plane.lines}
        op_line = by_name.get("XLA Ops", by_name.get("XLA Modules"))
        if op_line is None:
            op_line = max(plane.lines, key=lambda ln: sum(1 for _ in ln.events),
                          default=None)
        if op_line is None:
            continue
        busy = trace_reduce.union(trace_reduce.clip(
            [(ev.start_ns, ev.start_ns + ev.duration_ns)
             for ev in op_line.events], lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        # trace_reduce's own gap labelling (a private helper: a change to
        # it changes this reduction too, which test_bench_spans checks)
        for label, (a, b) in zip(trace_reduce._labels(
                [(a + b) / 2 for a, b in gaps], labels_from), gaps):
            idle[label] += (b - a) * 1e-9
        harvest_idle.append(_intersect(gaps, harvest) * 1e-9)
    return Spans(
        window_s=(hi - lo) * 1e-9,
        jobs=[(e - s) * 1e-9 for s, e in jobs],
        span_s=dict(span_s), unspanned_s=unspanned, idle_gaps=dict(idle),
        harvest_idle_s=sum(harvest_idle) / len(harvest_idle)
        if harvest_idle else None)


def main(argv=None) -> int:
    import argparse
    import shutil
    import tempfile

    from bench import main as bench_main
    from repro.obs import trace

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--trace-out", default=None,
                    help="also keep the raw trace here (gzipped .xplane.pb)")
    args, rest = ap.parse_known_args(argv)
    tmp = tempfile.mkdtemp(prefix="bench_spans_")
    out = args.trace_out or os.path.join(tmp, "window.xplane.pb.gz")
    try:
        trace.configure(enabled=True)
        rc = bench_main.main(["--workload", args.workload, "--seed", args.seed,
                              "--seconds", args.seconds, "--trace", "1",
                              "--trace-out", out, *rest])
        if os.path.exists(out):
            t0 = time.perf_counter()
            line = reduce(out).line()
            print(f"spans: {os.path.getsize(out)} bytes gzipped, reduced in "
                  f"{time.perf_counter() - t0} s", file=sys.stderr, flush=True)
            print(json.dumps(line), flush=True)
    finally:
        trace.configure(enabled=False)
        shutil.rmtree(tmp, ignore_errors=True)
    return rc


if __name__ == "__main__":
    from bench import main as _bench_main

    _bench_main.enable_compile_cache()
    sys.exit(main())
