"""stage_s.count.

Stage clock 'stage' per count job: seconds of the dispatcher's
device/stage spans, the device_put of each batch plus its launch
(runtime/dispatch.py).
"""
from bench.clocks import clock


def read(run):
    return clock(run, "count", "stage")
