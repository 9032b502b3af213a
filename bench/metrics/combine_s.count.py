"""combine_s.count.

Stage clock 'combine' per count job: seconds of the dispatcher's combine
spans, which fetch each batch's partials from the device and add them up
exactly on the host (runtime/dispatch.py).
"""
from bench.clocks import clock


def read(run):
    return clock(run, "count", "combine")
