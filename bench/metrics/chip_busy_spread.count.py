"""chip_busy_spread.count.

How unevenly the chips were kept busy in a traced count run, in %:
100 * (max - mean) / mean of the device planes' busy seconds in the
window (busy = union of device-op intervals).  None on fewer than two
device planes.
"""
from bench.readers import device_trace


def read(run):
    tr = device_trace(run, "count")
    if tr is None or len(tr.devices) < 2:
        return None
    busy = [d.busy_s for d in tr.devices.values()]
    mean = sum(busy) / len(busy)
    if mean <= 0:
        return None
    return 100.0 * (max(busy) - mean) / mean
