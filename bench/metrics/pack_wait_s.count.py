"""pack_wait_s.count.

Stage clock 'pack_wait' per count job: wall seconds the dispatching thread
waits on the pack pool for the next batch, the sum of its pack/wait spans
(core/pipeline.stream_batches).  The front end's share of the critical
path, where frontend_cpu_s counts CPU-seconds over the pack workers.
"""
from bench.clocks import clock


def read(run):
    return clock(run, "count", "pack_wait")
