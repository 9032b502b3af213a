"""pack_wait_s.list.

Stage clock 'pack_wait' per list job: wall seconds the dispatching thread
waits on the pack pool for the next batch, the sum of its pack/wait spans
(core/pipeline.stream_batches).  The front end's share of the critical
path, where frontend_cpu_s counts CPU-seconds over the pack workers.
"""
from bench.clocks import clock


def read(run):
    return clock(run, "list", "pack_wait")
