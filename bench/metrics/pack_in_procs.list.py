"""pack_in_procs.list.

Share of each list job's packed batches that a worker process packed,
outside the dispatching interpreter, in %: Stats.pack_proc_batches over
Stats.packed_batches (core/pipeline.stream_batches, core/packing.PackPool),
mean over the window's jobs.  It says whether the process pool engaged.
None where the program keeps no such counters.
"""


def read(run):
    if run.mode != "list" or not run.jobs:
        return None
    shares = []
    for job in run.jobs:
        stats = getattr(job, "stats", None)
        procs = getattr(stats, "pack_proc_batches", None)
        packed = getattr(stats, "packed_batches", None)
        if procs is None or packed is None:  # a program without the counters
            return None
        shares.append(100.0 * procs / max(1, packed))
    return sum(shares) / len(shares)
