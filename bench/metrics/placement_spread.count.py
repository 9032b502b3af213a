"""placement_spread.count.

How unevenly the dispatcher's cost model placed each count job's tiles
over the cell's chips, in %: per job, 100 * (max - mean) / mean over the
chips of Stats.device_cost (the scheduler cost of the tiles placed on
each, runtime/dispatch.py), averaged over the window's jobs.  None on one
chip, and where a job's program keeps no such counter.
"""


def read(run):
    if run.mode != "count" or run.chips < 2 or not run.jobs:
        return None
    spreads = []
    for job in run.jobs:
        cost = getattr(getattr(job, "stats", None), "device_cost", None)
        if not cost:  # a program without the counter
            return None
        placed = [float(cost.get(d, 0.0)) for d in range(run.chips)]
        mean = sum(placed) / len(placed)
        if mean <= 0:
            return None
        spreads.append(100.0 * (max(placed) - mean) / mean)
    return sum(spreads) / len(spreads)
