"""d2h_bytes_per_row.list.

Stats.d2h_bytes over the rows delivered to the sink, per list job: bytes
fetched from the device (sizing counts, emit buffers, counts and overflow
flags; runtime/dispatch.py) for each row listed.
"""


def read(run):
    if run.mode != "list" or not run.jobs:
        return None
    got = [getattr(getattr(j, "stats", None), "d2h_bytes", None)
           for j in run.jobs]
    if any(b is None for b in got):  # a program without the counter
        return None
    return sum(b / max(1, j.rows) for b, j in zip(got, run.jobs)) / len(got)
