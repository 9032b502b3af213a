"""decoded_on_device.list.

Stats.device_decoded_rows over the rows delivered to the sink, per list
job, in %: the share of the listed rows that the device decoded
(core/listing.decode_on_device) instead of the host (decode_batch).
"""


def read(run):
    if run.mode != "list" or not run.jobs:
        return None
    got = [getattr(getattr(j, "stats", None), "device_decoded_rows", None)
           for j in run.jobs]
    if any(r is None for r in got):  # a program without the counter
        return None
    return 100.0 * sum(r / max(1, j.rows)
                       for r, j in zip(got, run.jobs)) / len(got)
