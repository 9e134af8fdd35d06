"""Share of the HBM roofline that the traversal reaches: the bytes the
algorithm must move (``run.work_bytes``: each traversed arc's neighbour id
and attributes, each reached vertex's state read and written), at the
chip's peak bandwidth, over the device's busy time in the traced window."""


def read(run):
    t = run.trace
    if not t or t.get("busy_s", 0) <= 0 or not run.work_bytes:
        return None
    return 100.0 * run.work_bytes / run.peaks["hbm_bytes_per_s"] / t["busy_s"]
