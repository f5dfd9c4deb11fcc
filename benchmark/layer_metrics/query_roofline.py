"""operators / kernels: the least time the chip's HBM needs to stream the
columns the traced queries read (``logical_bytes`` of each query's own file
over the peak bytes/s of ``peaks.json``), as a share of the time operations
ran on the device while they were traced.  Memory-bound by construction (a
scan-filter-aggregate does a few operations a byte).  It counts the query's
logical bytes, so it reads the same work whatever implements it."""


def read(run):
    t = run["trace"]
    if not t or not t["busy_s"]:
        return None
    bytes_ = sum(run["queries"][r["query"]].logical_bytes(run["rows"])
                 for r in run["records"] if r["traced"] and r["answered"])
    if not bytes_:
        return None
    return 100.0 * (bytes_ / run["peaks"]["hbm_bytes_per_s"]) / t["busy_s"]
