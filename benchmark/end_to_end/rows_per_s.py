"""Base-table rows the answered queries' scans cover, per second of the
window: all the work over all the time (host clock)."""


def read(run):
    done = sum(run["queries"][r["query"]].scanned_rows(run["rows"])
               for r in run["records"] if r["answered"])
    return done / run["window_s"] if done else None
