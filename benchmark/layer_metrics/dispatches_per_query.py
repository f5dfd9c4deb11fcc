"""stage pipeline: ``dispatchCount`` of a warm query, mean over the window."""


def read(run):
    n = [r["counters"].get("dispatchCount", 0) for r in run["records"]
         if r["answered"]]
    return sum(n) / len(n) if n else None
