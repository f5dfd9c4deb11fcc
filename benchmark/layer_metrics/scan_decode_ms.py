"""scan: ``scanDecodeWallNs`` (decode wall across the pool's workers) per
query, mean over the window.  Nothing to read where no query scanned."""


def read(run):
    ns = [r["counters"].get("scanDecodeWallNs", 0) for r in run["records"]
          if r["answered"]]
    return sum(ns) / len(ns) / 1e6 if ns and any(ns) else None
