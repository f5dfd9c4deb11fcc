"""entry: ``queryWallNs - critpath.device_wait`` of a window query, mean in ms:
everything ``session.execute_with_metrics`` does that is not waiting for the
chip (planning, staging, enqueue, D2H copy, result assembly, bookkeeping).
Nothing to read where the program publishes no ``critpath``."""


def read(run):
    ns = [r["counters"]["queryWallNs"]
          - r["counters"]["critpath"].get("device_wait", 0)
          for r in run["records"]
          if r["answered"] and "critpath" in r["counters"]
          and "queryWallNs" in r["counters"]]
    return sum(ns) / len(ns) / 1e6 if ns else None
