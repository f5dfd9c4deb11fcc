"""stage pipeline: ``compileCount`` summed over the window (should be 0)."""


def read(run):
    n = [r["counters"].get("compileCount", 0) for r in run["records"]
         if r["answered"]]
    return sum(n) if n else None
