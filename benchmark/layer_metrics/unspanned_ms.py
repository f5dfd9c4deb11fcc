"""entry: ``unspannedNs`` of a window query, mean in ms: the part of the
collect's wall (``collectWallNs``, ``DataFrame.collect`` entry to exit)
that no span of the program covers — ``critpath``'s ``wait`` inside the
query window plus what of the call's head and tail no span covers.
Nothing to read where the program publishes no ``unspannedNs``."""


def read(run):
    ns = [r["counters"]["unspannedNs"] for r in run["records"]
          if r["answered"] and "unspannedNs" in r["counters"]]
    return sum(ns) / len(ns) / 1e6 if ns else None
