"""stage pipeline: ``compileCacheLoadNs`` summed over set-up's executions, in
s: persistent compile-cache reads and executable deserialisation on hits.
Nothing to read where the program has no such counter."""

KEY = "compileCacheLoadNs"


def read(run):
    ns = [e["counters"][KEY] for e in run["setup"]["executions"]
          if KEY in e.get("counters", {})]
    return sum(ns) / 1e9 if ns else None
