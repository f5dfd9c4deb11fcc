"""stage pipeline: ``lowerNs`` summed over set-up's executions, in s: the part
of the compile wall spent lowering jaxprs to MLIR modules.  Nothing to read
where the program has no such counter."""

KEY = "lowerNs"


def read(run):
    ns = [e["counters"][KEY] for e in run["setup"]["executions"]
          if KEY in e.get("counters", {})]
    return sum(ns) / 1e9 if ns else None
