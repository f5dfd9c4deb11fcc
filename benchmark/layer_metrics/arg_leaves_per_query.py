"""stage pipeline: ``dispatchArgLeaves`` of a window query, mean: the flat
argument leaves (device buffers and bound literal values) handed to the
query's dispatches, each counted once its program was traced — what the
``enqueue`` spans pay for beside the runtime's own work.  Nothing to read
where the program publishes no such counter."""


def read(run):
    n = [r["counters"]["dispatchArgLeaves"] for r in run["records"]
         if r["answered"] and "dispatchArgLeaves" in r["counters"]]
    return sum(n) / len(n) if n else None
