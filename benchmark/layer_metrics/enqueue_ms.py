"""stage pipeline: ``critpath.enqueue`` of a window query, mean in ms: the
host wall of the jitted calls' ``srt/enqueue/<program>`` spans (the enqueue
of each dispatch: argument handling, the runtime's allocation and launch),
where nothing of a higher site covers it.  A query that dispatched nothing
reads 0.  Nothing to read where the program publishes no ``critpath``."""


def read(run):
    cps = [r["counters"]["critpath"] for r in run["records"]
           if r["answered"] and "critpath" in r["counters"]]
    return sum(cp.get("enqueue", 0) for cp in cps) / len(cps) / 1e6 \
        if cps else None
