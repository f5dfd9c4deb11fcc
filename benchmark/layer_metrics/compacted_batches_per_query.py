"""operators / kernels: batches a filter operator compacted
(``filterCompactedBatches``: a gather of every column of the batch,
``kernels/layout.compact``) per query, mean over the window's answered
queries; 0 where every filter sits inside an aggregate's arguments.  Nothing
to read where the program publishes no such counter."""


def read(run):
    n = [r["counters"]["filterCompactedBatches"] for r in run["records"]
         if r["answered"] and "filterCompactedBatches" in r["counters"]]
    return sum(n) / len(n) if n else None
