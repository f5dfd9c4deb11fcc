"""operators / kernels: share of the update batches of aggregates WITH
grouping keys that went through the slot contraction (``mxuAggBatches``:
limb rows against a one-hot slot table on the MXU) and not through the
sort-based grouping, of all such batches the query saw
(``keyedUpdateBatches``), in per cent; mean over the window's answered
queries that ran a keyed aggregate.  The twin of ``keyless_reduce_pct``.
Nothing to read where the program publishes no such counter, or no query ran
a keyed aggregate."""


def read(run):
    shares = [100.0 * r["counters"].get("mxuAggBatches", 0)
              / r["counters"]["keyedUpdateBatches"]
              for r in run["records"]
              if r["answered"] and r["counters"].get("keyedUpdateBatches")]
    return sum(shares) / len(shares) if shares else None
