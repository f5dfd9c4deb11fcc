"""operators / kernels: share of the update batches of aggregates with no
grouping key that were reduced (``keylessAggBatches``: limb rows summed along
their chunk, one row out at the minimum capacity) and not contracted against a
one-hot slot table or sorted, of all such batches the query saw
(``keylessUpdateBatches``), in per cent; mean over the window's answered
queries that ran a keyless aggregate.  Nothing to read where the program
publishes no such counters, or no query ran one."""


def read(run):
    shares = [100.0 * r["counters"]["keylessAggBatches"]
              / r["counters"]["keylessUpdateBatches"]
              for r in run["records"]
              if r["answered"] and "keylessAggBatches" in r["counters"]
              and r["counters"].get("keylessUpdateBatches")]
    return sum(shares) / len(shares) if shares else None
