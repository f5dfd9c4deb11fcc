"""operators / kernels: sizes a query's joins read back to the host
(``joinSizeReads``: the ``device_read``s named ``join_*`` - candidate pairs,
output rows, output string bytes - each a round trip the host waits out), per
query; mean over the window's answered queries.  Nothing to read where the
program publishes no such counter."""


def read(run):
    n = [r["counters"]["joinSizeReads"] for r in run["records"]
         if r["answered"] and "joinSizeReads" in r["counters"]]
    return sum(n) / len(n) if n else None
