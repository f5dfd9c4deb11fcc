"""operators / kernels: candidate pairs the equi-joins of a query expanded
(``joinPairs``: the totals the join reads to the host to size its output; no
read of its own), per query; mean over the window's answered queries.  TPC-H
Q12 reads the lineitem rows that pass its predicate (each has one order),
and all 6.0 M lines where no conjunct moved below the join.  Nothing to read
where the program publishes no such counter."""


def read(run):
    n = [r["counters"]["joinPairs"] for r in run["records"]
         if r["answered"] and "joinPairs" in r["counters"]]
    return sum(n) / len(n) if n else None
