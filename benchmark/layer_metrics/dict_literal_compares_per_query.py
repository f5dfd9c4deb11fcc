"""operators / kernels: string tests against literals (``=``, ``<>``,
``<=>``, ``IN``, an exact ``LIKE``) that a batch's program decided on the
column's dictionary instead of its rows (``dictLiteralCompares``,
``exprs/strings.equals_any_literal``; an ``IN`` counts once) per query, mean
over the window's answered queries.  Nothing to read where the program
publishes no such counter."""


def read(run):
    n = [r["counters"]["dictLiteralCompares"] for r in run["records"]
         if r["answered"] and "dictLiteralCompares" in r["counters"]]
    return sum(n) / len(n) if n else None
