"""planner: WHERE conjuncts the planner moved below a join, onto the one side
they read (``pushedJoinFilters``), per query; mean over the window's answered
queries.  TPC-H Q12 reads 5: what the join sees is the lineitem rows that
pass, not all of lineitem.  0 where the rule found nothing to move.  Nothing
to read where the program publishes no such counter."""


def read(run):
    n = [r["counters"]["pushedJoinFilters"] for r in run["records"]
         if r["answered"] and "pushedJoinFilters" in r["counters"]]
    return sum(n) / len(n) if n else None
