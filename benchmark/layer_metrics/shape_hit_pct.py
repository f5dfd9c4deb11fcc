"""planner: share of the window's answered queries whose plan shape was found
in the plan cache (``planShapeHit`` 1: one physical plan and one set of
executables a query shape, the literals bound at execution), in per cent.
Nothing to read where the program publishes no ``planShapeHit``."""


def read(run):
    hits = [r["counters"]["planShapeHit"] for r in run["records"]
            if r["answered"] and "planShapeHit" in r["counters"]]
    return 100.0 * sum(hits) / len(hits) if hits else None
