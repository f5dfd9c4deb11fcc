"""planner: ``critpath.plan`` of a window query, mean in ms: the wall that
only planning covers in ``session.execute_with_metrics`` (``srt/plan/physical``:
fingerprint, conf state, plan-cache probe, context, history hook).  Nothing to
read where the program publishes no ``critpath``."""


def read(run):
    ns = [r["counters"]["critpath"].get("plan", 0) for r in run["records"]
          if r["answered"] and "critpath" in r["counters"]]
    return sum(ns) / len(ns) / 1e6 if ns else None
