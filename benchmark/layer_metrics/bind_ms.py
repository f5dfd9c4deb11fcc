"""planner: ``planShapeNs + planBindNs`` of a window query, mean in ms: the
``srt/plan/shape`` span (constant fold, literal lift and shape fingerprint of
a plan object seen for the first time) and the ``srt/plan/bind`` span (this
execution's literal values made device scalars, or found again), both inside
``srt/plan/physical``.  Nothing to read where the program publishes
neither."""


def read(run):
    ns = [r["counters"]["planShapeNs"] + r["counters"]["planBindNs"]
          for r in run["records"]
          if r["answered"] and "planShapeNs" in r["counters"]
          and "planBindNs" in r["counters"]]
    return sum(ns) / len(ns) / 1e6 if ns else None
