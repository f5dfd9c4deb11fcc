"""entry: ``parseNs`` of a window query, mean in ms: the ``srt/plan/parse``
interval of the ``session.sql(text)`` call that made the query's plan (it
lies outside ``queryWallNs``; a held statement reports the one parse it was
prepared with).  Nothing to read where the program publishes no
``parseNs``."""


def read(run):
    ns = [r["counters"]["parseNs"] for r in run["records"]
          if r["answered"] and "parseNs" in r["counters"]]
    return sum(ns) / len(ns) / 1e6 if ns else None
