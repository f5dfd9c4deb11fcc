"""operators / kernels: ``critpath.device_wait`` of a window query, mean in ms:
the wall the host spent blocked on the chip (size read-backs, the wait before
the D2H copy) — the device's busy time seen from inside the program.  Nothing
to read where the program publishes no ``critpath``."""


def read(run):
    ns = [r["counters"]["critpath"].get("device_wait", 0)
          for r in run["records"]
          if r["answered"] and "critpath" in r["counters"]]
    return sum(ns) / len(ns) / 1e6 if ns else None
