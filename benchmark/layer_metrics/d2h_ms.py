"""stage pipeline: ``critpath.d2h`` of a window query, mean in ms: the host
wall of the answer's way home — the ``srt/d2h/start`` span (the copies
started before the wait on the chip) and ``srt/d2h/transfer`` (what is left
of the copy once the programs have finished).  Nothing to read where the
program publishes no ``critpath``."""


def read(run):
    cps = [r["counters"]["critpath"] for r in run["records"]
           if r["answered"] and "critpath" in r["counters"]]
    return sum(cp.get("d2h", 0) for cp in cps) / len(cps) / 1e6 \
        if cps else None
