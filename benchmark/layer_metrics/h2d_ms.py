"""scan: ``critpath.h2d`` of a window query, mean in ms: the host wall of
the ``srt/h2d/*`` spans, a staged batch's ``device_put`` calls (the copy;
the host's packing of the buffers is ``h2d_pack_ms``).  Nothing to read
where no answered query staged a batch."""


def read(run):
    cps = [r["counters"]["critpath"] for r in run["records"]
           if r["answered"] and "critpath" in r["counters"]]
    if not any("h2d" in cp for cp in cps):
        return None
    return sum(cp.get("h2d", 0) for cp in cps) / len(cps) / 1e6
