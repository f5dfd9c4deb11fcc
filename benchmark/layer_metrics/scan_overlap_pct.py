"""scan: share of the decode wall (``scanDecodeWallNs``, summed over the
pool's workers) that the consumer did not sit blocked on, because it was
staging or computing meanwhile (``scanH2dOverlapNs``), in per cent; over all
answered queries of the window.  Nothing to read where no query scanned."""


def read(run):
    counters = [r["counters"] for r in run["records"] if r["answered"]]
    decode = sum(c.get("scanDecodeWallNs", 0) for c in counters)
    if not decode:
        return None
    return 100.0 * sum(c.get("scanH2dOverlapNs", 0) for c in counters) / decode
