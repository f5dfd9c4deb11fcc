"""scan: ``critpath.pack`` of a window query, mean in ms: the host wall of
the ``srt/pack/column`` spans, where ``batch.host_to_device`` packs each
column of a staged batch into the capacity buffers (validity, string offsets
and bytes, dictionary codes) that ``h2d_ms`` then copies.  Nothing to read
where no answered query packed a batch, or where the program does not
split the packing from the copy."""


def read(run):
    cps = [r["counters"]["critpath"] for r in run["records"]
           if r["answered"] and "critpath" in r["counters"]]
    if not any("pack" in cp for cp in cps):
        return None
    return sum(cp.get("pack", 0) for cp in cps) / len(cps) / 1e6
