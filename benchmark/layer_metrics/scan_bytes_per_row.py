"""scan: bytes the parquet decode produced (``scanBytesDecoded``, every
column the scan decodes, needed by the query or not) for each base-table row
the query's scans cover (``scanned_rows`` of the query's own file); mean over
the window's answered queries that scanned.  Nothing to read where no query
decoded a byte (cached tables)."""


def read(run):
    per_row = [r["counters"]["scanBytesDecoded"]
               / run["queries"][r["query"]].scanned_rows(run["rows"])
               for r in run["records"]
               if r["answered"] and r["counters"].get("scanBytesDecoded")]
    return sum(per_row) / len(per_row) if per_row else None
