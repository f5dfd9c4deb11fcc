"""95th percentile of the query latency (one query of the mix, from when it
was due to its answer) over every query of the window, answered or not."""

import numpy as np


def read(run):
    lat = [r["latency_s"] for r in run["records"]]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
