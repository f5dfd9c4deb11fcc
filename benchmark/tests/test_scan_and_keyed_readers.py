"""The readers the scan and keyed-aggregate cells bring: each gives a value
from hand-made ``run`` dicts, nothing on a program without its counters (the
parent of the PR that added them), and nothing where no query answered."""

import types

import pytest

import harness

Q1, PARQUET_Q6 = "tpch_sf1_cached.q1", "tpch_sf1_parquet.q6"


def _run(*counters, answered=True):
    query = types.SimpleNamespace(scanned_rows=lambda rows: rows["lineitem"])
    return {"rows": {"lineitem": 1000}, "queries": {"q": query},
            "records": [{"query": "q", "answered": answered, "counters": c}
                        for c in counters]}


SCAN = {"scanDecodeWallNs": 4_000_000, "scanH2dOverlapNs": 1_000_000,
        "scanBytesDecoded": 159_000}
KEYED = {"mxuAggBatches": 3, "keyedUpdateBatches": 6,
         "filterCompactedBatches": 6}


@pytest.mark.parametrize("metric,counters,value", [
    ("scan_decode_ms", SCAN, 4.0),
    ("scan_bytes_per_row", SCAN, 159.0),
    ("scan_overlap_pct", SCAN, 25.0),
    ("keyed_contraction_pct", KEYED, 50.0),
    ("compacted_batches_per_query", KEYED, 6.0),
])
def test_reader_gives_a_value_or_nothing(metric, counters, value):
    read = harness.load_reader("layer_metrics", metric).read
    assert read(_run(counters, counters)) == value
    assert read(_run({"dispatchCount": 2})) is None      # no such counter
    assert read(_run(counters, answered=False)) is None  # nothing answered
    assert read(_run()) is None


def test_zero_is_a_reading_for_counts_and_nothing_for_shares():
    """Cached tables decode nothing, keyless plans run no keyed aggregate:
    the shares have no base there.  A filter inside an aggregate's arguments
    compacts nothing, and 0 batches a query is what that cell reports."""
    zeros = {"scanDecodeWallNs": 0, "scanH2dOverlapNs": 0,
             "scanBytesDecoded": 0, "mxuAggBatches": 0,
             "keyedUpdateBatches": 0, "filterCompactedBatches": 0}
    for metric in ("scan_decode_ms", "scan_bytes_per_row", "scan_overlap_pct",
                   "keyed_contraction_pct"):
        read = harness.load_reader("layer_metrics", metric).read
        assert read(_run(zeros)) is None, metric
    read = harness.load_reader("layer_metrics",
                               "compacted_batches_per_query").read
    assert read(_run(zeros)) == 0.0
    # the sort form: keyed batches seen, none contracted
    read = harness.load_reader("layer_metrics", "keyed_contraction_pct").read
    assert read(_run(dict(zeros, keyedUpdateBatches=6))) == 0.0


GENERIC = {"first_query_s", "compile_s", "cache_bypass_compiles",
           "dispatches_per_query", "window_compiles", "query_roofline",
           "device_idle_pct", "peak_hbm_gb", "plan_ms", "device_wait_ms",
           "host_ms_per_query", "jax_trace_s", "lower_s", "backend_compile_s",
           "cache_load_s"}


@pytest.mark.parametrize("cell,own", [
    (Q1, {"keyed_contraction_pct", "compacted_batches_per_query"}),
    (PARQUET_Q6, {"scan_decode_ms", "scan_bytes_per_row", "scan_overlap_pct",
                  "compacted_batches_per_query", "keyless_reduce_pct"}),
])
def test_a_new_cell_reports_exactly_its_metrics(cell, own):
    spec = harness.load_cell(cell)
    assert {m["name"] for m in spec["per_layer"]} == GENERIC | own
    assert {m["name"] for m in spec["end_to_end"]} == \
        {"rows_per_s", "query_p95_ms", "setup_s"}
    assert spec["cell"]["chips"] == 1
    assert spec["config"]["cache_tables"] == (cell == Q1)
