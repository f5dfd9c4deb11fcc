"""The benchmark's keyed-aggregate and parquet-scan cells, rehearsed at a
tiny scale factor through the harness's own ``run_cell``: the normal path
(``session.read.parquet`` -> ``session.sql`` -> plan -> stage pipeline ->
``collect``), judged by the cell's own reference, and each cell drives the
mechanism it exists to measure.  A number from this run is not a speed."""

import os
import time
import types

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


@pytest.fixture
def harness(monkeypatch, tmp_path):
    monkeypatch.syspath_prepend(BENCH)
    import harness
    monkeypatch.setattr(harness, "DATA_DIR", str(tmp_path / "data"))
    return harness


def _rehearse(harness, monkeypatch, cell):
    """(result line, the ``run`` dict the metric readers were given)."""
    seen = {}
    load_reader = harness.load_reader

    def spy(group, metric):
        read = load_reader(group, metric).read
        return types.SimpleNamespace(
            read=lambda run: read(seen.setdefault("run", run)))

    monkeypatch.setattr(harness, "load_reader", spy)
    result = harness.run_cell(cell, seed=34, seconds=0.5, trace=False,
                              t_start=time.monotonic(), scale_factor=0.002)
    return result, seen["run"]


@pytest.mark.parametrize("cell", ["tpch_sf1_cached.q1",
                                  "tpch_sf1_parquet.q6"])
def test_new_cell_is_correct_and_drives_what_it_measures(
        harness, monkeypatch, cell):
    result, run = _rehearse(harness, monkeypatch, cell)
    assert result["correct"] and result["failed"] == 0, result["compared"]
    assert result["compared"]["wrong_answers"]["value"] == 0
    assert set(result["metrics"]) == {
        "rehearsal.rows_per_s", "rehearsal.query_p95_ms",
        "rehearsal.setup_s"}
    counters = [r["counters"] for r in run["records"]]
    assert counters and all(r["answered"] for r in run["records"])

    def read(metric):
        return harness.load_reader("layer_metrics", metric).read(run)

    for c in counters:
        assert c["compileCount"] == 0           # the window compiles nothing
        if cell == "tpch_sf1_parquet.q6":
            # every query decodes its columns again; the filter sits inside
            # the keyless aggregate's arguments and compacts nothing
            assert c["scanDecodeWallNs"] > 0 and c["scanBytesDecoded"] > 0
            assert c["keylessAggBatches"] > 0
            assert c["filterCompactedBatches"] == 0
            assert c["keyedUpdateBatches"] == 0
        else:
            # cached tables decode nothing; the two string keys arrive
            # dictionary-encoded through a filter that compacts their
            # codes, so every update batch groups on the slot contraction
            assert c["scanDecodeWallNs"] == 0 and c["scanBytesDecoded"] == 0
            assert c["keyedUpdateBatches"] > 0
            assert c["mxuAggBatches"] == c["keyedUpdateBatches"]
            assert c["filterCompactedBatches"] > 0
            assert c["keylessUpdateBatches"] == 0
    if cell == "tpch_sf1_parquet.q6":
        assert 100 < read("scan_bytes_per_row") < 200   # all 16 columns
        assert 0 <= read("scan_overlap_pct") <= 100
        assert read("scan_decode_ms") > 0
        assert read("keyless_reduce_pct") == 100
        assert read("compacted_batches_per_query") == 0
        assert read("keyed_contraction_pct") is None
    else:
        assert read("keyed_contraction_pct") == 100     # by the codes
        assert read("compacted_batches_per_query") >= 1
        assert read("scan_bytes_per_row") is None
        assert read("keyless_reduce_pct") is None


def test_join_cell_is_correct_and_drives_what_it_measures(
        harness, monkeypatch):
    """``tpch_sf1_join.q12``: Q12's source text through the planner's join
    rules, the equi-join and the keyed aggregate behind it; the three
    readers this cell brings read what its plan counts."""
    result, run = _rehearse(harness, monkeypatch, "tpch_sf1_join.q12")
    assert result["correct"] and result["failed"] == 0, result["compared"]
    assert result["compared"]["wrong_answers"]["value"] == 0
    assert result["compared"]["max_rel_gap"] == {"value": 0.0, "limit": 0.0}
    assert set(result["metrics"]) == {
        "rehearsal.rows_per_s", "rehearsal.query_p95_ms",
        "rehearsal.setup_s"}
    assert result["info"]["rows"] == {"lineitem": 12002, "orders": 3000}
    records = run["records"]
    assert records and all(r["answered"] for r in records)
    frames = harness.reference_frames(
        run["config"], ("lineitem", "orders"), run["rows"], 34)
    joined = len(run["queries"]["q12"].joined(frames))
    for r in records:
        c = r["counters"]
        assert c["compileCount"] == 0           # the window compiles nothing
        assert (c["pushedJoinFilters"], c["joinKeysFromWhere"]) == (5, 1)
        assert c["joinPairs"] == joined > 0
        assert c["filterCompactedBatches"] >= 1  # lineitem, under the join
        assert c["keyedUpdateBatches"] >= 1      # l_shipmode, after the join
        assert c["keylessUpdateBatches"] == 0
        assert sum(len(row) for row in r["rows"]) == 3 * len(r["rows"])

    def read(metric):
        return harness.load_reader("layer_metrics", metric).read(run)

    assert read("join_filters_pushed") == 5
    assert read("join_pairs_per_query") == joined
    assert read("join_size_reads_per_query") == 3   # pairs + two string columns
    assert read("compacted_batches_per_query") >= 1
    assert read("keyed_contraction_pct") is not None
    assert read("keyless_reduce_pct") is None
    rows = run["queries"]["q12"].scanned_rows(run["rows"])
    assert rows == 12002 + 3000
    # the entries this cell added keep to the file's limits
    import json
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    for entry in bench["configs"] + bench["workloads"]:
        assert len(entry["why"]) <= 200, entry["name"]
        assert len(entry.get("source", "")) <= 200, entry["name"]
