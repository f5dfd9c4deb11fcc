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
