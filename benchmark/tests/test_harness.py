"""What a cell reports follows from ``BENCHMARK.json`` alone, and a metric
``<quantity>.<class>`` finds its quantity's reader."""

import json
import os

import pytest

import harness

with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_reports_set_up_another_end_to_end_and_a_layer(cell):
    spec = harness.load_cell(cell)
    end_to_end = [m["name"] for m in spec["end_to_end"]]
    assert "setup_s" in end_to_end and len(end_to_end) >= 2
    assert spec["per_layer"]
    for m in spec["per_layer"]:
        assert m["moves"] in end_to_end, m["name"]


@pytest.mark.parametrize("group,key", [("end_to_end", "end_to_end"),
                                       ("layer_metrics", "per_layer")])
def test_every_metric_has_a_reader(group, key):
    for m in BENCH[key]:
        assert callable(harness.load_reader(group, m["name"]).read)
    assert (harness.load_reader("end_to_end", "rows_per_s.scan")
            is harness.load_reader("end_to_end", "rows_per_s"))
    with pytest.raises(SystemExit):
        harness.load_reader(group, "no_such_metric.scan")


def test_a_metric_with_no_cells_listed_follows_what_it_moves():
    by_cell = {c: {m["name"] for m in harness.load_cell(c)["per_layer"]}
               for c in CELLS}
    for m in BENCH["per_layer"]:
        if "workloads" in m:
            continue
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        for c in CELLS:
            assert (m["name"] in by_cell[c]) == (c in moved.get("workloads", CELLS))
