"""The bounds by noise class and ``tools/spread.py``: a ``<quantity>.host4``
entry reads its quantity's number under a bound of its own, the tool's
arithmetic on the ledger's numbers (no chip), and the distribution the result
line carries for it (a CPU rehearsal)."""

import json
import os
import subprocess
import sys
import time
import types

import numpy as np
import pytest

import harness
from tools import spread

with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
END_TO_END = {m["name"]: m for m in BENCH["end_to_end"]}
#: the entries that hold a quantity to a bound of its own on a machine of
#: another noise class (the whole four-chip host)
CLASSED = [name for name in END_TO_END if "." in name]


def _run(latencies_s, window_s):
    q6 = types.SimpleNamespace(scanned_rows=lambda rows: rows["lineitem"])
    return {"records": [{"query": "q6", "answered": True, "latency_s": x}
                        for x in latencies_s],
            "window_s": window_s, "rows": {"lineitem": 6001215},
            "queries": {"q6": q6}}


def _read(name, run):
    return harness.load_reader("end_to_end", name).read(run)


@pytest.mark.parametrize("name", CLASSED)
def test_a_classed_entry_is_its_quantity_under_a_tighter_bound(name):
    entry, quantity = END_TO_END[name], END_TO_END[name.split(".")[0]]
    assert name.endswith(".host4")
    for key in ("unit", "better", "source"):
        assert entry[key] == quantity[key]
    assert 0.01 <= entry["bound"] < quantity["bound"] <= 0.25
    chips = {w["name"]: w["chips"] for w in BENCH["workloads"]}
    # only a cell that holds the whole host is held to it, and that cell
    # still reports the quantity its per-layer metrics move
    assert entry["workloads"] and all(chips[c] == 4 for c in entry["workloads"])
    assert set(entry["workloads"]) <= set(quantity["workloads"])
    lat = 8.6e-3 + 0.4e-3 * np.random.RandomState(7).rand(5900)
    run = _run(lat, float(lat.sum()))
    assert _read(name, run) == _read(name.split(".")[0], run)


def test_three_stalls_move_the_rate_and_not_the_tail():
    quiet = 6.9e-3 + 0.4e-3 * np.random.RandomState(5).rand(7300)
    stalled = quiet.copy()
    stalled[[100, 3000, 7000]] += 0.100
    a = _run(quiet, float(quiet.sum()))
    b = _run(stalled, float(stalled.sum()))
    assert _read("query_p95_ms", b) == pytest.approx(_read("query_p95_ms", a),
                                                     rel=1e-4)
    assert _read("rows_per_s", b) < 0.995 * _read("rows_per_s", a)
    # a cost added to every query moves the tail by that cost
    slower = _run(quiet + 150e-6, float(quiet.sum()))
    assert (_read("query_p95_ms", slower) - _read("query_p95_ms", a)
            == pytest.approx(0.150, rel=1e-6))


@pytest.mark.parametrize("name", [n for n in END_TO_END if n != "setup_s"])
def test_an_empty_window_reads_nothing(name):
    assert _read(name, _run([], 51.0)) is None


def test_the_farthest_run_is_left_out_of_a_spread():
    assert spread.check_spread([100, 101, 102, 103, 150]) == 3
    assert spread.check_spread([50, 100, 101, 102, 103]) == 3
    assert spread.check_spread([100, 103]) == 3
    # the quartiles' distance, as ``statistics.quantiles`` gives them
    assert spread.quartile_spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5)


@pytest.mark.parametrize("share,admitted", [(0.0049, True), (0.0051, False)])
def test_a_metric_is_admitted_under_half_its_bound(share, admitted):
    values = [100 * (1 - share / 2), 100, 100, 100 * (1 + share / 2), 130]
    s = spread.summarize(values, bound=0.01)
    assert s["spread"] == pytest.approx(share)
    assert s["admitted"] is admitted


def _halves():
    """Two halves with the medians and spreads of PR 30's check of the held
    cell's ``rows_per_s`` (ledger, PR 30 ``reason``)."""
    parent = [837.735e6 + 1.4331e6 * k for k in (-1, 0, 0, 1, 3)]
    change = [841.89e6 + 10.91655e6 * k for k in (-1, 0, 0, 1, 3)]
    return parent, change


def test_split_reproduces_unresolved_at_one_percent():
    parent, change = _halves()
    j = spread.judge_no_gain(parent, change, 0.01, "higher")
    assert j["verdict"] == "unresolved"
    assert j["room"] == pytest.approx(8.37735e6)
    assert j["parent_spread"] == pytest.approx(2.8662e6)     # 0.34 %
    assert j["change_spread"] == pytest.approx(2.18331e7)    # 2.59 %


def test_split_reads_unchanged_at_the_bound_set_now():
    parent, change = _halves()
    bound = next(m["bound"] for m in BENCH["end_to_end"]
                 if m["name"] == "rows_per_s")
    assert bound <= 0.25
    assert spread.judge_no_gain(parent, change, bound,
                                "higher")["verdict"] == "unchanged"
    # a loss beyond the bound is seen once the halves are steady enough
    lost = [v * (1 - 1.5 * bound) for v in parent]
    assert spread.judge_no_gain(parent, lost, bound,
                                "higher")["verdict"] == "worse"


def test_a_handicap_is_seen_beyond_the_bound_only():
    assert spread.seen(5.80, 5.95, 0.01, "lower")["seen"]
    assert not spread.seen(850e6, 833e6, 0.125, "higher")["seen"]
    assert spread.seen(850e6, 700e6, 0.125, "higher")["seen"]


def test_the_tool_never_imports_jax():
    code = ("import sys; sys.path.insert(0, %r); import spread; "
            "spread.judged_metrics(%r, {}); assert 'jax' not in sys.modules"
            % (os.path.dirname(spread.__file__), BENCH["workloads"][0]["name"]))
    subprocess.run([sys.executable, "-c", code], check=True)


def test_the_result_line_carries_the_distribution():
    cell = BENCH["workloads"][0]["name"]
    result = harness.run_cell(cell, seed=2200000035, seconds=0.5, trace=False,
                              t_start=time.monotonic(), scale_factor=0.002)
    lat = result["info"]["latency_s"]
    assert set(lat) == {"min", "median", "max", "count", "p1", "p5", "p10",
                        "p25", "p75", "p95", "p99"}
    assert lat["count"] == result["attempted"] >= 1
    assert (lat["min"] <= lat["p1"] <= lat["p5"] <= lat["p10"] <= lat["p25"]
            <= lat["p75"] <= lat["p95"] <= lat["p99"] <= lat["max"])
    # the tool's candidates are the line's quantiles, in milliseconds
    read = spread.readings(result)
    for key in spread.CANDIDATES:
        assert read[f"query_{key}_ms"] == pytest.approx(lat[key] * 1e3,
                                                        rel=1e-12)
    assert read["query_p95_ms"] == pytest.approx(lat["p95"] * 1e3, rel=1e-12)
