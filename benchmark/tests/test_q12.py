"""What ``tpch_sf1_join.q12`` brings: its reference against a case computed by
hand, the faults a join can have (a pair lost, a pair doubled, an answer
finished on the host) each read not ``correct`` although the answer holds no
float, its three readers on hand-made ``run`` dicts, the configuration's
``requires`` ending a checkout without the planner's rule, and the metrics the
cell reports."""

import importlib.util
import json
import os
import types

import pandas as pd
import pytest

import checks
import harness

CELL = "tpch_sf1_join.q12"
with open(os.path.join(harness.HERE, "configs", "tpch_sf1_join.json")) as f:
    CONFIG = json.load(f)


def _frames():
    """Seven lines of five orders; day numbers around 1994 (8766..9130).
    Line by line: passes, wrong mode, commit not before receipt, ship not
    before commit, receipt before 1994, receipt in 1995, passes."""
    modes = pd.Categorical(
        ["MAIL", "AIR", "MAIL", "SHIP", "SHIP", "MAIL", "SHIP"],
        categories=["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"])
    lineitem = pd.DataFrame({
        "l_orderkey":    [1, 1, 2, 3, 4, 33, 33],
        "l_shipmode":    modes,
        "l_shipdate":    [8800, 8800, 8800, 8850, 8700, 9100, 9000],
        "l_commitdate":  [8810, 8810, 8830, 8840, 8710, 9110, 9010],
        "l_receiptdate": [8820, 8820, 8825, 8860, 8765, 9131, 9020],
    })
    orders = pd.DataFrame({
        "o_orderkey": [1, 2, 3, 4, 33],
        "o_orderpriority": pd.Categorical(
            ["2-HIGH", "1-URGENT", "5-LOW", "3-MEDIUM", "4-NOT SPECIFIED"]),
    })
    return {"lineitem": lineitem, "orders": orders}


def test_reference_on_a_case_computed_by_hand():
    q = harness.load_query("q12")
    mod = q["module"]
    assert mod.TABLES == ("lineitem", "orders") and mod.ORDERED
    assert len(mod.joined(_frames())) == 2
    # one MAIL line of a 2-HIGH order, one SHIP line of a 4-NOT SPECIFIED one
    assert mod.reference(_frames()) == [("MAIL", 1, 0), ("SHIP", 0, 1)]
    # the groups come in the order of the key's STRING value although the
    # generator's categories put SHIP before MAIL
    rows = {"lineitem": 6_001_215, "orders": 1_500_000}
    assert mod.scanned_rows(rows) == 7_501_215
    assert mod.logical_bytes(rows) == int(
        6_001_215 * (8 + 12 + 30 / 7 + 4) + 1_500_000 * (8 + 8.4 + 4))
    assert q["limits"]["max_rel_gap"] == 0.0
    text = " ".join(q["text"].split())
    assert "FROM orders, lineitem WHERE o_orderkey = l_orderkey" in text
    assert text in CONFIG["shapes"]["queries"]


WANT = [("MAIL", 1, 0), ("SHIP", 0, 1)]
FAULTS = {
    "sound": (WANT, 0, True),
    "a_pair_lost": ([("MAIL", 0, 0), ("SHIP", 0, 1)], 0, False),
    "a_pair_doubled": ([("MAIL", 2, 0), ("SHIP", 0, 1)], 0, False),
    "a_pair_under_the_other_case": ([("MAIL", 0, 1), ("SHIP", 0, 1)], 0,
                                    False),
    "a_group_lost": (WANT[:1], 0, False),
    "groups_out_of_order": (WANT[::-1], 0, False),
    "a_count_as_a_float": ([("MAIL", 1.0, 0), ("SHIP", 0, 1)], 0, False),
    "finished_on_the_host": (WANT, 1, False),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_join_fault_is_not_correct_without_a_float_to_compare(fault):
    got, detoured, correct = FAULTS[fault]
    q = harness.load_query("q12")
    verdict = checks.judge(
        [("q12", got)], {"q12": q["module"].reference(_frames())},
        {"q12": q["module"].ORDERED},
        {"q12": q["limits"]["max_rel_gap"]}, detoured)
    assert verdict["correct"] is correct, verdict["numbers"]
    n = verdict["numbers"]
    assert n["max_rel_gap"] == {"value": 0.0, "limit": 0.0}
    assert n["wrong_answers"]["value"] == int(not correct and not detoured)


def _run(*counters, answered=True):
    return {"records": [{"query": "q12", "answered": answered, "counters": c}
                        for c in counters]}


JOIN = {"pushedJoinFilters": 5, "joinKeysFromWhere": 1, "joinPairs": 30_000,
        "joinSizeReads": 3}


@pytest.mark.parametrize("metric,value", [
    ("join_filters_pushed", 5.0),
    ("join_pairs_per_query", 30_000.0),
    ("join_size_reads_per_query", 3.0),
])
def test_reader_gives_a_value_or_nothing(metric, value):
    read = harness.load_reader("layer_metrics", metric).read
    assert read(_run(JOIN, JOIN)) == value
    assert read(_run({k: 0 for k in JOIN})) == 0.0     # 0 is a reading
    assert read(_run({"dispatchCount": 2})) is None    # the parent: no counter
    assert read(_run(JOIN, answered=False)) is None    # nothing answered
    assert read(_run()) is None


def test_a_program_without_the_rule_cannot_load_the_configuration(
        monkeypatch):
    """``requires``: on a checkout whose planner lacks the rule the
    reference ends the run with the configuration's reason when it is
    loaded, before any data, session or compile: exit code 1."""
    import spark_rapids_tpu.plan.join_pushdown as rule
    module, _, symbol = CONFIG["requires"]["program"].rpartition(".")
    assert (module, symbol) == (rule.__name__, "push_filters_through_joins")
    path = os.path.join(harness.HERE, "queries", "q12", "reference.py")

    def load():
        spec = importlib.util.spec_from_file_location("reference_probe", path)
        spec.loader.exec_module(importlib.util.module_from_spec(spec))

    load()
    monkeypatch.delattr(rule, symbol)
    with pytest.raises(SystemExit) as stop:
        load()
    assert isinstance(stop.value.code, str)      # exit code 1, said why
    assert CONFIG["requires"]["program"] in stop.value.code
    assert "no 51 s window holds a query" in stop.value.code


def test_the_cell_reports_exactly_its_metrics():
    spec = harness.load_cell(CELL)
    generic = {"first_query_s", "compile_s", "cache_bypass_compiles",
               "dispatches_per_query", "window_compiles", "query_roofline",
               "device_idle_pct", "peak_hbm_gb", "plan_ms", "device_wait_ms",
               "host_ms_per_query", "jax_trace_s", "lower_s",
               "backend_compile_s", "cache_load_s"}
    own = {"join_filters_pushed", "join_pairs_per_query",
           "join_size_reads_per_query", "compacted_batches_per_query",
           "keyed_contraction_pct"}
    assert {m["name"] for m in spec["per_layer"]} == generic | own
    assert {m["name"] for m in spec["end_to_end"]} == \
        {"rows_per_s", "query_p95_ms", "setup_s"}
    assert spec["cell"]["chips"] == 1 and spec["config"]["chips"] == 1
    assert spec["mix"]["statement"] == "held" and \
        spec["mix"]["queries"] == ["q12"]
    assert len(spec["cell"]["why"]) <= 200
    assert spec["config"]["reduced"] == [] and spec["config"]["cache_tables"]
    assert spec["config"]["tables"] == {
        "lineitem": {"rows": 6001215, "files": 1},
        "orders": {"rows": 1500000, "files": 1}}
    assert "join" in spec["config"]["guarantees"]
