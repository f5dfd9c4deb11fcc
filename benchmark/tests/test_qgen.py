"""The eight substitution sets of ``tpch_sf1_qgen``: every ``q6_s*``
directory's text, its reference and its entry in the configuration say the
same DATE, DISCOUNT and QUANTITY, each inside the domain TPC-H clause 2.4.6.3
gives it, and the mix sends all eight as text with nothing held.  The answers
of different sets differ, so a value bound from another set cannot pass for
the right one."""

import datetime
import json
import os
import re

import pytest

import harness

CONFIG = harness._json(os.path.join(
    harness.HERE, "configs", "tpch_sf1_qgen.json"))
SETS = CONFIG["substitution"]["sets"]
MIX = harness._json(os.path.join(
    harness.HERE, "traffic", "q6_qgen_text.json"))

TEXT = re.compile(
    r"SELECT sum\(l_extendedprice \* l_discount\) AS revenue\s+"
    r"FROM lineitem\s+"
    r"WHERE l_shipdate >= to_date\('(\d{4})-01-01'\)\s+"
    r"AND l_shipdate < to_date\('(\d{4})-01-01'\)\s+"
    r"AND l_discount BETWEEN (0\.\d\d) AND (0\.\d\d)\s+"
    r"AND l_quantity < (\d+)\s*$")


def _days(year):
    return (datetime.date(year, 1, 1) - datetime.date(1970, 1, 1)).days


def test_the_mix_sends_the_configurations_sets_as_text():
    assert MIX["queries"] == [s["query"] for s in SETS]
    assert len(SETS) == CONFIG["substitution_sets"] == 8
    assert MIX["statement"] == "text" and MIX["loop"] == "closed"
    assert MIX["clients"] == 1
    assert MIX.get("text_submissions_in_setup", 0) == 0
    assert CONFIG["substitution"]["combinations"] == 5 * 8 * 2
    assert len({(s["DATE"], s["DISCOUNT"], s["QUANTITY"])
                for s in SETS}) == 8
    assert (SETS[0]["DATE"], SETS[0]["DISCOUNT"], SETS[0]["QUANTITY"]) == \
        ("1994-01-01", 0.06, 24)     # the clause's validation set


@pytest.mark.parametrize("entry", SETS, ids=[s["query"] for s in SETS])
def test_text_reference_and_configuration_agree_inside_the_domains(entry):
    q = harness.load_query(entry["query"])
    ref = q["module"]
    m = TEXT.match(q["text"])
    assert m, q["text"]
    year, year_to, lo, hi, quantity = m.groups()
    # the three places agree
    assert entry["DATE"] == f"{year}-01-01" == f"{ref.DATE_YEAR}-01-01"
    assert int(year_to) == ref.DATE_YEAR + 1
    assert (ref.DAY_FROM, ref.DAY_TO) == (_days(ref.DATE_YEAR),
                                          _days(ref.DATE_YEAR + 1))
    assert entry["DISCOUNT"] == ref.DISCOUNT
    assert (lo, hi) == (f"{ref.DISCOUNT - 0.01:.2f}",
                        f"{ref.DISCOUNT + 0.01:.2f}")
    assert (float(lo), float(hi)) == (ref.DISCOUNT_LO, ref.DISCOUNT_HI)
    assert entry["QUANTITY"] == int(quantity) == ref.QUANTITY
    # inside clause 2.4.6.3's domains
    dom = CONFIG["substitution"]["domains"]
    assert dom["DATE_years"][0] <= ref.DATE_YEAR <= dom["DATE_years"][1]
    assert dom["DISCOUNT"][0] <= ref.DISCOUNT <= dom["DISCOUNT"][1]
    assert round(ref.DISCOUNT * 100) == pytest.approx(ref.DISCOUNT * 100)
    assert ref.QUANTITY in range(dom["QUANTITY"][0], dom["QUANTITY"][1] + 1)
    assert q["limits"] == {"max_rel_gap": 1e-11}
    assert ref.TABLES == ("lineitem",) and ref.ORDERED is True


def test_the_sets_answers_differ_and_a_swapped_answer_is_not_correct():
    import checks
    rows = harness.table_rows(CONFIG, 0.02)
    frames = harness.reference_frames(CONFIG, ["lineitem"], rows, 2200000033)
    refs = {s["query"]: harness.load_query(s["query"])["module"].reference(
        frames) for s in SETS}
    assert len({r[0][0] for r in refs.values()}) == 8
    names = list(refs)
    swapped = [(q, refs[names[(i + 1) % 8]]) for i, q in enumerate(names)]
    limits = {q: 1e-11 for q in names}
    ordered = {q: True for q in names}
    assert checks.judge(list(refs.items()), refs, ordered, limits,
                        0)["correct"]
    assert not checks.judge(swapped, refs, ordered, limits, 0)["correct"]


def test_the_new_readers_find_nothing_on_a_program_without_the_counters():
    """The parent publishes no ``planShapeHit``/``parseNs``/``planShapeNs``:
    the readers return None there and do not raise."""
    run = {"mix": MIX, "records": [{"answered": True, "counters": {
        "compileCount": 0}}], "setup": {"executions": [
            {"counters": {"compileCount": c}} for c in (2, 0, 2, 0, 0, 0)]}}
    for name in ("shape_hit_pct", "parse_ms", "bind_ms"):
        assert harness.load_reader("layer_metrics", name).read(run) is None
    assert harness.load_reader(
        "layer_metrics", "setup_variant_compiles").read(run) == 1
    run["records"][0]["counters"].update(
        planShapeHit=1, parseNs=2_000_000, planShapeNs=500_000,
        planBindNs=250_000)
    got = {n: harness.load_reader("layer_metrics", n).read(run)
           for n in ("shape_hit_pct", "parse_ms", "bind_ms")}
    assert got == {"shape_hit_pct": 100.0, "parse_ms": 2.0, "bind_ms": 0.75}
    single = dict(run, mix=dict(MIX, queries=["q6_s0"]))
    assert harness.load_reader(
        "layer_metrics", "setup_variant_compiles").read(single) is None


@pytest.mark.parametrize("entry", SETS, ids=[s["query"] for s in SETS])
def test_a_program_that_bakes_literals_cannot_load_the_configuration(
        entry, monkeypatch):
    """``requires`` of the configuration: on a program without
    ``plan.logical.plan_shape`` (the parent of PR 28) every set's reference
    ends the run with the configuration's reason when it is loaded, before
    any set-up; with it the reference loads."""
    import importlib.util

    import spark_rapids_tpu.plan.logical as logical
    module, _, symbol = CONFIG["requires"]["program"].rpartition(".")
    assert (module, symbol) == (logical.__name__, "plan_shape")
    path = os.path.join(harness.HERE, "queries", entry["query"],
                        "reference.py")

    def load():
        spec = importlib.util.spec_from_file_location("reference_probe", path)
        spec.loader.exec_module(importlib.util.module_from_spec(spec))

    load()
    monkeypatch.delattr(logical, symbol)
    with pytest.raises(SystemExit) as stop:
        load()
    assert isinstance(stop.value.code, str)      # exit code 1, said why
    assert CONFIG["requires"]["program"] in stop.value.code
    assert "compiling inside the measured window" in stop.value.code


def test_the_cell_holds_four_chips_for_steadiness_and_says_so():
    bench = harness._json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"]
                if w["name"] == "tpch_sf1_qgen.q6_text")
    assert cell["chips"] == CONFIG["machine"]["chips"] == 4
    assert "steadiness" in cell["why"] and len(cell["why"]) <= 200
    assert CONFIG["chips"] == 1     # the deployment computes on one
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 2)
