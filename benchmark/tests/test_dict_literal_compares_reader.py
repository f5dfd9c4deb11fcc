"""The reader ``dict_literal_compares_per_query`` brings: the mean over the
answered queries of the string tests against literals decided on a column's
dictionary; 0 is a reading; nothing on a program without the counter and
nothing where no query answered."""

import pytest

import harness

READ = harness.load_reader("layer_metrics",
                           "dict_literal_compares_per_query").read


def _run(*counters, answered=True):
    return {"records": [{"query": "q", "answered": answered, "counters": c}
                        for c in counters]}


@pytest.mark.parametrize("counters,value", [
    ([{"dictLiteralCompares": 6}, {"dictLiteralCompares": 6}], 6.0),
    ([{"dictLiteralCompares": 6}, {"dictLiteralCompares": 3}], 4.5),
    ([{"dictLiteralCompares": 0, "filterCompactedBatches": 6}], 0.0),
])
def test_reader_gives_the_mean_of_the_answered_queries(counters, value):
    assert READ(_run(*counters)) == value


@pytest.mark.parametrize("run", [
    _run({"filterCompactedBatches": 6}),          # a program without it
    _run({"dictLiteralCompares": 6}, answered=False),
    _run(),
])
def test_reader_gives_nothing_without_a_reading(run):
    assert READ(run) is None


def test_the_metric_is_q12s_alone():
    spec = harness.load_cell("tpch_sf1_join.q12")
    assert "dict_literal_compares_per_query" in {
        m["name"] for m in spec["per_layer"]}
    for cell in ("tpch_sf1_cached.q6", "tpch_sf1_qgen.q6_text",
                 "tpch_sf1_parquet.q6", "tpch_sf1_cached.q1"):
        assert "dict_literal_compares_per_query" not in {
            m["name"] for m in harness.load_cell(cell)["per_layer"]}
