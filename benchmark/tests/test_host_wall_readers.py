"""The six readers of a query's host wall by site (``enqueue_ms``,
``arg_leaves_per_query``, ``unspanned_ms``, ``d2h_ms``, ``h2d_ms``,
``h2d_pack_ms``): each is the mean over the answered queries of the window,
nothing on a program that publishes nothing to read, and an unanswered
query counts for nothing."""

import pytest

import harness

#: reader -> (a query's counters, what the reader makes of them alone)
READERS = {
    "enqueue_ms": ({"critpath": {"enqueue": 800_000, "wait": 5}}, 0.8),
    "arg_leaves_per_query": ({"dispatchArgLeaves": 200}, 200.0),
    "unspanned_ms": ({"unspannedNs": 250_000, "collectWallNs": 4e6}, 0.25),
    "d2h_ms": ({"critpath": {"d2h": 500_000, "enqueue": 7}}, 0.5),
    "h2d_ms": ({"critpath": {"h2d": 2_000_000_000}}, 2000.0),
    "h2d_pack_ms": ({"critpath": {"pack": 3_000_000_000, "h2d": 1}},
                    3000.0),
}
#: a program without the key: the parent of the PR that added the reader
PARENT = {
    "enqueue_ms": {"queryWallNs": 4_000_000},
    "arg_leaves_per_query": {"dispatchCount": 1},
    "unspanned_ms": {"critpath": {"wait": 1_300_000}},
    "d2h_ms": {"queryWallNs": 4_000_000},
    "h2d_ms": {"critpath": {"d2h": 5}},
    "h2d_pack_ms": {"critpath": {"h2d": 5_400_000_000}},
}


def _run(*records):
    return {"records": [{"query": "q", "answered": answered,
                         "counters": counters}
                        for counters, answered in records]}


def _scaled(counters, k):
    return {key: ({s: v * k for s, v in val.items()}
                  if isinstance(val, dict) else val * k)
            for key, val in counters.items()}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_is_the_mean_of_the_answered_queries(metric):
    read = harness.load_reader("layer_metrics", metric).read
    counters, value = READERS[metric]
    assert read(_run((counters, True))) == pytest.approx(value)
    # the mean of a query and one that read three times as much
    assert read(_run((counters, True), (_scaled(counters, 3), True))) == \
        pytest.approx(2 * value)
    # an unanswered query is left out, whatever it read
    assert read(_run((counters, True), (_scaled(counters, 9), False))) == \
        pytest.approx(value)
    # nothing on the parent, nor where no query answered
    assert read(_run((PARENT[metric], True))) is None
    assert read(_run((counters, False))) is None
    assert read(_run()) is None


def test_the_scan_metrics_are_the_parquet_cells_and_the_rest_everyones():
    names = {c: {m["name"] for m in harness.load_cell(c)["per_layer"]}
             for c in ("tpch_sf1_cached.q6", "tpch_sf1_cached.q1",
                       "tpch_sf1_parquet.q6", "tpch_sf1_join.q12",
                       "tpch_sf1_qgen.q6_text")}
    for cell, have in names.items():
        assert {"enqueue_ms", "arg_leaves_per_query", "unspanned_ms",
                "d2h_ms"} <= have, cell
        assert ({"h2d_ms", "h2d_pack_ms"} <= have) == \
            (cell == "tpch_sf1_parquet.q6"), cell
