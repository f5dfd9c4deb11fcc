"""The generator keeps the TPC-H population rules that the cells' shapes rest
on (specification clause 4.2.3), at a size a test can hold."""

import numpy as np
import pytest

ROWS = {"lineitem": 120_024, "orders": 30_000}


@pytest.fixture(scope="module")
def tables():
    from datagen import tpch
    return {t: tpch.generate(t, ROWS, 2200000033) for t in ROWS}


def test_lineitem_has_dbgen_columns_and_row_count(tables):
    li = tables["lineitem"]
    assert list(li) == [
        "l_orderkey", "l_partkey", "l_suppkey", "l_linenumber", "l_quantity",
        "l_extendedprice", "l_discount", "l_tax", "l_returnflag",
        "l_linestatus", "l_shipdate", "l_commitdate", "l_receiptdate",
        "l_shipinstruct", "l_shipmode", "l_comment"]
    assert all(len(c.values) == ROWS["lineitem"] for c in li.values())


def test_one_to_seven_lines_an_order_numbered_from_one(tables):
    li, o = tables["lineitem"], tables["orders"]
    keys, lines = np.unique(li["l_orderkey"].values, return_counts=True)
    assert np.array_equal(keys, o["o_orderkey"].values)   # sparse, sorted
    assert lines.min() == 1 and lines.max() == 7
    assert np.all(np.bincount(lines)[1:] > ROWS["orders"] / 8)   # each of 1..7
    first = np.r_[True, np.diff(li["l_orderkey"].values) != 0]
    number = li["l_linenumber"].values
    assert np.all(number[first] == 1)
    assert np.all(np.diff(number)[~first[1:]] == 1)
    assert set(np.unique(o["o_orderkey"].values % 32)) == set(range(1, 9))


def test_prices_dates_and_flags_follow_the_rules(tables):
    from datagen import tpch
    li = {k: c.values for k, c in tables["lineitem"].items()}
    part = li["l_partkey"]
    retail = (90000 + (part // 10) % 20001 + 100 * (part % 1000)) / 100.0
    assert np.allclose(li["l_extendedprice"], li["l_quantity"] * retail,
                       rtol=1e-15)
    odate = np.repeat(tables["orders"]["o_orderdate"].values,
                      np.unique(li["l_orderkey"], return_counts=True)[1])
    assert np.all((li["l_shipdate"] - odate >= 1) & (li["l_shipdate"] - odate <= 121))
    assert np.all((li["l_commitdate"] - odate >= 30) & (li["l_commitdate"] - odate <= 90))
    assert np.all((li["l_receiptdate"] - li["l_shipdate"] >= 1)
                  & (li["l_receiptdate"] - li["l_shipdate"] <= 30))
    late = li["l_receiptdate"] > tpch.CURRENT_DATE
    assert np.all((li["l_returnflag"] == tpch.FLAGS.index("N")) == late)
    assert np.all((li["l_linestatus"] == 1) == (li["l_shipdate"] > tpch.CURRENT_DATE))
    groups = set(zip(li["l_returnflag"], li["l_linestatus"]))
    assert groups == {(0, 0), (1, 0), (1, 1), (2, 0)}   # A/F N/F N/O R/F


def test_comments_are_10_to_43_characters_of_text(tables):
    from datagen import tpch
    comments = tpch.to_arrow(tables["lineitem"])["l_comment"].to_pylist()
    lengths = np.array([len(c) for c in comments])
    assert lengths.min() == 10 and lengths.max() == 43
    assert 26 < lengths.mean() < 27
    assert len(set(comments)) > 0.9 * len(comments)


def test_same_seed_same_table_other_seed_other_table(tables):
    from datagen import tpch
    again = tpch.generate("lineitem", ROWS, 2200000033)
    other = tpch.generate("lineitem", ROWS, 2200000034)
    for name, c in tables["lineitem"].items():
        assert np.array_equal(c.values, again[name].values), name
    assert not np.array_equal(other["l_partkey"].values,
                              tables["lineitem"]["l_partkey"].values)
    assert len(other["l_partkey"].values) == ROWS["lineitem"]
