"""q12 (a two-table equi-join under a five-conjunct predicate, two CASE sums
grouped and ordered by a string), TPC-H Q12 "Shipping Modes and Order
Priority" / Q12Like with the validation parameters of clause 2.4.12.3
(SHIPMODE1 MAIL, SHIPMODE2 SHIP, DATE 1994-01-01): the plain reference and
the functions that count the query's rows and bytes.  ``date '1994-01-01' +
interval '1' year`` is 1995-01-01 after Spark's constant folding.

The answer holds no float: two rows of a string and two exact integers.  The
``float_dtype`` of the other references' control changes nothing here, and
``wrong_answers`` is the whole comparison (``limits.json``)."""

import numpy as np

import config_requires

# without the planner's rule the filter stays above the join and a query
# joins all of lineitem to all of orders first: minutes a query, no window
# holds one; the configuration says so and such a checkout ends here, exit
# code 1
config_requires.program("tpch_sf1_join")

TABLES = ("lineitem", "orders")
ORDERED = True   # ORDER BY l_shipmode
SHIPMODES = ("MAIL", "SHIP")
HIGH_PRIORITIES = ("1-URGENT", "2-HIGH")
DAY_FROM, DAY_TO = 8766, 9131   # days since 1970-01-01 of 1994-01-01, 1995-01-01


def joined(frames):
    """The rows the join hands the aggregate: the lineitem rows that pass the
    five conjuncts, each with its order (``o_orderkey`` is unique, so the
    merge neither drops nor doubles a line that has an order)."""
    li = frames["lineitem"]
    li = li[li["l_shipmode"].isin(SHIPMODES)
            & (li["l_commitdate"] < li["l_receiptdate"])
            & (li["l_shipdate"] < li["l_commitdate"])
            & (li["l_receiptdate"] >= DAY_FROM)
            & (li["l_receiptdate"] < DAY_TO)]
    return li[["l_orderkey", "l_shipmode"]].merge(
        frames["orders"][["o_orderkey", "o_orderpriority"]],
        left_on="l_orderkey", right_on="o_orderkey", how="inner")


def reference(frames, float_dtype=np.float64):
    """Rows as the query returns them, sorted by the key's STRING value (the
    generator's frames are categorical and would sort by code)."""
    j = joined(frames)
    high = j["o_orderpriority"].isin(HIGH_PRIORITIES).to_numpy()
    modes = j["l_shipmode"].astype(str).to_numpy()
    return [(str(m), int(high[modes == m].sum()), int((~high[modes == m]).sum()))
            for m in sorted(set(modes))]


def scanned_rows(rows):
    """Base-table rows the query's scans cover: both tables'."""
    return rows["lineitem"] + rows["orders"]


def logical_bytes(rows):
    """Bytes of the columns the query reads, at their in-memory widths:
    lineitem's key 8, three dates 4 each and ``l_shipmode`` (mean length of
    the seven modes, 30 / 7 bytes, + a 4-byte offset); orders' key 8 and
    ``o_orderpriority`` (mean length of the five priorities, 8.4 bytes, + a
    4-byte offset)."""
    return int(rows["lineitem"] * (8 + 3 * 4 + 30 / 7 + 4)
               + rows["orders"] * (8 + 8.4 + 4))
