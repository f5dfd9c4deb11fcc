"""q6_s4 (TPC-H Q6 with the substitution parameters DATE 1997-01-01,
DISCOUNT 0.07, QUANTITY 24; specification clause 2.4.6.3): the plain
reference, its lower-precision control, and the functions that count the
query's rows and bytes.  The text's constants are the clause's after Spark's
constant folding: ``date '1997-01-01' + interval '1' year`` is 1998-01-01,
``0.07 - 0.01`` and ``0.07 + 0.01`` (decimals in Spark) are 0.06 and 0.08."""

import numpy as np

import config_requires

# a program that bakes literals compiles inside the measured window on this
# traffic; the configuration says so and such a checkout ends here, exit code 1
config_requires.program("tpch_sf1_qgen")

TABLES = ("lineitem",)
ORDERED = True   # one row
#: the set's substitution parameters, as configs/tpch_sf1_qgen.json lists them
DATE_YEAR, DISCOUNT, QUANTITY = 1997, 0.07, 24
DAY_FROM, DAY_TO = 9862, 10227   # days since 1970-01-01 of 1997-01-01, 1998-01-01
DISCOUNT_LO, DISCOUNT_HI = 0.06, 0.08


def reference(frames, float_dtype=np.float64):
    """Rows as the query returns them.  ``float_dtype=np.float32`` is the
    control: every DOUBLE column, literal, product and accumulator in
    float32."""
    li = frames["lineitem"]
    f = float_dtype
    date = li["l_shipdate"].to_numpy()
    disc = li["l_discount"].to_numpy().astype(f)
    qty = li["l_quantity"].to_numpy().astype(f)
    price = li["l_extendedprice"].to_numpy().astype(f)
    m = ((date >= DAY_FROM) & (date < DAY_TO) & (disc >= f(DISCOUNT_LO))
         & (disc <= f(DISCOUNT_HI)) & (qty < f(QUANTITY)))
    return [(float(np.sum(price[m] * disc[m], dtype=f)),)]


def scanned_rows(rows):
    """Base-table rows the query's scans cover."""
    return rows["lineitem"]


def logical_bytes(rows):
    """Bytes of the columns the query reads, at their in-memory widths:
    l_shipdate 4, l_discount 8, l_quantity 8, l_extendedprice 8."""
    return rows["lineitem"] * (4 + 8 + 8 + 8)
