"""q6 (filter + keyless aggregate of a product), TPC-H Q6 / Q6Like: the plain
reference, its lower-precision control, and the functions that count the
query's rows and bytes.  The text's constants are the source's after Spark's
constant folding: ``date '1994-01-01' + interval '1' year`` is 1995-01-01,
``.06 - 0.01`` and ``.06 + 0.01`` (decimals in Spark) are 0.05 and 0.07."""

import numpy as np

TABLES = ("lineitem",)
ORDERED = True   # one row
DAY_1994, DAY_1995 = 8766, 9131   # days since 1970-01-01


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
    m = ((date >= DAY_1994) & (date < DAY_1995) & (disc >= f(0.05))
         & (disc <= f(0.07)) & (qty < f(24)))
    return [(float(np.sum(price[m] * disc[m], dtype=f)),)]


def scanned_rows(rows):
    """Base-table rows the query's scans cover."""
    return rows["lineitem"]


def logical_bytes(rows):
    """Bytes of the columns the query reads, at their in-memory widths:
    l_shipdate 4, l_discount 8, l_quantity 8, l_extendedprice 8."""
    return rows["lineitem"] * (4 + 8 + 8 + 8)
