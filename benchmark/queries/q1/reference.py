"""q1 (string-key group-by of eight aggregates + sort), TPC-H Q1 / Q1Like: the
plain reference, its lower-precision control, and the functions that count
the query's rows and bytes.  ``date '1998-12-01' - interval '90' day`` is
1998-09-02 after Spark's constant folding."""

import numpy as np

TABLES = ("lineitem",)
ORDERED = True   # ORDER BY l_returnflag, l_linestatus
DAY_1998_09_02 = 10471   # days since 1970-01-01


def reference(frames, float_dtype=np.float64):
    """Rows as the query returns them.  ``float_dtype=np.float32`` is the
    control: every DOUBLE column, product and accumulator in float32."""
    li = frames["lineitem"]
    li = li[li["l_shipdate"] <= DAY_1998_09_02]
    li = li.astype({c: float_dtype for c in
                    ("l_quantity", "l_extendedprice", "l_discount", "l_tax")})
    one = float_dtype(1)
    li = li.assign(disc_price=li["l_extendedprice"] * (one - li["l_discount"]))
    li = li.assign(charge=li["disc_price"] * (one + li["l_tax"]))
    g = li.groupby(["l_returnflag", "l_linestatus"], sort=True, observed=True)
    out = g.agg(sum_qty=("l_quantity", "sum"),
                sum_base_price=("l_extendedprice", "sum"),
                sum_disc_price=("disc_price", "sum"),
                sum_charge=("charge", "sum"),
                avg_qty=("l_quantity", "mean"),
                avg_price=("l_extendedprice", "mean"),
                avg_disc=("l_discount", "mean"),
                count_order=("l_quantity", "size")).reset_index()
    return [(str(r[0]), str(r[1]), *(float(v) for v in r[2:9]), int(r[9]))
            for r in out.itertuples(index=False, name=None)]


def scanned_rows(rows):
    """Base-table rows the query's scans cover."""
    return rows["lineitem"]


def logical_bytes(rows):
    """Bytes of the columns the query reads, at their in-memory widths:
    l_shipdate 4, l_quantity, l_extendedprice, l_discount and l_tax 8 each,
    and the two one-character string keys at 1 byte + a 4-byte offset each."""
    return rows["lineitem"] * (4 + 4 * 8 + 2 * (1 + 4))
