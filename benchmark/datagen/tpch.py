"""TPC-H ``lineitem`` and ``orders`` from a seed, straight to numpy / arrow /
pandas, by the population rules of the TPC-H specification (clause 4.2.3):
every column of both tables, at dbgen's widths and domains.

* ``orders``: sparse ``o_orderkey`` (the first 8 of every 32 keys),
  ``o_orderdate`` uniform over 1992-01-01 .. 1998-08-02, one to seven lines
  an order.
* ``lineitem``: one row per line, in order-key order; ``l_linenumber`` 1..n;
  ``l_extendedprice = l_quantity * p_retailprice(l_partkey)``; ship, commit
  and receipt dates offset from the order's date; ``l_returnflag`` and
  ``l_linestatus`` follow from the dates against 1995-06-17 (four groups);
  ``l_comment`` is a 10..43-character substring of a pseudo-text pool, as
  dbgen 2.x cuts it.

What is not dbgen's: the random streams (numpy's, keyed by ``--seed``), the
text pool (a short grammar over the specification's word lists, 1 MB), and
``o_totalprice`` / ``o_orderstatus``, which are drawn and not summed from the
lines.  The number of lines is drawn 1..7 an order and then moved, an order
at a time, to the table's exact row count, so every seed has the same sizes.
No jax, nothing of the engine.

A table is a dict ``column -> Column``: numeric columns carry a numpy array;
``string`` columns carry int32 codes into a short vocabulary; ``date``
columns carry int32 days since 1970-01-01; a ``text`` column carries a start
and a length into ``pool`` and is cut only when it is written.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Sequence

import numpy as np

PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
FLAGS = ["A", "N", "R"]
LINE_STATUSES = ["F", "O"]
ORDER_STATUSES = ["F", "O", "P"]
INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]

START_DATE = 8035      # 1992-01-01, days since 1970-01-01
END_DATE = 10591       # 1998-12-31
CURRENT_DATE = 9298    # 1995-06-17

NOUNS = ("foxes ideas theodolites pinto_beans instructions dependencies excuses "
         "platelets asymptotes courts dolphins multipliers sauternes warthogs "
         "frets dinos attainments somas Tiresias' patterns forges braids "
         "hockey_players frays warhorses dugouts notornis epitaphs pearls "
         "tithes waters orbits gifts sheaves depths sentiments decoys realms "
         "pains grouches escapades packages requests accounts deposits").split()
VERBS = ("sleep wake are cajole haggle nag use boost affix detect integrate "
         "maintain nod was lose sublate solve thrash promise engage hinder "
         "print x-ray breach eat grow impress mold poach serve run dazzle "
         "snooze doze unwind kindle play hang believe doubt").split()
ADJECTIVES = ("furious sly careful blithe quick fluffy slow quiet ruthless "
              "thin close dogged daring brave stealthy permanent enticing idle "
              "busy regular final ironic even bold silent special pending "
              "unusual express").split()
ADVERBS = ("sometimes always never furiously slyly carefully blithely quickly "
           "fluffily slowly quietly ruthlessly thinly closely doggedly "
           "daringly bravely stealthily permanently enticingly idly busily "
           "regularly finally ironically evenly boldly silently").split()
PREPOSITIONS = ("about above according_to across after against along "
                "alongside_of among around at atop before behind beneath "
                "beside besides between beyond by despite during except for "
                "from in_place_of inside instead_of into near of on outside "
                "over past since through throughout to toward under until up "
                "upon without with within").split()
TERMINATORS = [".", ";", ":", "?", "!", "--"]
POOL_BYTES = 1 << 20

_TABLE_ID = {"lineitem": 0, "orders": 1, "order level": 2, "text pool": 3}


@dataclass
class Column:
    kind: str                                  # long | double | int | date | string | text
    values: np.ndarray                         # string: int32 codes; text: int32 starts
    vocabulary: Optional[Sequence[str]] = None  # string only
    lengths: Optional[np.ndarray] = None        # text only
    pool: Optional[np.ndarray] = None           # text only: uint8


def _stream(seed: int, what: str):
    return np.random.RandomState([seed % (1 << 32), _TABLE_ID[what]])


def _strings(r, vocabulary, n) -> Column:
    return Column("string", r.randint(0, len(vocabulary), n).astype(np.int32),
                  vocabulary)


def _text_pool(seed: int) -> np.ndarray:
    """Sentences of "noun phrase, verb phrase, [prepositional phrase],
    terminator" over the specification's word lists, POOL_BYTES long."""
    r = _stream(seed, "text pool")

    def pick(words):
        return words[r.randint(len(words))].replace("_", " ")

    parts, size = [], 0
    while size < POOL_BYTES + 64:
        noun = [[pick(NOUNS)], [pick(ADJECTIVES), pick(NOUNS)],
                [pick(ADVERBS), pick(ADJECTIVES), pick(NOUNS)]][r.randint(3)]
        verb = [[pick(VERBS)], [pick(VERBS), pick(ADVERBS)]][r.randint(2)]
        tail = [[], [pick(PREPOSITIONS), "the", pick(NOUNS)]][r.randint(2)]
        s = " ".join(noun + verb + tail) + TERMINATORS[r.randint(6)] + " "
        parts.append(s)
        size += len(s)
    return np.frombuffer("".join(parts).encode("ascii"), np.uint8)


def _text(r, seed: int, n: int, lo: int, hi: int) -> Column:
    lengths = r.randint(lo, hi + 1, n).astype(np.int32)
    starts = r.randint(0, POOL_BYTES - hi, n).astype(np.int32)
    return Column("text", starts, lengths=lengths, pool=_text_pool(seed))


def _order_level(rows: Dict[str, int], seed: int):
    """What an order and its lines share: key, date, number of lines."""
    n, total = rows["orders"], rows["lineitem"]
    r = _stream(seed, "order level")
    i = np.arange(n, dtype=np.int64)
    key = (i // 8) * 32 + i % 8 + 1
    date = r.randint(START_DATE, END_DATE - 151 + 1, n).astype(np.int32)
    lines = r.randint(1, 8, n)
    while lines.sum() != total:   # move to the exact row count, one line an order
        step = 1 if lines.sum() < total else -1
        free = np.flatnonzero(lines < 7 if step > 0 else lines > 1)
        lines[r.permutation(free)[:abs(total - lines.sum())]] += step
    return key, date, lines


def _lineitem(r, rows: Dict[str, int], seed: int) -> Dict[str, Column]:
    n = rows["lineitem"]
    scale = rows["orders"] / 1_500_000
    key, odate, lines = _order_level(rows, seed)
    first = np.cumsum(lines) - lines
    order_date = np.repeat(odate, lines)
    parts = max(1, int(200_000 * scale))
    suppliers = max(1, int(10_000 * scale))
    part = r.randint(1, parts + 1, n).astype(np.int64)
    supp = (part + r.randint(0, 4, n) * (suppliers // 4 + (part - 1) // suppliers)
            ) % suppliers + 1
    quantity = r.randint(1, 51, n).astype(np.int64)
    retail_cents = 90000 + (part // 10) % 20001 + 100 * (part % 1000)
    ship = (order_date + r.randint(1, 122, n)).astype(np.int32)
    commit = (order_date + r.randint(30, 91, n)).astype(np.int32)
    receipt = (ship + r.randint(1, 31, n)).astype(np.int32)
    returned = np.where(r.randint(0, 2, n) == 0, FLAGS.index("R"),
                        FLAGS.index("A"))
    return {
        "l_orderkey": Column("long", np.repeat(key, lines)),
        "l_partkey": Column("long", part),
        "l_suppkey": Column("long", supp),
        "l_linenumber": Column("long", np.arange(n, dtype=np.int64)
                               - np.repeat(first, lines) + 1),
        "l_quantity": Column("double", quantity.astype(np.float64)),
        "l_extendedprice": Column("double", quantity * retail_cents / 100.0),
        "l_discount": Column("double", r.randint(0, 11, n) / 100.0),
        "l_tax": Column("double", r.randint(0, 9, n) / 100.0),
        "l_returnflag": Column(
            "string", np.where(receipt <= CURRENT_DATE, returned,
                               FLAGS.index("N")).astype(np.int32), FLAGS),
        "l_linestatus": Column(
            "string", (ship > CURRENT_DATE).astype(np.int32), LINE_STATUSES),
        "l_shipdate": Column("date", ship),
        "l_commitdate": Column("date", commit),
        "l_receiptdate": Column("date", receipt),
        "l_shipinstruct": _strings(r, INSTRUCTIONS, n),
        "l_shipmode": _strings(r, MODES, n),
        "l_comment": _text(r, seed, n, 10, 43),
    }


def _orders(r, rows: Dict[str, int], seed: int) -> Dict[str, Column]:
    n = rows["orders"]
    key, date, _ = _order_level(rows, seed)
    customers = max(1, n // 10)
    clerks = max(1, n // 1500)
    return {
        "o_orderkey": Column("long", key),
        "o_custkey": Column("long", r.randint(1, customers + 1, n)
                            .astype(np.int64)),
        "o_orderstatus": _strings(r, ORDER_STATUSES, n),
        "o_totalprice": Column("double",
                               r.randint(90_000, 50_000_000, n) / 100.0),
        "o_orderdate": Column("date", date),
        "o_orderpriority": _strings(r, PRIORITIES, n),
        "o_clerk": Column("string", r.randint(0, clerks, n).astype(np.int32),
                          [f"Clerk#{i + 1:09d}" for i in range(clerks)]),
        "o_shippriority": Column("int", np.zeros(n, dtype=np.int32)),
        "o_comment": _text(r, seed, n, 19, 78),
    }


_GENERATORS = {"lineitem": _lineitem, "orders": _orders}


def generate(table: str, rows: Dict[str, int], seed: int) -> Dict[str, Column]:
    """One table, the same for the same ``(table, rows, seed)``.  ``seed`` is
    any whole number; each table draws from a stream of its own, and the
    lines of an order agree between the two tables."""
    return _GENERATORS[table](_stream(seed, table), rows, seed)


def _cut(c: Column):
    """The text column as an arrow string array: each value its substring of
    the pool, gathered a million rows at a time."""
    import pyarrow as pa
    offsets = np.zeros(len(c.values) + 1, dtype=np.int64)
    np.cumsum(c.lengths, out=offsets[1:])
    if offsets[-1] >= 1 << 31:
        raise ValueError("text column over 2 GiB: write it in more files")
    data = np.empty(offsets[-1], dtype=np.uint8)
    for lo in range(0, len(c.values), 1 << 20):
        hi = min(lo + (1 << 20), len(c.values))
        out = offsets[lo:hi]
        take = np.repeat(c.values[lo:hi] - (out - out[0]), c.lengths[lo:hi])
        take += np.arange(offsets[hi] - out[0], dtype=np.int64)
        data[out[0]:offsets[hi]] = c.pool[take]
    return pa.StringArray.from_buffers(
        len(c.values), pa.py_buffer(offsets.astype(np.int32)),
        pa.py_buffer(data))


def to_arrow(columns: Dict[str, Column]):
    """The arrow table the engine's own writer would produce for these
    columns (int64 / float64 / int32 / date32 / string)."""
    import pyarrow as pa
    arrays = {}
    for name, c in columns.items():
        if c.kind == "string":
            arrays[name] = pa.DictionaryArray.from_arrays(
                pa.array(c.values), pa.array(list(c.vocabulary))
            ).cast(pa.string())
        elif c.kind == "text":
            arrays[name] = _cut(c)
        elif c.kind == "date":
            arrays[name] = pa.array(c.values, type=pa.int32()).cast(
                pa.date32())
        else:
            arrays[name] = pa.array(c.values)
    return pa.table(arrays)


def to_pandas(columns: Dict[str, Column]):
    """A pandas frame for the plain references: strings as categoricals
    (groupby with ``observed=True`` gives the string keys), dates as day
    numbers.  Text columns are left out: no reference reads one yet, and a
    query that does takes it from ``to_arrow``."""
    import pandas as pd
    return pd.DataFrame({
        name: (pd.Categorical.from_codes(c.values, list(c.vocabulary))
               if c.kind == "string" else c.values)
        for name, c in columns.items() if c.kind != "text"})
