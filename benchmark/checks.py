"""The comparison that decides ``correct``.

Every answer a timed query returned is compared with the plain reference's
rows once the window has closed:

* ``wrong_answers`` — answers whose row count, or any value that is not a
  float (group keys, counts, NULLs), differs from the reference.  Limit 0.
* ``max_rel_gap`` — the widest ``|got - want| / |want|`` over every float of
  every answer.  Its limit is the query's (``queries/<q>/limits.json``),
  set between the program's readings and the float32 control's (PERF.md §2).
* ``failed`` — queries that raised, or finished with a detour counter that
  is not 0 (an answer finished on the host is a different result).  Limit 0.
* ``compared`` — answers compared; at least 1, or the run proved nothing.
"""

from __future__ import annotations

import datetime
import math
from typing import Dict, List, Sequence, Tuple

#: ``session.last_metrics`` counters that must read 0 after every query
MUST_BE_ZERO = ("retryCount", "deviceLostCount", "partitionFallbackCount",
                "pallasFallbackCount")


def _plain(v):
    if isinstance(v, datetime.date):
        return (v - datetime.date(1970, 1, 1)).days
    if hasattr(v, "item"):
        return v.item()
    return v


def _sort_key(row: tuple) -> tuple:
    return tuple((v is None, "" if v is None else v)
                 for v in row if not isinstance(v, float))


def compare_answer(got_rows: Sequence[tuple], want_rows: Sequence[tuple],
                   ordered: bool) -> Tuple[bool, float]:
    """``(exact parts agree, widest relative gap of the floats)``."""
    got = [tuple(_plain(v) for v in r) for r in got_rows]
    want = [tuple(_plain(v) for v in r) for r in want_rows]
    if len(got) != len(want):
        return False, math.inf
    if not ordered:
        got, want = sorted(got, key=_sort_key), sorted(want, key=_sort_key)
    gap = 0.0
    for g_row, w_row in zip(got, want):
        if len(g_row) != len(w_row):
            return False, math.inf
        for g, w in zip(g_row, w_row):
            if isinstance(w, float) and isinstance(g, float):
                if math.isnan(w) or math.isnan(g):
                    if math.isnan(w) != math.isnan(g):
                        return False, math.inf
                elif w == 0.0:
                    gap = max(gap, 0.0 if g == 0.0 else math.inf)
                else:
                    gap = max(gap, abs(g - w) / abs(w))
            elif g != w or type(g) is not type(w):
                return False, gap
    return True, gap


def judge(answers: List[Tuple[str, Sequence[tuple]]],
          references: Dict[str, Sequence[tuple]], ordered: Dict[str, bool],
          gap_limits: Dict[str, float], failed: int) -> dict:
    """The numbers compared, each beside its limit, and the verdict.
    ``answers`` is ``(query name, rows)`` for every answer that came."""
    wrong, gap, gap_limit = 0, 0.0, math.inf
    for name, rows in answers:
        exact, g = compare_answer(rows, references[name], ordered[name])
        wrong += not exact
        if exact:
            gap = max(gap, g)
        gap_limit = min(gap_limit, gap_limits[name])
    numbers = {
        "compared": {"value": len(answers), "at_least": 1},
        "failed": {"value": failed, "limit": 0},
        "wrong_answers": {"value": wrong, "limit": 0},
        "max_rel_gap": {"value": gap, "limit": gap_limit},
    }
    correct = (len(answers) >= 1 and failed == 0 and wrong == 0
               and gap <= gap_limit)
    return {"correct": bool(correct), "numbers": numbers}
