"""The one traffic generator: reads a mix (``traffic/<name>.json``) and drives
``issue(query_name)`` for the length of the window.

A mix says::

    {"loop": "closed", "clients": 1, "queries": ["q6"],
     "statement": "held", "text_submissions_in_setup": 2, "source": "...",
     "trace": {"queries": 10, "max_seconds": 20.0}}

    {"loop": "open", "clients": 8, "queries": ["q6", "q1"],
     "rate_per_s": 2.5, "arrivals": "poisson"}

* ``closed``: each of ``clients`` callers sends its next query when the last
  one has answered.  No query starts once ``seconds`` have passed, and the
  window closes when the last one in flight answers: rates divide by that
  real elapsed time.
* ``open``: queries fall due on a schedule drawn from the seed at
  ``rate_per_s`` (``poisson`` gaps, or ``uniform``), whether or not earlier
  ones have answered; ``clients`` bounds how many are in flight.  A query's
  latency counts from when it was due.  Arrivals stop at ``seconds`` and the
  window closes when all have answered.

``statement``, ``text_submissions_in_setup`` (what one query is, and what the
client sends before the window: harness.py) and ``source`` (who sends such
traffic) are read by the harness, not here.

Every seed gives the same multiset of queries in another order: the list is
cycled through permutations drawn from the seed.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Iterator, List, Optional

import numpy as np


def query_order(queries: List[str], seed: int) -> Iterator[str]:
    r = np.random.RandomState(seed % (1 << 32))
    while True:
        for i in r.permutation(len(queries)):
            yield queries[i]


def due_times(mix: dict, seed: int, seconds: float) -> List[float]:
    """Open-loop arrival offsets in ``[0, seconds)``."""
    r = np.random.RandomState((seed + 1) % (1 << 32))
    n = int(mix["rate_per_s"] * seconds * 2) + 16
    gaps = r.exponential(1.0 / mix["rate_per_s"], n) \
        if mix.get("arrivals", "poisson") == "poisson" \
        else np.full(n, 1.0 / mix["rate_per_s"])
    t = np.cumsum(gaps)
    return [float(x) for x in t[t < seconds]]


def run_window(mix: dict, seed: int, seconds: float,
               issue: Callable[[str, Optional[float]], None],
               between: Optional[Callable[[], None]] = None) -> float:
    """Drives ``issue(query, due_at)`` (``due_at``: monotonic seconds, None in
    a closed loop) and returns the window's real length in seconds.
    ``between`` runs on the first client after each of its answers (the
    harness stops the profiler there)."""
    order = query_order(mix["queries"], seed)
    lock = threading.Lock()
    clients = int(mix.get("clients", 1))
    t0 = time.monotonic()
    if mix["loop"] == "closed":
        def client(first: bool) -> None:
            while time.monotonic() - t0 < seconds:
                with lock:
                    q = next(order)
                issue(q, None)
                if first and between is not None:
                    between()
    elif mix["loop"] == "open":
        due = iter([(t0 + d, next(order))
                    for d in due_times(mix, seed, seconds)])

        def client(first: bool) -> None:
            while True:
                with lock:
                    nxt = next(due, None)
                if nxt is None:
                    return
                time.sleep(max(0.0, nxt[0] - time.monotonic()))
                issue(nxt[1], nxt[0])
                if first and between is not None:
                    between()
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    others = [threading.Thread(target=client, args=(False,), daemon=True)
              for _ in range(clients - 1)]
    for t in others:
        t.start()
    client(True)
    for t in others:
        t.join()
    return time.monotonic() - t0
