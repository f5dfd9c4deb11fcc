"""Critical-path attribution: an exact wall-time decomposition per query.

The per-site totals in :class:`~spark_rapids_tpu.obs.profile.QueryProfile`
sum each site's span wall independently, so overlapping work double
counts and host gaps vanish — "what would make this query faster" stays
a guess.  This module computes it instead: a sweep over the query's
event spans (all threads — a decode-pool or spill-writer span that the
runner blocks on is exactly the critical path) attributes every
nanosecond of the query window ``[t0, t1)`` to the highest-priority
site covering it, and the uncovered remainder to ``wait`` (host compute
/ runner wait).  By construction the segments sum to the window EXACTLY
— ``sum(last_metrics["critpath"].values()) == queryWallNs`` — and
the pinned test asserts it on a query that shuffles, spills and retries, serial and under
3-thread serve concurrency.

Priority encodes the blocking chain: ``device_wait`` — the host blocked
on the chip (a size read-back, the wait before a D2H copy, an
exchange's sync) — first, so an exchange's credit is its span wall
MINUS the waits nested inside it, i.e. the host-side shuffle cost.
``enqueue`` (the host wall of an asynchronous jitted call; never device
time) ranks below every site that names real work, and the outer host
parts of ``session.execute_with_metrics`` (``stage``, ``stage_inputs``,
``result``, ``plan``, ``bookkeeping``) last, innermost first: each is
credited only what nothing inside it claims.  The window is the whole
of ``execute_with_metrics``, so the segments are ``queryWallNs``.

Engine-free (stdlib only, duck-typed events) so ``rapidsprof
--critpath`` reconstructs the same decomposition offline from a JSONL
event log.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from .events import SPAN, field

#: Site attribution priority, highest first.  ``wait`` (uncovered wall)
#: is not a site — it is the remainder.
SITE_PRIORITY: Tuple[str, ...] = (
    "device_wait", "h2d", "d2h", "spill", "unspill", "exchange", "mesh",
    "scan", "io", "pallas", "retry", "fault", "enqueue",
    "stage", "stage_inputs", "result", "plan", "bookkeeping",
)

WAIT = "wait"
OTHER = "other"


def _rank(site: str) -> int:
    try:
        return SITE_PRIORITY.index(site)
    except ValueError:
        return len(SITE_PRIORITY)  # unknown sites: lowest known priority


class CritPath:
    """One query's decomposition.  ``segments`` maps site (plus
    ``wait``) -> attributed ns; ``chain`` is the merged timeline of
    (site, t0, t1) runs, in order.  ``total_ns`` == window width and
    ``sum(segments.values()) == total_ns`` exactly."""

    def __init__(self, t0: int, t1: int, segments: Dict[str, int],
                 chain: List[Tuple[str, int, int]]):
        self.t0 = t0
        self.t1 = t1
        self.total_ns = max(0, t1 - t0)
        self.segments = segments
        self.chain = chain

    @property
    def attributed_ns(self) -> int:
        """Nanoseconds attributed to concrete sites (window minus the
        ``wait`` remainder) — the ``critpathAttributedNs`` metric."""
        return self.total_ns - self.segments.get(WAIT, 0)

    def top_site(self) -> str:
        """The dominant segment — bench's ``critpath_top_site``."""
        if not self.segments:
            return ""
        return max(self.segments.items(), key=lambda kv: kv[1])[0]

    def summary(self) -> str:
        lines = [
            f"critical path: {self.total_ns / 1e6:.2f} ms wall, "
            f"{self.attributed_ns / 1e6:.2f} ms attributed "
            f"({100.0 * self.attributed_ns / self.total_ns if self.total_ns else 0.0:.0f}%)"
        ]
        for site, ns in sorted(self.segments.items(),
                               key=lambda kv: -kv[1]):
            if ns <= 0:
                continue
            pct = 100.0 * ns / self.total_ns if self.total_ns else 0.0
            lines.append(f"  {site:<9} {ns / 1e6:>9.2f} ms  {pct:>5.1f}%")
        return "\n".join(lines)


def compute(events: List[Any], t0: int, t1: int) -> CritPath:
    """Decompose the window ``[t0, t1)`` over ``events``.

    Spans are clipped to the window; instants carry no width and are
    ignored.  Every elementary slice between consecutive span boundaries
    is attributed to the highest-priority site with a span covering it;
    slices no span covers go to ``wait``.  Total is exact by
    construction: the slices partition the window."""
    t0, t1 = int(t0), int(t1)
    if t1 <= t0:
        return CritPath(t0, t1, {}, [])
    spans: List[Tuple[int, int, int, str]] = []  # (start, end, rank, site)
    cuts = {t0, t1}
    for ev in events:
        if field(ev, "kind") != SPAN:
            continue
        raw_t0 = int(field(ev, "t0", 0) or 0)
        if raw_t0 <= 0:
            continue  # unstamped span: no defensible placement
        s = max(t0, raw_t0)
        e = min(t1, int(field(ev, "t1", 0) or 0))
        if e <= s:
            continue
        site = field(ev, "site") or OTHER
        spans.append((s, e, _rank(site), site))
        cuts.add(s)
        cuts.add(e)
    bounds = sorted(cuts)
    # active-span sweep: spans sorted by start; a heap-free variant is
    # fine at per-query event counts (ring-bounded)
    spans.sort()
    segments: Dict[str, int] = {}
    chain: List[Tuple[str, int, int]] = []
    si = 0
    active: List[Tuple[int, int, str]] = []  # (rank, end, site)
    for i in range(len(bounds) - 1):
        lo, hi = bounds[i], bounds[i + 1]
        while si < len(spans) and spans[si][0] <= lo:
            s, e, rank, site = spans[si]
            active.append((rank, e, site))
            si += 1
        active = [a for a in active if a[1] > lo]
        if active:
            site = min(active)[2]
        else:
            site = WAIT
        segments[site] = segments.get(site, 0) + (hi - lo)
        if chain and chain[-1][0] == site and chain[-1][2] == lo:
            chain[-1] = (site, chain[-1][1], hi)
        else:
            chain.append((site, lo, hi))
    return CritPath(t0, t1, segments, chain)


def from_profile(profile) -> Optional[CritPath]:
    """Decompose a :class:`QueryProfile` over its recorded query window
    (``qt0_ns``/``qt1_ns``, stamped by ``session.execute``).  Falls back
    to the event extent for pre-v2 logs without window stamps; None when
    no window is known at all."""
    qt0 = int(getattr(profile, "qt0_ns", 0) or 0)
    qt1 = int(getattr(profile, "qt1_ns", 0) or 0)
    if qt1 <= qt0:
        qt0 = int(getattr(profile, "t_min", 0) or 0)
        qt1 = int(getattr(profile, "t_max", 0) or 0)
    if qt1 <= qt0:
        return None
    return compute(profile.events, qt0, qt1)
