"""Query-scoped observability event bus and per-query attribution scopes.

The engine's instrumentation chokepoints emit typed span/instant events
into a bounded ring buffer while a query runs — spans through ONE
emitter, ``utils.tracing.span``, which also puts each on the profiler's
timeline (only it calls :func:`emit_span`); instants directly
(``fault.*``, ``plan.adaptive``, ``history``, ``serve``); ``session.execute`` opens a
:class:`QueryScope` before its metric snapshots and drains it after, so
the event window matches the metric deltas exactly.  The reference
analogue is the Spark event log + the SQL UI's per-exec metrics feed,
with ``NvtxWithMetrics`` (NvtxWithMetrics.scala:27-36) as the span model.

Concurrency model (the serving runtime runs N ``session.execute`` calls
at once):

* Every top-level execute opens its own scope, bound to the opening
  thread in a thread->scope registry.  Helper threads a query spawns
  (stage read-ahead, spill writers, the deadline watchdog) are *adopted*
  into the spawning query's scope via :func:`adopt`, so their events and
  counters attribute to the right query.
* When exactly ONE scope is open process-wide (the serial case — all of
  tier-1), unbound threads fall back to that scope, which makes the
  concurrent model bit-identical to the old single-global-bus behavior.
  Under true concurrency an unbound, unadopted thread has no scope and
  its events vanish rather than pollute a random query's timeline.
* Scopes also carry the per-query metric counters
  (``utils.compile_registry`` / ``fault.metrics`` credit the current
  scope alongside their process-cumulative tallies) and the per-query
  fault-injection registry, so concurrent queries neither mix their
  compile/dispatch economics nor each other's injected faults.

Design constraints (rapidslint R2/R3/R4 apply here like everywhere):

* **Disabled path is cheap**: :func:`emit_span` / :func:`emit_instant`
  cost one dict probe + one ``is None`` test when no scope is open — the
  same disarmed-hook pattern as ``fault.inject.maybe_fire``.
* **Bounded**: the ring holds at most ``obs.ring.maxEvents`` events; once
  full, later events are counted in ``dropped`` instead of appended
  (surfaced as ``last_metrics['obsEventsDropped']``) — profiling a
  pathological query can never grow memory without bound.
* **No blocking**: appends take one uncontended lock, no waits, no joins.
* **Engine-free**: this module imports only the stdlib, so
  ``tools/rapidsprof.py`` can load the ``obs`` package standalone
  (the ``rapidslint`` loader pattern) without pulling in jax.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from . import timeseries as _ts

SPAN = "span"
INSTANT = "instant"

#: Process-cumulative events dropped across every query ring — the
#: telemetry gauge feed (per-query drops surface via obsEventsDropped).
_RING_DROPS_TOTAL = 0


def ring_drops_total() -> int:
    return _RING_DROPS_TOTAL


class Event:
    """One timeline entry.  ``kind`` is ``span`` (t0..t1) or ``instant``
    (t0 == t1); times are ``time.monotonic_ns`` stamps; ``site`` names the
    emitting chokepoint (device_wait/enqueue/stage/h2d/d2h/spill/unspill/
    exchange/retry/fault/adaptive/io/plan/…, the list is in
    ``utils/tracing.py``); ``op_id`` ties the event to a physical-plan
    node when the site knows one."""

    __slots__ = ("kind", "site", "name", "op_id", "t0", "t1", "thread",
                 "payload")

    def __init__(self, kind: str, site: str, name: str, op_id: str,
                 t0: int, t1: int, thread: str,
                 payload: Optional[Dict[str, Any]]):
        self.kind = kind
        self.site = site
        self.name = name
        self.op_id = op_id
        self.t0 = t0
        self.t1 = t1
        self.thread = thread
        self.payload = payload

    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "kind": self.kind, "site": self.site, "name": self.name,
            "op_id": self.op_id, "t0": self.t0, "t1": self.t1,
            "thread": self.thread,
        }
        if self.payload:
            d["payload"] = self.payload
        return d

    def __repr__(self):
        return (f"Event({self.kind} {self.site}:{self.name} "
                f"op={self.op_id or '-'} dur={self.t1 - self.t0}ns)")


def field(ev, key: str, default=None):
    """Duck-typed event accessor: works on :class:`Event` objects and on
    the plain dicts a JSONL event log round-trips through."""
    if isinstance(ev, dict):
        return ev.get(key, default)
    return getattr(ev, key, default)


class EventBus:
    """Bounded ring of events.  Append-only while the epoch is open; the
    first ``max_events`` events win and later ones increment ``dropped``
    (deterministic for tests, and the query *start* — scans, first
    dispatches, spill onset — is what a truncated profile needs most)."""

    def __init__(self, max_events: int):
        self._max = max(1, int(max_events))
        self._lock = threading.Lock()
        self._events: deque = deque()
        self._dropped = 0
        #: site -> drop count; a truncated profile's rollups silently
        #: under-attribute exactly these sites, so the summary banner
        #: must name them
        self._dropped_by_site: Dict[str, int] = {}

    def append(self, ev: Event) -> None:
        global _RING_DROPS_TOTAL
        with self._lock:
            if len(self._events) >= self._max:
                self._dropped += 1
                site = getattr(ev, "site", None) or "?"
                self._dropped_by_site[site] = \
                    self._dropped_by_site.get(site, 0) + 1
                _RING_DROPS_TOTAL += 1
                return
            self._events.append(ev)

    def drop_sites(self) -> Dict[str, int]:
        """Per-site drop counts since the last drain."""
        with self._lock:
            return dict(self._dropped_by_site)

    def drain(self) -> Tuple[List[Event], int]:
        with self._lock:
            evs = list(self._events)
            self._events.clear()
            dropped = self._dropped
            self._dropped = 0
            self._dropped_by_site = {}
            return evs, dropped

    def __len__(self):
        with self._lock:
            return len(self._events)


class QueryScope:
    """One executing query's attribution context.

    Carries the (optional) event ring, the per-query metric counters
    that ``utils.compile_registry`` / ``fault.metrics`` credit alongside
    their process-wide tallies, and the query's fault-injection
    registry.  A scope exists for every top-level ``session.execute``
    even with obs disabled — counter attribution and fault scoping are
    needed regardless; only ``bus`` is gated by ``obs.enabled``."""

    __slots__ = ("query_id", "bus", "fault_registry", "_lock", "_counters")

    def __init__(self, query_id: int, bus: Optional[EventBus]):
        self.query_id = query_id
        self.bus = bus
        self.fault_registry = None
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {}

    def add(self, key: str, n) -> None:
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + n

    def counters_for(self, keys) -> Dict[str, int]:
        """This query's counter values for ``keys`` (0 when never hit) —
        the concurrent-safe replacement for a global snapshot delta."""
        with self._lock:
            return {k: self._counters.get(k, 0) for k in keys}


# Thread -> scope bindings plus the single-open-scope fallback.  With
# exactly one scope open, every thread resolves to it (identical to the
# historical one-global-bus behavior); with several open, only bound /
# adopted threads attribute.
_SCOPES: Dict[int, QueryScope] = {}
_OPEN: List[QueryScope] = []
_FALLBACK: Optional[QueryScope] = None
_QUERY_SEQ = 0
_EPOCH_LOCK = threading.Lock()


def current_scope() -> Optional[QueryScope]:
    """The scope the calling thread attributes to: its own binding, else
    the sole open scope, else None."""
    return _SCOPES.get(threading.get_ident()) or _FALLBACK


def task_key() -> Optional[QueryScope]:
    """Identity key for "which query/task is this thread working for" —
    used by the TpuSemaphore's per-task re-entrancy.  None = the
    process-wide default task (work outside any query)."""
    return _SCOPES.get(threading.get_ident()) or _FALLBACK


def scope_add(key: str, n) -> None:
    """Credit ``n`` to the current scope's ``key`` counter (no-op when
    the calling thread attributes to no query)."""
    sc = _SCOPES.get(threading.get_ident()) or _FALLBACK
    if sc is not None:
        sc.add(key, n)


def active() -> bool:
    """True while the calling thread attributes to a scope with a live
    event ring — sites with costly payload construction may check this
    first; plain emits don't need to."""
    sc = _SCOPES.get(threading.get_ident()) or _FALLBACK
    return sc is not None and sc.bus is not None


def _recompute_fallback_locked() -> None:
    global _FALLBACK
    _FALLBACK = _OPEN[0] if len(_OPEN) == 1 else None


def begin_query(enabled: bool, max_events: int) -> Optional[QueryScope]:
    """Open a per-query scope bound to the calling thread; returns the
    scope for :func:`end_query`, or None when this thread already runs
    inside a scope (a nested execute — prewarm, recovery re-lowering —
    neither resets nor drains: its events fold into the outer query's
    timeline).  ``enabled`` gates only the event ring; the scope itself
    (counters, fault registry, task identity) always exists."""
    global _QUERY_SEQ
    ident = threading.get_ident()
    with _EPOCH_LOCK:
        if _SCOPES.get(ident) is not None:
            return None
        _QUERY_SEQ += 1
        scope = QueryScope(
            _QUERY_SEQ, EventBus(max_events) if enabled else None)
        _SCOPES[ident] = scope
        _OPEN.append(scope)
        _recompute_fallback_locked()
        return scope


def end_query(scope: Optional[QueryScope]
              ) -> Tuple[List[Event], int, Dict[str, int]]:
    """Close ``scope`` and drain its (events, dropped, dropped_by_site).
    A None scope (nested execute) is a no-op returning ([], 0, {}).
    Straggler emits after the close (e.g. an async spill writer
    finishing late) find no scope and vanish."""
    if scope is None:
        return [], 0, {}
    with _EPOCH_LOCK:
        for ident in [i for i, s in _SCOPES.items() if s is scope]:
            del _SCOPES[ident]
        if scope in _OPEN:
            _OPEN.remove(scope)
        _recompute_fallback_locked()
    if scope.bus is None:
        return [], 0, {}
    by_site = scope.bus.drop_sites()
    events, dropped = scope.bus.drain()
    return events, dropped, by_site


class _adopt_ctx:
    """Bind the calling thread to ``scope`` for the duration (restoring
    any previous binding on exit).  No-op for a None scope or when the
    thread is already bound to it."""

    def __init__(self, scope: Optional[QueryScope]):
        self._scope = scope
        self._ident = None
        self._prev = None

    def __enter__(self):
        if self._scope is None:
            return self
        ident = threading.get_ident()
        with _EPOCH_LOCK:
            prev = _SCOPES.get(ident)
            if prev is self._scope:
                return self
            self._ident = ident
            self._prev = prev
            _SCOPES[ident] = self._scope
        return self

    def __exit__(self, *exc):
        if self._ident is None:
            return False
        with _EPOCH_LOCK:
            if self._prev is None:
                _SCOPES.pop(self._ident, None)
            else:
                _SCOPES[self._ident] = self._prev
        return False


def adopt(scope: Optional[QueryScope]) -> "_adopt_ctx":
    """Context manager a helper thread uses to attribute its work to the
    query that spawned it: capture ``current_scope()`` at submit/spawn
    time on the query thread, then run the helper body under
    ``with adopt(scope):``."""
    return _adopt_ctx(scope)


def emit_span(site: str, name: str, op_id: str = "",
              t0: int = 0, t1: int = 0, **payload) -> None:
    """Record a timed range.  No-op outside a scope with a live ring
    (the continuous telemetry fold still runs — it is process-scoped,
    not query-scoped, so late async-writer spans and inter-query work
    stay visible in the time-series view)."""
    if _ts._RING is not None:
        _ts.record_span(site, t1 - t0, int(payload.get("bytes", 0) or 0))
    sc = _SCOPES.get(threading.get_ident()) or _FALLBACK
    if sc is None or sc.bus is None:
        return
    sc.bus.append(Event(SPAN, site, name, op_id, t0, t1,
                        threading.current_thread().name, payload or None))


def emit_instant(site: str, name: str, op_id: str = "", **payload) -> None:
    """Record a point event stamped now.  No-op outside a scope with a
    live ring (the telemetry fold counts it regardless, like spans)."""
    if _ts._RING is not None:
        _ts.record_span(site, 0, int(payload.get("bytes", 0) or 0))
    sc = _SCOPES.get(threading.get_ident()) or _FALLBACK
    if sc is None or sc.bus is None:
        return
    t = time.monotonic_ns()
    sc.bus.append(Event(INSTANT, site, name, op_id, t, t,
                        threading.current_thread().name, payload or None))
