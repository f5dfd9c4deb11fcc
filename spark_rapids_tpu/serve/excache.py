"""Process-wide shared physical-plan / executable cache.

PR 2 introduced the plan-fingerprint memo so re-executing the same
DataFrame reuses physical exec instances and therefore their
``jax.jit`` caches; PR 11 lifted it from the session to a lock-guarded
process singleton: the compiled executables live on the physical plan's
op instances (``plan/pipeline._stage_program`` caches jits on the root
op), so sharing the plan object shares every executable — the second
session's warm execution reports ``compileCount == 0``.

Keying is (plan SHAPE fingerprint, plan-relevant conf state): the
fingerprint of ``plan/logical.plan_shape`` encodes a lifted literal by
slot and type, not by value, so every query of one shape — the same SQL
text with other substitution parameters — shares one entry, one physical
plan and one set of executables; its values are bound per execution
(:meth:`PlanEntry.bind`) and never written into the shared plan.  See
``session.plan_bound`` for what the conf state excludes.

Lifetime is LRU and nothing else: an entry lives until
``spark.rapids.sql.tpu.serve.planCache.maxPlans`` newer ones, or what it
pins, push it out.  It holds strongly whatever its fingerprint names by
``id()`` (host batches, cache holders, user functions), so a recycled
``id()`` can never give a false hit.  What that pins is bounded: plans
over in-memory host batches — one-shot plans, as a rule: nobody can send
that batch again — are kept ``MAX_PINNING_PLANS`` deep and
``MAX_PINNED_BYTES`` wide over the whole cache, so a stream of them
evicts its own oldest entries instead of holding ``maxPlans`` dead inputs
and their executables (a loaded executable costs address-space mappings,
and a process has 65,530).

Metrics stay attributed per query: the cache only shares PLANS; every
execution still opens its own QueryScope and counts its own dispatches
(a shared-cache hit shows up precisely as ``compileCount == 0``).

Thread safety: lookups and inserts hold the cache lock; plan BUILDING
(``TpuOverrides.lower``) runs outside it so a slow lowering cannot
stall unrelated sessions.  Two sessions racing to build the same key
both build; the first insert wins and the loser adopts the winner's
plan (build is pure planning — no device state — so discarding the
duplicate is free).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Callable, Sequence, Tuple

from spark_rapids_tpu.utils.params import BoundParams

DEFAULT_MAX_PLANS = 256
#: host-batch bytes all entries together may keep alive, and how many
#: entries may keep any
MAX_PINNED_BYTES = 256 << 20
MAX_PINNING_PLANS = 32
#: device-scalar tuples an entry keeps, one per recently bound value set
MAX_BOUND_SETS = 128


def _pinned_bytes(pinned: Sequence[Any]) -> int:
    """Host bytes ``pinned`` keeps alive: the batches of an in-memory
    scan.  Anything else (a cache holder's device batches belong to the
    spill catalog's budget; a function) counts nothing."""
    from spark_rapids_tpu.batch import HostBatch
    return sum(getattr(c.values, "nbytes", 0)
               + getattr(c.validity, "nbytes", 0)
               for b in pinned if isinstance(b, HostBatch)
               for c in b.columns)


class PlanEntry:
    """One shape's physical plan, its explain, what its fingerprint pins,
    and the device scalars of the value sets bound to it lately."""

    __slots__ = ("phys", "explain", "dtypes", "pinned", "pinned_bytes",
                 "_bound", "_lock")

    def __init__(self, phys, explain, dtypes, pinned):
        self.phys = phys
        self.explain = explain
        self.dtypes = dtypes
        self.pinned = pinned
        self.pinned_bytes = _pinned_bytes(pinned)
        self._bound: "OrderedDict[Any, BoundParams]" = OrderedDict()
        self._lock = threading.Lock()

    def bind(self, values: Tuple) -> BoundParams:
        """This execution's parameters: ``values`` slot by slot and the
        device scalars a program is called with.  A value set bound
        before (a held statement, a dashboard's few parameter sets) finds
        its scalars again, so a repeat costs no transfer."""
        if not values:
            return BoundParams((), ())
        import jax.numpy as jnp
        from spark_rapids_tpu.runtime.device import DeviceRuntime
        # a device-lost recovery rebuilds the runtime: scalars of a dead
        # generation are never handed out again.  Keyed on the spelling:
        # 1, 1.0 and True are equal and hash alike, and so do 0.0 and -0.0
        key = (DeviceRuntime.generation(), tuple(map(repr, values)))
        with self._lock:
            got = self._bound.get(key)
            if got is not None:
                self._bound.move_to_end(key)
                return got
        got = BoundParams(values, tuple(
            jnp.asarray(v, dtype=dt.jnp_dtype)
            for v, dt in zip(values, self.dtypes)))
        with self._lock:
            self._bound[key] = got
            while len(self._bound) > MAX_BOUND_SETS:
                self._bound.popitem(last=False)
        return got


class SharedPlanCache:
    """(shape fingerprint, conf state) -> :class:`PlanEntry` with LRU
    eviction by count and by pinned bytes, shared by every session in the
    process."""

    def __init__(self, max_plans: int = DEFAULT_MAX_PLANS):
        self._lock = threading.Lock()
        self._plans: "OrderedDict[Any, PlanEntry]" = OrderedDict()
        self._max = max(1, int(max_plans))
        self._pinned_bytes = 0
        self._pinning = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def _drop_locked(self, key) -> None:
        ent = self._plans.pop(key)
        self._pinned_bytes -= ent.pinned_bytes
        self._pinning -= bool(ent.pinned_bytes)
        self.evictions += 1

    def _evict_locked(self) -> None:
        while len(self._plans) > self._max:
            self._drop_locked(next(iter(self._plans)))
        # the newest entry stays, whatever it pins
        while self._pinning > 1 and (
                self._pinning > MAX_PINNING_PLANS
                or self._pinned_bytes > MAX_PINNED_BYTES):
            self._drop_locked(next(
                k for k, e in self._plans.items() if e.pinned_bytes))

    def set_max_plans(self, max_plans: int) -> None:
        with self._lock:
            self._max = max(1, int(max_plans))
            self._evict_locked()

    def get_or_build(self, key: Any, conf_state: Tuple,
                     builder: Callable[[], PlanEntry]
                     ) -> Tuple[PlanEntry, bool]:
        """Return ``(entry, hit)`` for ``key``; on miss call ``builder()``
        outside the lock and insert first-writer-wins.

        The stored key is ``(key, conf_state)``: two sessions with
        different plan-relevant conf alternating over the same
        fingerprint each keep their own entry instead of thrashing
        one slot (and re-compiling on every alternation)."""
        full = (key, conf_state)
        with self._lock:
            ent = self._plans.get(full)
            if ent is not None:
                self._plans.move_to_end(full)
                self.hits += 1
                return ent, True
        built = builder()
        with self._lock:
            ent = self._plans.get(full)
            if ent is not None:
                # a concurrent builder won the race: use ITS plan so
                # both sessions share one set of executables
                self._plans.move_to_end(full)
                self.hits += 1
                return ent, True
            self.misses += 1
            self._plans[full] = built
            self._pinned_bytes += built.pinned_bytes
            self._pinning += bool(built.pinned_bytes)
            self._evict_locked()
        return built, False

    def stats(self):
        with self._lock:
            return {"plan_cache_entries": len(self._plans),
                    "plan_cache_hits": self.hits,
                    "plan_cache_misses": self.misses,
                    "plan_cache_evictions": self.evictions,
                    "plan_cache_pinned_bytes": self._pinned_bytes}

    def clear(self) -> None:
        with self._lock:
            self._plans.clear()
            self._pinned_bytes = self._pinning = 0

    def __len__(self):
        with self._lock:
            return len(self._plans)


_SHARED: SharedPlanCache = SharedPlanCache()


def shared_plan_cache() -> SharedPlanCache:
    """The process singleton every ``session.plan_bound`` consults."""
    return _SHARED
