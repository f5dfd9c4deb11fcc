"""Compile/dispatch economics: the registry behind every ``jax.jit``
entry point the execs use.

The reference engine pays no per-query compile tax — cudf kernels ship
precompiled — so its metrics layer never needed to account for it.  On
TPU every new (program, shape-bucket) pair costs an XLA compile that can
dwarf the query itself, and every dispatched program costs a host->device
round trip.  This module makes both quantities *measured*:

* :func:`instrumented_jit` wraps ``jax.jit`` so each call is counted as a
  dispatch, and a growth of the jitted function's executable cache is
  counted as a compile (with the call's wall time attributed to
  ``compile_wall_ns`` — compile-inclusive first-call wall, the number a
  user actually waits for).
* The process-wide tallies are snapshotted around each query by
  ``session.execute`` into ``last_metrics`` (``compileCount``,
  ``compileWallNs``, ``dispatchCount``, ``compiledShapes``) and surfaced
  by ``bench.py`` as ``compile_s``.
* :func:`enable_persistent_cache` owns the placement of JAX's persistent
  compilation cache (``JAX_COMPILATION_CACHE_DIR`` wins, else conf
  ``spark.rapids.sql.tpu.compileCacheDir``, else ``<checkout>/.jax_cache``)
  so repeated processes skip recompilation entirely.

Data-plane accounting rides the same snapshot/delta machinery:

* ``donate_argnums`` passes through :func:`instrumented_jit` to ``jax.jit``
  and every donated call adds the donated arguments' buffer bytes to
  ``donated_bytes`` (surfaced as ``session.last_metrics['donatedBytes']``).
  The :func:`donation_guard` context manager arms a use-after-donate
  assertion for tests: once a buffer has been donated, presenting it to
  any later instrumented call (or sync site registered via
  :func:`guard_check`) raises.
* :func:`record_transfer` accumulates host<->device staging bytes and
  wall time (``h2d_bytes``/``h2d_ns``/``d2h_bytes``/``d2h_ns``) from the
  batch staging layer, feeding bench.py's ``h2d_gb_per_sec`` /
  ``d2h_gb_per_sec``.

When available, ``jax.monitoring`` backend-compile duration events are
also accumulated (``backend_compile_ns``) — pure XLA compile seconds,
excluding the first-run execution that the wall number includes.
"""

from __future__ import annotations

import functools
import logging
import os
import sys
import threading
import time
from typing import Any, Callable, Dict, Optional

import jax

from spark_rapids_tpu.fault import inject as _fault_inject
from spark_rapids_tpu.obs import events as _obs_events

_LOCK = threading.Lock()
_STATS: Dict[str, int] = {
    # cumulative process-wide; per-query deltas come from snapshot() pairs
    "compiles": 0,          # executable-cache misses observed at call sites
    "cache_bypass_compiles": 0,  # ... of which donating (never persisted)
    "compile_wall_ns": 0,   # wall ns of calls that triggered a compile
    "dispatches": 0,        # jitted program invocations
    "backend_compile_ns": 0,  # jax.monitoring backend compile durations
    "donated_bytes": 0,     # input buffer bytes donated to dispatches
    "h2d_bytes": 0,         # host->device staging bytes
    "h2d_ns": 0,            # host->device staging wall ns
    "d2h_bytes": 0,         # device->host bulk-copy bytes
    "d2h_ns": 0,            # device->host bulk-copy wall ns
}
_LABEL_COMPILES: Dict[str, int] = {}


def snapshot() -> Dict[str, int]:
    """Copy of the cumulative counters (take two and subtract for a
    per-query delta)."""
    with _LOCK:
        return dict(_STATS)


def delta(before: Dict[str, int], after: Dict[str, int]) -> Dict[str, int]:
    return {k: after[k] - before.get(k, 0) for k in after}


def compiled_shapes() -> int:
    """Cumulative executables compiled at registered call sites — an UPPER
    BOUND on distinct (program, shape-bucket) cardinality.  Exact within a
    session (plan/exec memoization means a shape compiles once); across
    sessions the same shape recompiles and is counted again, so suite-level
    trends, not absolute cardinality, are what this metric shows."""
    with _LOCK:
        return _STATS["compiles"]


def per_label_compiles() -> Dict[str, int]:
    with _LOCK:
        return dict(_LABEL_COMPILES)


def _record(label: str, compiled: bool, wall_ns: int,
            donated_bytes: int = 0, bypassed_cache: bool = False) -> None:
    with _LOCK:
        _STATS["dispatches"] += 1
        _STATS["donated_bytes"] += donated_bytes
        if compiled:
            _STATS["compiles"] += 1
            _STATS["compile_wall_ns"] += wall_ns
            _LABEL_COMPILES[label] = _LABEL_COMPILES.get(label, 0) + 1
            if bypassed_cache:
                _STATS["cache_bypass_compiles"] += 1
    # credit the executing query's scope as well: under concurrent
    # serving the global delta mixes queries, so session.execute reads
    # these per-scope counters instead
    sc = _obs_events.current_scope()
    if sc is not None:
        sc.add("dispatches", 1)
        if donated_bytes:
            sc.add("donated_bytes", donated_bytes)
        if compiled:
            sc.add("compiles", 1)
            sc.add("compile_wall_ns", wall_ns)


def record_transfer(kind: str, nbytes: int, wall_ns: int) -> None:
    """Accumulate one host<->device staging pass (kind: "h2d" | "d2h")."""
    with _LOCK:
        _STATS[kind + "_bytes"] += int(nbytes)
        _STATS[kind + "_ns"] += int(wall_ns)
    sc = _obs_events.current_scope()
    if sc is not None:
        sc.add(kind + "_bytes", int(nbytes))
        sc.add(kind + "_ns", int(wall_ns))
    if _obs_events.active():
        now = time.monotonic_ns()
        _obs_events.emit_span(kind, "transfer", t0=now - int(wall_ns),
                              t1=now, bytes=int(nbytes))


# -- use-after-donate guard (tests) ------------------------------------------

# When armed, maps id(array) -> (donating label, strong ref).  The strong
# ref pins the array object so a GC'd id can never be reused by a fresh
# buffer and false-positive.
_DONATION_GUARD: Optional[Dict[int, tuple]] = None


class _guard_ctx:
    def __enter__(self):
        global _DONATION_GUARD
        self._prev = _DONATION_GUARD
        _DONATION_GUARD = {}
        return _DONATION_GUARD

    def __exit__(self, *exc):
        global _DONATION_GUARD
        _DONATION_GUARD = self._prev
        return False


def donation_guard() -> "_guard_ctx":
    """Context manager arming the use-after-donate assertion: every
    instrumented dispatch (and every sync site calling :func:`guard_check`)
    verifies none of its inputs were previously donated."""
    return _guard_ctx()


def guard_check(tree, site: str) -> None:
    """Assert no leaf of ``tree`` was donated to an earlier dispatch.
    No-op unless :func:`donation_guard` is armed (hot paths pay one
    ``is None`` test)."""
    guard = _DONATION_GUARD
    if guard is None:
        return
    for leaf in jax.tree_util.tree_leaves(tree):
        hit = guard.get(id(leaf))
        if hit is not None:
            raise AssertionError(
                f"use-after-donate: {site} received a buffer already "
                f"donated to {hit[0]}")


def _guard_mark(label: str, leaves) -> None:
    guard = _DONATION_GUARD
    if guard is None:
        return
    for leaf in leaves:
        guard[id(leaf)] = (label, leaf)


def _cache_size(jitted) -> int:
    try:
        return jitted._cache_size()
    except Exception:  # noqa: BLE001 — older/newer jax without the probe
        return -1


# -- persistent-cache bypass for donating executables -------------------------
#
# XLA:CPU (jax 0.4.37): an executable DESERIALIZED from the persistent
# compilation cache mishandles input-output aliasing — donated input
# buffers are freed while the deserialized program still reads them
# (wrong results and segfaults; reproduced 8/8 with a populated cache,
# 0/8 with the cache disabled, identical code).  Freshly *compiled*
# donating executables are sound, so donating programs simply never
# enter the persistent cache: while a donating dispatch is on the
# current thread, cache reads return a miss and writes are dropped.
# Non-donating programs (the vast majority of compile time) keep full
# persistence.

_NO_PERSIST = threading.local()
_CACHE_BYPASS_INSTALLED = False


class _no_persist_scope:
    def __enter__(self):
        _NO_PERSIST.depth = getattr(_NO_PERSIST, "depth", 0) + 1

    def __exit__(self, *exc):
        _NO_PERSIST.depth -= 1
        return False


def _install_cache_bypass() -> None:
    global _CACHE_BYPASS_INSTALLED
    with _LOCK:
        # under the lock, and the installed flag is only set AFTER the
        # hooks are swapped: a concurrent donation_supported() must not
        # see True while cache reads are still live (that window would
        # re-open the deserialized-donation use-after-free)
        if _CACHE_BYPASS_INSTALLED:
            return
        try:
            from jax._src import compilation_cache as _cc
            real_get = _cc.get_executable_and_time
            real_put = _cc.put_executable_and_time

            @functools.wraps(real_get)
            def get(*args, **kwargs):
                if getattr(_NO_PERSIST, "depth", 0):
                    return None, None
                return real_get(*args, **kwargs)

            @functools.wraps(real_put)
            def put(*args, **kwargs):
                if getattr(_NO_PERSIST, "depth", 0):
                    return None
                return real_put(*args, **kwargs)

            _cc.get_executable_and_time = get
            _cc.put_executable_and_time = put
        except Exception:  # noqa: BLE001 — private API moved: fall back
            # to disabling donation outright rather than risk the
            # use-after-free
            global _DONATION_FORCED_OFF
            _DONATION_FORCED_OFF = True
        _CACHE_BYPASS_INSTALLED = True


_DONATION_FORCED_OFF = False


def donation_supported() -> bool:
    """False when the persistent-cache bypass could not be installed (jax
    private API moved) — donation then stays off everywhere rather than
    risk cache-deserialized aliasing corruption."""
    _install_cache_bypass()
    return not _DONATION_FORCED_OFF


def _trace_state_clean() -> bool:
    """False while jax is tracing (a nested-jit call inlines, it doesn't
    dispatch)."""
    try:
        return jax.core.trace_state_clean()
    except Exception:  # noqa: BLE001
        return True


_DONATION_WARNING_FILTERED = False


def _filter_donation_warning() -> None:
    """Once per process: a donated input whose shape matches no output
    can't be aliased in place; jax warns per lowering, but the buffer is
    still consumed (freed at dispatch) — exactly the intent, so the
    warning is noise at our opt-in call sites.  One global filter entry,
    not one per donating jit (every warning check scans the list)."""
    global _DONATION_WARNING_FILTERED
    if _DONATION_WARNING_FILTERED:
        return
    _DONATION_WARNING_FILTERED = True
    import warnings
    warnings.filterwarnings(
        "ignore", message="Some donated buffers were not usable")


def instrumented_jit(fn: Optional[Callable] = None, *, label: str = "",
                     **jit_kwargs) -> Callable:
    """``jax.jit`` with dispatch/compile accounting.

    Usable as ``instrumented_jit(f, label=...)`` or as a decorator
    ``@instrumented_jit(label=..., static_argnames=...)``.  The wrapper is
    call-compatible with the jitted function; the raw jitted callable is
    exposed as ``wrapper.jitted``.  ``donate_argnums`` passes through to
    ``jax.jit``; donated argument bytes are accumulated per dispatch.
    """
    if fn is None:
        return functools.partial(instrumented_jit, label=label, **jit_kwargs)
    name = label or getattr(fn, "__name__", "jit")
    donate = tuple(jit_kwargs.get("donate_argnums") or ())
    if donate and not donation_supported():
        jit_kwargs = {k: v for k, v in jit_kwargs.items()
                      if k != "donate_argnums"}
        donate = ()
    if donate:
        _filter_donation_warning()
    jitted = jax.jit(fn, **jit_kwargs)

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if not _trace_state_clean():
            # nested call while an outer program is being traced: it
            # inlines into the outer jaxpr, so it is neither a device
            # dispatch nor a separate compile — don't count it (donation
            # of a traced value is likewise meaningless and ignored)
            return jitted(*args, **kwargs)
        # fault-injection site: every real dispatch (not nested traces)
        # counts; disarmed cost is one module-global None test
        _fault_inject.maybe_fire("dispatch")
        if _DONATION_GUARD is not None:
            guard_check((args, kwargs), name)
        donated_bytes = 0
        donated_leaves = ()
        if donate:
            donated_leaves = [
                leaf for i in donate if i < len(args)
                for leaf in jax.tree_util.tree_leaves(args[i])]
            donated_bytes = sum(
                getattr(leaf, "nbytes", 0) for leaf in donated_leaves)
        before = _cache_size(jitted)
        t0 = time.monotonic_ns()
        if donate:
            # a compile triggered by a donating dispatch must neither read
            # nor write the persistent cache (deserialized executables
            # mishandle the donation aliasing — see _install_cache_bypass)
            with _no_persist_scope():
                out = jitted(*args, **kwargs)
        else:
            out = jitted(*args, **kwargs)
        t1 = time.monotonic_ns()
        after = _cache_size(jitted)
        compiled = after >= 0 and after != before
        _record(name, compiled, t1 - t0, donated_bytes,
                bypassed_cache=bool(donate))
        if compiled:
            _obs_events.emit_span("dispatch", name, t0=t0, t1=t1,
                                  compiled=True)
        else:
            _obs_events.emit_span("dispatch", name, t0=t0, t1=t1)
        if donated_leaves:
            _guard_mark(name, donated_leaves)
        return out

    wrapper.jitted = jitted
    wrapper.label = name
    return wrapper


# -- jax.monitoring hook (precise backend compile seconds) -------------------

_MONITORING_HOOKED = False


#: Per-thread stack of the exception that was being handled when each
#: open compile phase started (normally None).  jax brackets tracing,
#: MLIR lowering and the backend compile with a start scalar and an end
#: duration that fires even while the phase unwinds by exception.
_COMPILE_PHASES = threading.local()


def _on_compile_phase_start(event: str, value: float, **kw) -> None:
    if "/compile/" not in event:
        return
    stack = getattr(_COMPILE_PHASES, "ambient", None)
    if stack is None:
        stack = _COMPILE_PHASES.ambient = []
    stack.append(sys.exception())


def _pin_compile_failure(fun_name: str) -> None:
    """Called as a compile phase ends: an exception in flight that was
    not already being handled when the phase began was raised BY the
    trace/lower/compile — a refusal that no replay can fix, whatever
    its status text says (Mosaic refusals read ``INTERNAL``)."""
    stack = getattr(_COMPILE_PHASES, "ambient", None)
    ambient = stack.pop() if stack else None
    err = sys.exception()
    if err is None or err is ambient or \
            getattr(err, "rapids_error_class", None) is not None:
        return
    from spark_rapids_tpu.fault.errors import mark_non_retryable
    mark_non_retryable(err)
    err.add_note(f"raised while lowering/compiling program "
                 f"'{fun_name}': a compile refusal, not a device loss "
                 f"(never retried, never completed on the CPU)")


def _on_event_duration(event: str, duration_secs: float, **kw) -> None:
    if "compil" not in event:
        return
    if "/compile/" in event:
        _pin_compile_failure(kw.get("fun_name", "?"))
    with _LOCK:
        _STATS["backend_compile_ns"] += int(duration_secs * 1e9)
    # the listener fires on the dispatching thread mid-jit, so the
    # current scope is the compiling query's
    sc = _obs_events.current_scope()
    if sc is not None:
        sc.add("backend_compile_ns", int(duration_secs * 1e9))


def _hook_monitoring() -> None:
    global _MONITORING_HOOKED
    if _MONITORING_HOOKED:
        return
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_event_duration)
        monitoring.register_scalar_listener(_on_compile_phase_start)
        _MONITORING_HOOKED = True
    except Exception:  # noqa: BLE001 — monitoring API is best-effort
        _MONITORING_HOOKED = True  # don't retry every call


_hook_monitoring()


# -- persistent compilation cache --------------------------------------------

_PERSISTENT_DIR: Optional[str] = None
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_log = logging.getLogger(__name__)


def persistent_cache_stats() -> Dict[str, Any]:
    """Where the persistent cache is and how much of the compile work can
    ever land in it: programs compiled by a DONATING dispatch bypass it
    (:func:`_install_cache_bypass`) and recompile in every process."""
    with _LOCK:
        compiles = _STATS["compiles"]
        bypassed = _STATS["cache_bypass_compiles"]
    files = len(os.listdir(_PERSISTENT_DIR)) \
        if _PERSISTENT_DIR and os.path.isdir(_PERSISTENT_DIR) else 0
    return {"dir": _PERSISTENT_DIR, "files": files, "compiles": compiles,
            "bypassedDonating": bypassed}


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — a FIXED path (the directory is part of
    jax's cache key, so a tempdir, pid or timestamp in it never hits)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".jax_cache")


def enable_persistent_cache(cache_dir: str = "",
                            min_compile_secs: float = 1.0) -> str:
    """The ONE owner of XLA's persistent compilation cache placement;
    returns the directory in effect.  Where ``JAX_COMPILATION_CACHE_DIR``
    is set the operator has placed the cache from outside: jax reads the
    variable itself and nothing here overrides it (a differing
    ``cache_dir`` — conf ``spark.rapids.sql.tpu.compileCacheDir`` — is
    ignored with one log line).  Otherwise ``cache_dir`` or, by default,
    :func:`default_cache_dir`."""
    global _PERSISTENT_DIR
    env_dir = os.environ.get(_CACHE_ENV)
    if env_dir:
        if cache_dir and cache_dir != env_dir and _PERSISTENT_DIR != env_dir:
            _log.warning("%s=%s is set; ignoring compile cache dir %s",
                         _CACHE_ENV, env_dir, cache_dir)
        target = env_dir
    else:
        target = cache_dir or default_cache_dir()
    if _PERSISTENT_DIR == target:
        return target
    os.makedirs(target, exist_ok=True)
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", target)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    _PERSISTENT_DIR = target
    return target
