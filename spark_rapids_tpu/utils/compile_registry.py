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
  ``compileWallNs``, ``dispatchCount``, ``compiledShapes``); the
  benchmark's ``compile_s`` reads them.
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
  batch staging layer (``last_metrics``' ``h2dBytes`` / ``h2dTimeNs`` /
  ``d2hBytes`` / ``d2hTimeNs``).

The compile wall is also split by phase from ``jax.monitoring``'s
duration events, routed by exact event name (jax 0.9.0), per query scope
and per program (:func:`per_label_compiles`):

* ``trace_ns`` — ``/jax/core/compile/jaxpr_trace_duration``: Python
  tracing of the program to a jaxpr;
* ``lower_ns`` — ``…/jaxpr_to_mlir_module_duration``: jaxpr to MLIR;
* ``backend_compile_ns`` — ``…/backend_compile_duration`` LESS the
  persistent-cache retrieval jax times inside it: XLA compiling, nothing
  else;
* ``cache_load_ns`` — ``/jax/compilation_cache/cache_retrieval_time_sec``
  (reading and deserialising an executable on a hit);
* ``cache_hits`` / ``cache_misses`` — the cache's own events (a miss is
  counted when the entry is written).

Only a program's outermost phases count (a ``jnp`` helper traced inside
a stage program fires a nested trace event), so the four wall counters
are disjoint and sum to no more than ``compile_wall_ns``, which also
holds the first execution.
"""

from __future__ import annotations

import functools
import inspect
import logging
import os
import sys
import threading
from typing import Any, Callable, Dict, Optional

import jax

from spark_rapids_tpu.fault import inject as _fault_inject
from spark_rapids_tpu.obs import events as _obs_events
from spark_rapids_tpu.utils import params as _params
from spark_rapids_tpu.utils import tracing as _tracing

_LOCK = threading.Lock()
_STATS: Dict[str, int] = {
    # cumulative process-wide; per-query deltas come from snapshot() pairs
    "compiles": 0,          # executable-cache misses observed at call sites
    "cache_bypass_compiles": 0,  # ... of which donating (never persisted)
    "compile_wall_ns": 0,   # wall ns of calls that triggered a compile
    "dispatches": 0,        # jitted program invocations
    # the compile wall by phase (jax.monitoring, outermost phases only)
    "trace_ns": 0,          # Python tracing to a jaxpr
    "lower_ns": 0,          # jaxpr -> MLIR module
    "backend_compile_ns": 0,  # XLA compiling (cache retrieval taken out)
    "cache_load_ns": 0,     # persistent-cache read + deserialise (hits)
    "cache_hits": 0,        # persistent-cache hits
    "cache_misses": 0,      # persistent-cache entries written
    "donated_bytes": 0,     # input buffer bytes donated to dispatches
    "h2d_bytes": 0,         # host->device staging bytes
    "h2d_ns": 0,            # host->device staging wall ns
    "d2h_bytes": 0,         # device->host bulk-copy bytes
    "d2h_ns": 0,            # device->host bulk-copy wall ns
}
#: program name (the sanitised label, what ``XLA Modules`` shows after
#: ``jit_``) -> {"compiles": n, "<phase>_ns": …, "cache_hits": …}
_LABEL_COMPILES: Dict[str, Dict[str, int]] = {}


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


def per_label_compiles() -> Dict[str, Dict[str, int]]:
    """Which program cost what: program name -> ``compiles`` (executable
    cache misses at its call sites) and the compile phases' ns and cache
    counts credited to it."""
    with _LOCK:
        return {k: dict(v) for k, v in _LABEL_COMPILES.items()}


def _credit_label_locked(program: str, key: str, n: int) -> None:
    d = _LABEL_COMPILES.setdefault(program, {})
    d[key] = d.get(key, 0) + n


def program_name(label: str) -> str:
    """``stage:TpuHashAggregateExec`` -> ``stage_TpuHashAggregateExec``:
    the label as a Python identifier, the name the jitted function (and
    so the ``jit_<name>`` XLA module on the device timeline) is given."""
    out = "".join(c if c.isalnum() or c == "_" else "_" for c in label)
    return out if out and not out[0].isdigit() else "p_" + out


def _record(label: str, compiled: bool, wall_ns: int,
            donated_bytes: int = 0, bypassed_cache: bool = False) -> None:
    with _LOCK:
        _STATS["dispatches"] += 1
        _STATS["donated_bytes"] += donated_bytes
        if compiled:
            _STATS["compiles"] += 1
            _STATS["compile_wall_ns"] += wall_ns
            _credit_label_locked(label, "compiles", 1)
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
    """Accumulate one host<->device staging pass (kind: "h2d" | "d2h");
    the caller's ``tracing.span`` around the pass is its timeline entry."""
    with _LOCK:
        _STATS[kind + "_bytes"] += int(nbytes)
        _STATS[kind + "_ns"] += int(wall_ns)
    sc = _obs_events.current_scope()
    if sc is not None:
        sc.add(kind + "_bytes", int(nbytes))
        sc.add(kind + "_ns", int(wall_ns))


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


try:  # jax 0.9 keeps it under _src only (jax.core lost the name)
    from jax._src.core import trace_state_clean as _jax_trace_state_clean
except ImportError:  # pragma: no cover — moved again
    _jax_trace_state_clean = getattr(jax.core, "trace_state_clean", None)


def _trace_state_clean() -> bool:
    """False while jax is tracing (a nested-jit call inlines, it doesn't
    dispatch)."""
    if _jax_trace_state_clean is None:
        return True
    return _jax_trace_state_clean()


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
                     bound: bool = False, **jit_kwargs) -> Callable:
    """``jax.jit`` with dispatch/compile accounting.

    Usable as ``instrumented_jit(f, label=...)`` or as a decorator
    ``@instrumented_jit(label=..., static_argnames=...)``.  The wrapper is
    call-compatible with the jitted function; the raw jitted callable is
    exposed as ``wrapper.jitted``.  ``donate_argnums`` passes through to
    ``jax.jit``; donated argument bytes are accumulated per dispatch.

    ``bound=True`` (:func:`plan_jit`) is for a program that traces a plan's
    expressions: the executing query's bound literal parameters
    (``utils/params``) ride as a hidden first argument, so a lifted
    ``Literal`` is an input of the executable and not a constant in it.
    """
    if fn is None:
        return functools.partial(instrumented_jit, label=label, bound=bound,
                                 **jit_kwargs)
    name = label or getattr(fn, "__name__", "jit")
    program = program_name(name)
    donate = tuple(jit_kwargs.get("donate_argnums") or ())
    if donate and not donation_supported():
        jit_kwargs = {k: v for k, v in jit_kwargs.items()
                      if k != "donate_argnums"}
        donate = ()
    if donate:
        _filter_donation_warning()

    # the jitted function carries the program name, so the device
    # timeline's ``XLA Modules`` line reads ``jit_<program>(<hash>)`` and
    # jax.monitoring's ``fun_name`` is the key of per_label_compiles()
    if bound:
        @functools.wraps(fn)
        def named(bound_params, *args, **kwargs):
            with _params.tracing(bound_params):
                return fn(*args, **kwargs)
        # the hidden argument shifts the caller's positions by one; jax
        # resolves static_argnames and checks donate_argnums against the
        # signature, so it must see the shifted one
        sig = inspect.signature(fn)
        named.__signature__ = sig.replace(parameters=[inspect.Parameter(
            "bound_params", inspect.Parameter.POSITIONAL_OR_KEYWORD)]
            + list(sig.parameters.values()))
        jit_kwargs = dict(jit_kwargs)
        for key in ("donate_argnums", "static_argnums"):
            if jit_kwargs.get(key):
                jit_kwargs[key] = tuple(i + 1 for i in jit_kwargs[key])
    else:
        @functools.wraps(fn)
        def named(*args, **kwargs):
            return fn(*args, **kwargs)
    named.__name__ = named.__qualname__ = program
    jitted = jax.jit(named, **jit_kwargs)
    if bound:
        def call(*args, **kwargs):
            return jitted(_params.dispatch_args(), *args, **kwargs)
    else:
        call = jitted

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if not _trace_state_clean():
            # nested call while an outer program is being traced: it
            # inlines into the outer jaxpr, so it is neither a device
            # dispatch nor a separate compile — don't count it (donation
            # of a traced value is likewise meaningless and ignored)
            return call(*args, **kwargs)
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
        # ONE span a jitted call: the host wall of the (asynchronous)
        # enqueue, compile-inclusive on a first call — never device time
        prev_program = getattr(_COMPILE_PHASES, "program", None)
        _COMPILE_PHASES.program = program
        try:
            with _tracing.span("enqueue", name,
                               _tracing.current_op()) as sp:
                if donate:
                    # a compile triggered by a donating dispatch must
                    # neither read nor write the persistent cache
                    # (deserialized executables mishandle the donation
                    # aliasing — see _install_cache_bypass)
                    with _no_persist_scope():
                        out = call(*args, **kwargs)
                else:
                    out = call(*args, **kwargs)
                after = _cache_size(jitted)
                compiled = after >= 0 and after != before
                if compiled:
                    sp.set(compiled=True)
        finally:
            _COMPILE_PHASES.program = prev_program
        _record(program, compiled, sp.elapsed_ns, donated_bytes,
                bypassed_cache=bool(donate))
        if donated_leaves:
            _guard_mark(name, donated_leaves)
        return out

    wrapper.jitted = jitted
    wrapper.label = name
    wrapper.program = program
    return wrapper


def plan_jit(fn: Optional[Callable] = None, **kwargs) -> Callable:
    """:func:`instrumented_jit` for a program that traces a plan's
    expressions (an operator's per-batch program, a stage program): it
    takes the executing query's bound literal parameters."""
    return instrumented_jit(fn, bound=True, **kwargs)


# -- jax.monitoring hook (precise backend compile seconds) -------------------

_MONITORING_HOOKED = False


#: Per-thread state of the open compile phases.  ``stack`` holds, per
#: open phase, the exception that was being handled when it started
#: (normally None): jax brackets tracing, MLIR lowering and the backend
#: compile with a start scalar and an end duration that fires even while
#: the phase unwinds by exception.  ``program`` is the instrumented
#: program being called on this thread; ``retrieved`` the cache-retrieval
#: seconds jax timed inside the backend phase that is still open.
_COMPILE_PHASES = threading.local()

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_PHASE_COUNTER = {_TRACE_EVENT: "trace_ns", _LOWER_EVENT: "lower_ns",
                  _BACKEND_EVENT: "backend_compile_ns"}
_CACHE_COUNTER = {_CACHE_HIT_EVENT: "cache_hits",
                  _CACHE_MISS_EVENT: "cache_misses"}


def _on_compile_phase_start(event: str, value: float, **kw) -> None:
    if event not in _PHASE_COUNTER:
        return
    stack = getattr(_COMPILE_PHASES, "stack", None)
    if stack is None:
        stack = _COMPILE_PHASES.stack = []
    stack.append(sys.exception())


def _pin_compile_failure(ambient, fun_name: str) -> None:
    """Called as a compile phase ends: an exception in flight that was
    not already being handled when the phase began was raised BY the
    trace/lower/compile — a refusal that no replay can fix, whatever
    its status text says (Mosaic refusals read ``INTERNAL``)."""
    err = sys.exception()
    if err is None or err is ambient or \
            getattr(err, "rapids_error_class", None) is not None:
        return
    from spark_rapids_tpu.fault.errors import mark_non_retryable
    mark_non_retryable(err)
    err.add_note(f"raised while lowering/compiling program "
                 f"'{fun_name}': a compile refusal, not a device loss "
                 f"(never retried, never completed on the CPU)")


def _credit(counter: str, n: int, fun_name: str = "") -> None:
    """``n`` into the process tally, the compiling query's scope (the
    listeners fire on the dispatching thread mid-jit) and the program.
    A phase outside any instrumented call (an eager ``jnp`` op compiling
    its one-op program) is no part of ``compile_wall_ns``: it is kept
    under its own name in per_label_compiles() alone."""
    program = getattr(_COMPILE_PHASES, "program", None)
    if program is None:
        if fun_name:
            with _LOCK:
                _credit_label_locked(fun_name.removeprefix("jit_"),
                                     counter, n)
        return
    with _LOCK:
        _STATS[counter] += n
        _credit_label_locked(program, counter, n)
    sc = _obs_events.current_scope()
    if sc is not None:
        sc.add(counter, n)


def _on_event_duration(event: str, duration_secs: float, **kw) -> None:
    ns = int(duration_secs * 1e9)
    counter = _PHASE_COUNTER.get(event)
    if counter is not None:
        stack = getattr(_COMPILE_PHASES, "stack", None)
        ambient = stack.pop() if stack else None
        fun_name = kw.get("fun_name", "?")
        _pin_compile_failure(ambient, fun_name)
        if stack:
            return  # nested (a jnp helper traced inside a program)
        if event == _BACKEND_EVENT:
            # jax times the persistent-cache read inside this phase
            ns = max(0, ns - getattr(_COMPILE_PHASES, "retrieved", 0))
            _COMPILE_PHASES.retrieved = 0
        _credit(counter, ns, fun_name)
    elif event == _CACHE_LOAD_EVENT:
        _COMPILE_PHASES.retrieved = \
            getattr(_COMPILE_PHASES, "retrieved", 0) + ns
        _credit("cache_load_ns", ns)
    # …/compilation_cache/compile_time_saved_sec is time NOT spent: it
    # (and any other duration) moves no counter


def _on_event(event: str, **kw) -> None:
    counter = _CACHE_COUNTER.get(event)
    if counter is not None:
        _credit(counter, 1)


def _hook_monitoring() -> None:
    global _MONITORING_HOOKED
    if _MONITORING_HOOKED:
        return
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_event_duration)
        monitoring.register_scalar_listener(_on_compile_phase_start)
        monitoring.register_event_listener(_on_event)
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
