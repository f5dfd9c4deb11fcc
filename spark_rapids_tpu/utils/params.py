"""Bound literal parameters: the values of one query's lifted literals.

A physical plan is shared by every query of one *shape*
(``plan/logical.plan_shape``): a lifted :class:`~spark_rapids_tpu.exprs.
base.Literal` carries a ``slot`` and no value of its own, and each
execution binds its tuple of values here.  Three readers:

* a program built by ``compile_registry.plan_jit`` takes the executing
  query's device scalars as a hidden first argument (:func:`dispatch_args`)
  and traces its body under :func:`tracing`, so ``Literal.tpu_eval`` reads
  its slot as a traced scalar (:func:`traced`) and nothing is baked;
* ``Literal.cpu_eval`` and an eager ``tpu_eval`` read the executing
  query's values (:func:`host`, :func:`traced` outside a trace);
* ``repr`` prints the query's own value (:func:`shown`).

The binding is per thread (:func:`executing`); a helper thread that drives
a plan's iterators adopts its spawner's (:func:`current` there,
:func:`executing` in the helper).  A thread with no binding of its own
falls back to the only one open in the process; a lifted literal read with
none in reach raises: a wrong value is never guessed.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, List, Optional, Tuple


class BoundParams:
    """One execution's values, slot by slot: ``host`` as python scalars,
    ``device`` as the device scalars a program is called with."""

    __slots__ = ("host", "device")

    def __init__(self, host: Tuple[Any, ...], device: Tuple[Any, ...]):
        self.host = host
        self.device = device


_LOCAL = threading.local()   # .run / .trace: this thread's stacks
_OPEN: List[BoundParams] = []   # every binding open in the process
_OPEN_LOCK = threading.Lock()


def current() -> Optional[BoundParams]:
    """The binding this thread executes under: its own innermost, else the
    only one open in the process."""
    stack = getattr(_LOCAL, "run", None)
    if stack:
        return stack[-1]
    with _OPEN_LOCK:
        return _OPEN[0] if len(_OPEN) == 1 else None


@contextlib.contextmanager
def executing(params: Optional[BoundParams]):
    """Run the body with ``params`` as this thread's binding.  A plan with
    no lifted literal binds the empty tuple (None reads as that): its
    programs are then never called with another query's scalars."""
    if params is None:
        params = BoundParams((), ())
    stack = getattr(_LOCAL, "run", None)
    if stack is None:
        stack = _LOCAL.run = []
    stack.append(params)
    with _OPEN_LOCK:
        _OPEN.append(params)
    try:
        yield
    finally:
        stack.pop()
        with _OPEN_LOCK:
            _OPEN.remove(params)


@contextlib.contextmanager
def tracing(tracers: Tuple[Any, ...]):
    """Trace the body of a ``plan_jit`` program with ``tracers`` as the
    slots' values."""
    stack = getattr(_LOCAL, "trace", None)
    if stack is None:
        stack = _LOCAL.trace = []
    stack.append(tracers)
    try:
        yield
    finally:
        stack.pop()


def dispatch_args() -> Tuple[Any, ...]:
    """What a ``plan_jit`` program is called with: the enclosing trace's
    slots when it is inlined into another program, else the executing
    query's device scalars, else nothing."""
    trace = getattr(_LOCAL, "trace", None)
    if trace:
        return trace[-1]
    bound = current()
    return bound.device if bound is not None else ()


def _unbound(slot: int) -> RuntimeError:
    return RuntimeError(
        f"lifted literal (slot {slot}) evaluated with no bound parameters "
        "in reach: the plan belongs to session.plan_bound's caller, which "
        "executes it under utils.params.executing(...)")


def traced(slot: int):
    """Slot ``slot`` for ``Literal.tpu_eval``: the traced scalar inside a
    ``plan_jit`` program, the executing query's device scalar in eager
    evaluation.  Inside any other program being traced it raises — the
    value would be baked into an executable that other queries share."""
    trace = getattr(_LOCAL, "trace", None)
    if trace and slot < len(trace[-1]):
        return trace[-1][slot]
    from spark_rapids_tpu.utils.compile_registry import _trace_state_clean
    bound = current()
    if bound is None or slot >= len(bound.device) \
            or not _trace_state_clean():
        raise _unbound(slot)
    return bound.device[slot]


def host(slot: int):
    """Slot ``slot`` as a python scalar, for ``Literal.cpu_eval``."""
    bound = current()
    if bound is None or slot >= len(bound.host):
        raise _unbound(slot)
    return bound.host[slot]


def shown(slot: int, default):
    """Slot ``slot`` for ``repr``: the executing (or :func:`showing`)
    query's value, else ``default`` (the value the shape was planned
    with)."""
    values = getattr(_LOCAL, "show", None)
    if values is None:
        bound = current()
        values = bound.host if bound is not None else ()
    return values[slot] if slot < len(values) else default


@contextlib.contextmanager
def showing(values: Tuple[Any, ...]):
    """``repr`` of a lifted literal prints ``values[slot]`` in the body
    (explain output of a query that is not executing)."""
    prev = getattr(_LOCAL, "show", None)
    _LOCAL.show = values
    try:
        yield
    finally:
        _LOCAL.show = prev
