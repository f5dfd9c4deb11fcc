"""The one span API: every program span lands on the profiler's clock AND
in the obs ring.

Reference analogue: NvtxWithMetrics (NvtxWithMetrics.scala:27-36) — one
``with`` block feeds both the profiler timeline and a SQL metric.  On TPU
the profiler side is XProf via ``jax.profiler.TraceAnnotation`` (the XLA
runtime exports these through the PJRT profiler C API, SURVEY.md section
2.9 NVTX row), which shares the device's clock; the obs side is the
per-query event ring (``obs.events``) that ``critpath``, ``QueryProfile``
and ``rapidsprof`` read.

:class:`span` is the only way the program opens a span.  It enters
``TraceAnnotation("srt/<site>/<name>")`` and, on exit, appends the same
interval to the ring, so a kept ``.xplane.pb`` holds every program span
under one prefix next to the device's operations, and the ring holds the
same intervals on the host clock.  With no profiler session the
annotation is one flag test; the ring append is what it was.

Sites (``obs.critpath.SITE_PRIORITY`` ranks them): ``device_wait`` — the
host blocked on the chip (a size read-back, the wait before a D2H copy,
an exchange's sync); ``h2d`` / ``d2h`` — staging copies; ``enqueue`` —
ONE span per jitted call (``compile_registry.instrumented_jit``), the
host wall of an asynchronous enqueue (compile-inclusive on a first
call), never device time; ``stage`` — a stage program's whole dispatch (enqueue + any size
read-back inside it: the host's wall, like every span here);
``stage_inputs``, ``plan``, ``result``, ``bookkeeping`` — the host parts
of ``session.execute_with_metrics``; ``scan``, ``io``, ``exchange``,
``mesh``, ``spill``, ``unspill``, ``pallas``, ``retry``,
``serve.frontend`` as before.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import Callable

import jax.profiler

from spark_rapids_tpu.obs import events as obs_events

#: every program span's profiler name starts with this
PREFIX = "srt"

_CURRENT = threading.local()


class span:
    """``with span(site, name, op, **payload) as sp:`` — one interval on
    both timelines.  ``sp.set(k=v)`` adds payload known only inside the
    body (bytes moved, ``compiled``); a body that raises is recorded with
    ``error=True``; ``sp.t0`` is the opening stamp and ``sp.elapsed_ns``
    the closed interval's width, so a metric fed from them agrees with
    the span to the nanosecond.  Only a ``with`` opens one: the profiler
    range and the thread's current operator are restored however the body
    leaves.  ``ring=False`` keeps a span off the ring: a pool worker's
    side of an interval the consumer records with :func:`record_span`."""

    __slots__ = ("site", "name", "op", "payload", "t0", "elapsed_ns",
                 "_ann", "_ring", "_outer_op")

    def __init__(self, site: str, name: str, op: str = "",
                 ring: bool = True, **payload):
        self.site = site
        self.name = name
        self.op = op
        self.payload = payload
        self.t0 = 0
        self.elapsed_ns = 0
        self._ann = None
        self._ring = ring
        self._outer_op = None

    def __enter__(self) -> "span":
        self._ann = jax.profiler.TraceAnnotation(
            f"{PREFIX}/{self.site}/{self.name}")
        self._ann.__enter__()
        if self.op:
            # spans opened inside inherit the operator (an exchange's
            # split programs, a stage's enqueue)
            self._outer_op = getattr(_CURRENT, "op", "")
            _CURRENT.op = self.op
        self.t0 = time.monotonic_ns()
        return self

    def set(self, **payload) -> None:
        self.payload.update(payload)

    def __exit__(self, exc_type, exc, tb):
        t1 = time.monotonic_ns()
        self._ann.__exit__(None, None, None)
        if self._outer_op is not None:
            _CURRENT.op = self._outer_op
        self.elapsed_ns = t1 - self.t0
        if exc_type is not None:
            self.payload["error"] = True
        if self._ring:
            obs_events.emit_span(self.site, self.name, self.op,
                                 self.t0, t1, **self.payload)
        return False


def record_span(site: str, name: str, op: str, t0: int, t1: int,
                **payload) -> None:
    """Ring entry for an interval that was timed on another thread (a
    decode-pool worker's chunk, harvested by the consumer).  The worker
    opens the profiler range itself with :func:`annotated`."""
    obs_events.emit_span(site, name, op, t0, t1, **payload)


def annotated(site: str, name: str, fn: Callable) -> Callable:
    """``fn`` run under the profiler range only (``ring=False``): for a
    pool task whose interval the consumer hands to :func:`record_span`."""
    def run(*args, **kwargs):
        with span(site, name, ring=False):
            return fn(*args, **kwargs)
    return run


def kernel_scope(fn: Callable) -> Callable:
    """Decorator: trace ``fn`` under ``jax.named_scope("k.<module>.<fn>")``
    — the kernel entry points a device trace blames (row gathers, the
    compaction, group-by, sort keys, the join probe, string byte
    gathers), so their HLO operations say which kernel they came from
    inside whichever operator scope inlined them."""
    scope = f"k.{fn.__module__.rsplit('.', 1)[-1]}.{fn.__name__}"

    @functools.wraps(fn)
    def run(*args, **kwargs):
        with jax.named_scope(scope):
            return fn(*args, **kwargs)
    return run


def current_op() -> str:
    """The operator of the innermost open span that names one, on this
    thread: what an ``enqueue`` or ``device_wait`` span opened now is
    working for."""
    return getattr(_CURRENT, "op", "")


def device_wait(name: str, tree, op: str = ""):
    """Block until ``tree``'s buffers are ready, under a ``device_wait``
    span: the host's wall on the chip, by name."""
    with span("device_wait", name, op or current_op()):
        return jax.block_until_ready(tree)


def device_read(name: str, tree, op: str = ""):
    """``jax.device_get(tree)`` under a ``device_wait`` span: a size or
    flag read-back out of programs still in flight is where the host
    waits for the chip (the bytes are a few scalars)."""
    with span("device_wait", name, op or current_op()):
        return jax.device_get(tree)


@contextlib.contextmanager
def trace_range(site: str, name: str, metric=None):
    """A container range (a partition's drive loop, the whole collect)
    plus optional elapsed-nanos metric accumulation.  Profiler side
    only: on the ring it would cover, and so rename, every host gap
    ``critpath`` reports as ``wait``."""
    sp = span(site, name, ring=False)
    try:
        with sp:
            yield
    finally:
        if metric is not None:
            metric.add(sp.elapsed_ns)


def start_profile(logdir: str):
    """Begin an XProf capture (nsys-capture analogue,
    docs/dev/nvtx_profiling.md).  The capture keeps the HLO proto, so
    ``tools/rapidsprof.py --xplane`` can tie every device operation to
    its operator and kernel scope."""
    jax.profiler.start_trace(logdir)


def stop_profile():
    jax.profiler.stop_trace()
