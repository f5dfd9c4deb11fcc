"""Profiler ranges fused with metrics.

Reference analogue: NvtxWithMetrics (NvtxWithMetrics.scala:27-36) — one
``with`` block feeds both the profiler timeline and a SQL metric.  On TPU the
profiler side is XProf via ``jax.profiler.TraceAnnotation`` (the XLA runtime
exports these through the PJRT profiler C API, SURVEY.md section 2.9 NVTX
row); the metric side is the ExecContext Metric objects.

Device-time accounting: jax dispatch is asynchronous, so the wall time of a
dispatch call is only a *lower bound* on device execution.  The accurate
number needs a ``block_until_ready`` on the outputs — a host sync that
costs a device round trip and kills async overlap, so it is gated behind
``spark.rapids.sql.tpu.metrics.detailEnabled`` (off by default).
:func:`device_dispatch` implements both modes for the dispatch sites in
``plan/pipeline.py`` / ``plan/physical.py``.
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional

import jax.profiler

from spark_rapids_tpu.config import METRICS_DETAIL
from spark_rapids_tpu.obs import events as obs_events


def metrics_detail(conf) -> bool:
    """True when the accurate-sync metrics path is enabled (the cheap
    lower-bound path is the default)."""
    return METRICS_DETAIL.get(conf)


@contextlib.contextmanager
def trace_range(name: str, metric=None):
    """Profiler range + optional elapsed-nanos metric accumulation."""
    t0 = time.monotonic_ns()
    with jax.profiler.TraceAnnotation(name):
        yield
    if metric is not None:
        metric.add(time.monotonic_ns() - t0)


@contextlib.contextmanager
def device_dispatch(ctx, op_id: str, name: str,
                    obs_op: Optional[str] = None):
    """Time one device program dispatch into ``ctx.metric(op_id,
    'deviceTimeNs')`` under a profiler range.

    The body sets ``holder['outputs']`` to the dispatched result.  With
    the metrics-detail conf on, the outputs are blocked on before the
    clock stops — on pre-staged (already device-resident) inputs that
    delta IS device execution time; ``deviceTimeSyncs`` counts how many
    accurate samples the total contains.  Detail off: the dispatch wall
    alone is recorded (a lower bound, async dispatch).

    The elapsed time is recorded in a ``finally`` so a dispatch that
    raises (an injected fault, an OOM about to be retried) still shows
    in the metric and the profile instead of vanishing; the failed
    attempt's obs span is tagged ``error``.  ``obs_op`` names the
    physical-plan node the span is attributed to when the metric op_id
    is a shared bucket (the pipeline dispatcher passes the stage root's
    op_id here while keeping the metric under ``"pipeline"``).
    """
    holder: dict = {}
    err = False
    t0 = time.monotonic_ns()
    try:
        with jax.profiler.TraceAnnotation(f"{op_id}:{name}"):
            yield holder
            if metrics_detail(ctx.conf) and \
                    holder.get("outputs") is not None:
                jax.block_until_ready(holder["outputs"])
                ctx.metric(op_id, "deviceTimeSyncs").add(1)
    except BaseException:
        err = True
        raise
    finally:
        elapsed = time.monotonic_ns() - t0
        ctx.metric(op_id, "deviceTimeNs").add(elapsed)
        if err:
            ctx.metric(op_id, "deviceTimeErrors").add(1)
            obs_events.emit_span("device", name, obs_op or op_id,
                                 t0, t0 + elapsed, error=True)
        else:
            obs_events.emit_span("device", name, obs_op or op_id,
                                 t0, t0 + elapsed)


def start_profile(logdir: str):
    """Begin an XProf capture (nsys-capture analogue,
    docs/dev/nvtx_profiling.md)."""
    jax.profiler.start_trace(logdir)


def stop_profile():
    jax.profiler.stop_trace()
