"""Observability subsystem: query event bus, per-operator profiles,
JSONL event-log export, and the ``tools/rapidsprof.py`` analysis CLI.

The package is deliberately engine-free (stdlib only, relative imports)
so ``rapidsprof`` can load it standalone the way ``rapidslint`` loads
``spark_rapids_tpu.analysis`` — without executing the engine's root
``__init__`` (which imports jax); ``xplane`` alone loads more, tsl's
generated ``xplane_pb2``, when a trace is read.  See
``docs/observability.md``.
"""

from __future__ import annotations

import logging
from typing import Callable, Optional

from .events import (  # noqa: F401 — re-exported emitter surface
    Event, EventBus, QueryScope, active, adopt, begin_query, current_scope,
    emit_instant, emit_span, end_query,
)
from . import critpath, sentinel, timeseries  # noqa: F401 — obs v2 surface

# -- explain sink -------------------------------------------------------------
#
# ``spark.rapids.sql.explain`` output used to be print()-ed straight to
# stdout (plan/overrides.py), spamming library embedders and pytest
# capture.  It now goes through this sink: a standard logger by default
# (enable with ``logging.getLogger("spark_rapids_tpu.explain")``), or a
# caller-installed callable for tests/tools.

_EXPLAIN_LOGGER = logging.getLogger("spark_rapids_tpu.explain")
_EXPLAIN_SINK: Optional[Callable[[str], None]] = None


def set_explain_sink(fn: Optional[Callable[[str], None]]) -> None:
    """Route explain output to ``fn(text)``; None restores the logger."""
    global _EXPLAIN_SINK
    _EXPLAIN_SINK = fn


def explain_sink(text: str) -> None:
    """Deliver one explain block (plan/overrides calls this when
    ``spark.rapids.sql.explain`` is on)."""
    sink = _EXPLAIN_SINK
    if sink is not None:
        sink(text)
        return
    _EXPLAIN_LOGGER.info("%s", text)
