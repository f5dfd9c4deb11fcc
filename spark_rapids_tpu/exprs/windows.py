"""Window expressions (reference: GpuWindowExec.scala:99,
GpuWindowExpression.scala:93 — row-frame windowing via cudf rolling windows).

TPU-first design: instead of per-row rolling kernels, the window exec sorts
the whole partition by (partition keys, order keys) once, derives partition
*segments*, and computes every supported function with prefix-sum /
segmented-scan primitives — O(n log n) sort + O(n) scans, ideal XLA shapes.

Supported frames: ROWS/RANGE with UNBOUNDED PRECEDING..CURRENT ROW (running,
RANGE extends to peers), UNBOUNDED..UNBOUNDED (whole partition), bounded
value-based RANGE BETWEEN x PRECEDING AND y FOLLOWING over a single
numeric/date/timestamp order key (binary search on the sorted span;
NULL/NaN keys frame over their peer blocks), and bounded
ROWS frames for sum/count/avg/min/max via prefix sums (min/max bounded uses a
log-steps sliding reduction).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exprs.aggregates import AggregateFunction
from spark_rapids_tpu.exprs.base import Expression, Literal, SortOrder

UNBOUNDED = None
CURRENT_ROW = 0


@dataclasses.dataclass(frozen=True)
class WindowFrame:
    """kind: "rows" or "range".  start/end: None = unbounded, ints are
    offsets relative to the current row (negative = preceding)."""

    kind: str = "range"
    start: Optional[int] = UNBOUNDED
    end: Optional[int] = CURRENT_ROW

    @property
    def is_unbounded_whole(self) -> bool:
        return self.start is None and self.end is None

    @property
    def is_running(self) -> bool:
        return self.start is None and self.end == 0


class WindowFunction(Expression):
    """Marker base for ranking/offset window functions."""

    context_free = False  # the row's place in its partition

    needs_order = True


class RowNumber(WindowFunction):
    def __init__(self):
        self.children = ()
        self.dtype = T.INT
        self.nullable = False

    def with_children(self, children):
        return self


class Rank(WindowFunction):
    def __init__(self):
        self.children = ()
        self.dtype = T.INT
        self.nullable = False

    def with_children(self, children):
        return self


class DenseRank(WindowFunction):
    def __init__(self):
        self.children = ()
        self.dtype = T.INT
        self.nullable = False

    def with_children(self, children):
        return self


class Lag(WindowFunction):
    def __init__(self, child: Expression, offset: int = 1,
                 default: Optional[Expression] = None):
        self.children = (child,) if default is None else (child, default)
        self.offset = int(offset)
        self.dtype = child.dtype
        self.nullable = True

    def with_children(self, children):
        d = children[1] if len(children) > 1 else None
        return type(self)(children[0], self.offset, d)


class Lead(Lag):
    pass


class WindowExpression(Expression):
    """function OVER (PARTITION BY ... ORDER BY ... frame)."""

    context_free = False

    def __init__(self, function: Expression,
                 partition_by: List[Expression],
                 order_by: List[SortOrder],
                 frame: Optional[WindowFrame] = None):
        self.function = function
        self.partition_by = list(partition_by)
        self.order_by = list(order_by)
        if frame is None:
            # Spark defaults: with ORDER BY -> RANGE UNBOUNDED..CURRENT;
            # without -> whole partition.
            frame = WindowFrame("range", UNBOUNDED, CURRENT_ROW) \
                if order_by else WindowFrame("rows", UNBOUNDED, UNBOUNDED)
        self.frame = frame
        self.children = (function,) + tuple(partition_by) + \
            tuple(o.child for o in order_by)
        self.dtype = function.dtype
        self.nullable = True

    def with_children(self, children):
        nf = children[0]
        np_ = children[1:1 + len(self.partition_by)]
        no = children[1 + len(self.partition_by):]
        orders = [SortOrder(c, o.ascending, o.nulls_first)
                  for c, o in zip(no, self.order_by)]
        return WindowExpression(nf, list(np_), orders, self.frame)

    @property
    def name(self):
        return f"WindowExpression({self.function.name})"

    def tpu_supported(self, conf):
        fn = self.function
        if isinstance(fn, (RowNumber, Rank, DenseRank)):
            if not self.order_by:
                return f"{fn.name} requires ORDER BY"
            return None
        if isinstance(fn, Lag):
            if len(fn.children) > 1 and fn.children[0].dtype.is_string:
                # ops/window.py has no string default-fill yet; route to
                # CPU instead of silently returning NULL for the default.
                return (f"{fn.name} with a default value on a string "
                        f"column not supported on TPU")
            return None
        if isinstance(fn, AggregateFunction):
            from spark_rapids_tpu.exprs.aggregates import (
                Average, Count, Max, Min, Sum,
            )
            if not isinstance(fn, (Sum, Count, Min, Max, Average)):
                return f"window aggregate {fn.name} not supported"
            r = fn.tpu_supported(conf)
            if r:
                return r
            if self.frame.kind == "range" and not (
                    self.frame.is_running or
                    self.frame.is_unbounded_whole):
                # bounded value-range frame: Spark requires exactly one
                # numeric/date/timestamp order key; anything else routes
                # to the CPU exec, which raises the analysis error
                if len(self.order_by) != 1:
                    return ("bounded RANGE frame needs exactly one "
                            "ORDER BY expression")
                kd = self.order_by[0].child.dtype
                if not kd.is_numeric and kd not in (T.DATE, T.TIMESTAMP):
                    return ("bounded RANGE frame needs a numeric "
                            "order key")
            return None
        return f"window function {fn.name} not supported"
