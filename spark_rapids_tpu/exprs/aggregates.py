"""Declarative aggregate functions (reference: AggregateFunctions.scala, 513
LoC: min/max/sum/count/avg/first/last as declarative cudf agg pairs).

Here each aggregate declares segment-reduce kernels instead of cudf agg pairs:
``segment_update`` folds raw input rows into per-group buffers and
``segment_merge`` folds partial buffers; both are plain
``jax.ops.segment_*`` calls with ``num_segments = capacity`` so shapes stay
static (worst case: every live row its own group).  ``finalize`` computes the
result projection (e.g. avg = sum / count).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exprs.base import CpuVal, DevVal, Expression, Literal

# Trace-time flag: the sort-groupby path feeds segment kernels seg_ids in
# ascending order; the MXU hash-agg slot path feeds them UNSORTED.
# ``indices_are_sorted`` is a correctness contract for TPU scatter
# lowering, not just a speed hint, so the hash path must trace with it
# off (kernels/hashagg.py wraps its segment_update calls).
_SEG_IDS_SORTED = [True]


def _seg_sorted() -> bool:
    return _SEG_IDS_SORTED[-1]


@contextlib.contextmanager
def unsorted_segment_ids():
    _SEG_IDS_SORTED.append(False)
    try:
        yield
    finally:
        _SEG_IDS_SORTED.pop()


def _sum_result_type(dt: T.DataType) -> T.DataType:
    if dt.is_integral:
        return T.LONG
    return T.DOUBLE


@dataclasses.dataclass
class AggBufferSpec:
    dtype: T.DataType


class AggregateFunction(Expression):
    """Base: declares buffers + segment kernels.  Not columnar-evaluable."""

    context_free = False  # a reduction over rows, whatever its argument

    def __init__(self, child: Expression):
        self.children = (child,)
        self._resolve_type()

    @property
    def child(self):
        return self.children[0]

    def _resolve_type(self):
        raise NotImplementedError

    # number and types of intermediate buffers
    def buffers(self) -> List[AggBufferSpec]:
        raise NotImplementedError

    def segment_update(self, v: DevVal, seg_ids, num_segments: int,
                      live_mask) -> List[DevVal]:
        """Fold input rows into per-group buffers (partial aggregation)."""
        raise NotImplementedError

    def segment_merge(self, buffers: List[DevVal], seg_ids,
                      num_segments: int, live_mask) -> List[DevVal]:
        """Fold partial buffers (final aggregation after shuffle)."""
        raise NotImplementedError

    def finalize(self, buffers: List[DevVal]) -> DevVal:
        raise NotImplementedError

    # CPU oracle: reduce a python/numpy group
    def cpu_reduce(self, values: np.ndarray, validity: np.ndarray):
        raise NotImplementedError

    def tpu_supported(self, conf):
        if self.child.dtype.is_string:
            return f"{self.name} over strings not supported on TPU"
        if self.child.dtype.is_fractional and not conf.variable_float_agg \
                and type(self) in (Sum, Average):
            return (f"{self.name} over floats can produce non-deterministic "
                    "results; set spark.rapids.sql.variableFloatAgg.enabled")
        return None


def _seg_any_valid(valid, seg_ids, num_segments, live_mask):
    # scatter-ADD (not max): adds combine in-lane on TPU scatters
    return jax.ops.segment_sum((valid & live_mask).astype(jnp.int32), seg_ids,
                               num_segments=num_segments, indices_are_sorted=_seg_sorted()) > 0


class Sum(AggregateFunction):
    def _resolve_type(self):
        self.dtype = _sum_result_type(self.child.dtype)
        self.nullable = True

    def buffers(self):
        return [AggBufferSpec(self.dtype), AggBufferSpec(T.BOOLEAN)]

    def segment_update(self, v, seg_ids, num_segments, live_mask):
        x = v.data.astype(self.dtype.jnp_dtype)
        use = v.validity & live_mask
        s = jax.ops.segment_sum(jnp.where(use, x, 0), seg_ids,
                                num_segments=num_segments, indices_are_sorted=_seg_sorted())
        any_v = _seg_any_valid(v.validity, seg_ids, num_segments, live_mask)
        ones = jnp.ones(num_segments, dtype=jnp.bool_)
        return [DevVal(self.dtype, s, ones), DevVal(T.BOOLEAN, any_v, ones)]

    def segment_merge(self, buffers, seg_ids, num_segments, live_mask):
        s, has = buffers
        total = jax.ops.segment_sum(
            jnp.where(live_mask, s.data, 0), seg_ids, num_segments=num_segments, indices_are_sorted=_seg_sorted())
        any_v = _seg_any_valid(has.data.astype(jnp.bool_), seg_ids,
                               num_segments, live_mask)
        ones = jnp.ones(num_segments, dtype=jnp.bool_)
        return [DevVal(self.dtype, total, ones), DevVal(T.BOOLEAN, any_v, ones)]

    def finalize(self, buffers):
        s, has = buffers
        return DevVal(self.dtype, s.data, has.data.astype(jnp.bool_))

    def cpu_reduce(self, values, validity):
        if not validity.any():
            return None
        vals = values[validity]
        if self.dtype == T.LONG:
            return int(np.sum(vals.astype(np.int64)))
        return float(np.sum(vals.astype(np.float64)))


class Count(AggregateFunction):
    def _resolve_type(self):
        self.dtype = T.LONG
        self.nullable = False

    def tpu_supported(self, conf):
        return None

    def buffers(self):
        return [AggBufferSpec(T.LONG)]

    def segment_update(self, v, seg_ids, num_segments, live_mask):
        use = v.validity & live_mask
        # scatter-add in i32 (native TPU lanes; a 64-bit scatter lowers to
        # an emulated sort-based path), widen after: one batch holds
        # < 2^31 rows so the per-batch count cannot overflow
        c32 = jax.ops.segment_sum(use.astype(jnp.int32), seg_ids,
                                  num_segments=num_segments,
                                  indices_are_sorted=_seg_sorted())
        c = c32.astype(jnp.int64)
        return [DevVal(T.LONG, c, jnp.ones(num_segments, dtype=jnp.bool_))]

    def segment_merge(self, buffers, seg_ids, num_segments, live_mask):
        c = jax.ops.segment_sum(
            jnp.where(live_mask, buffers[0].data, 0), seg_ids,
            num_segments=num_segments, indices_are_sorted=_seg_sorted())
        return [DevVal(T.LONG, c, jnp.ones(num_segments, dtype=jnp.bool_))]

    def finalize(self, buffers):
        return DevVal(T.LONG, buffers[0].data,
                      jnp.ones_like(buffers[0].data, dtype=jnp.bool_))

    def cpu_reduce(self, values, validity):
        return int(validity.sum())


class _MinMax(AggregateFunction):
    _is_min = True

    def _resolve_type(self):
        self.dtype = self.child.dtype
        self.nullable = True

    def tpu_supported(self, conf):
        if self.child.dtype.is_string:
            return f"{self.name} over strings not supported on TPU"
        return None

    def buffers(self):
        return [AggBufferSpec(self.dtype), AggBufferSpec(T.BOOLEAN)]

    def _ident(self):
        jdt = self.dtype.jnp_dtype
        if self.dtype.is_fractional:
            return jnp.asarray(jnp.inf if self._is_min else -jnp.inf, dtype=jdt)
        info = jnp.iinfo(jdt) if self.dtype != T.BOOLEAN else None
        if self.dtype == T.BOOLEAN:
            return jnp.asarray(True if self._is_min else False)
        return jnp.asarray(info.max if self._is_min else info.min, dtype=jdt)

    def _seg_reduce(self, x, seg_ids, num_segments):
        if self._is_min:
            return jax.ops.segment_min(x, seg_ids, num_segments=num_segments, indices_are_sorted=_seg_sorted())
        return jax.ops.segment_max(x, seg_ids, num_segments=num_segments, indices_are_sorted=_seg_sorted())

    def segment_update(self, v, seg_ids, num_segments, live_mask):
        use = v.validity & live_mask
        x = jnp.where(use, v.data.astype(self.dtype.jnp_dtype), self._ident())
        m = self._seg_reduce(x, seg_ids, num_segments)
        any_v = _seg_any_valid(v.validity, seg_ids, num_segments, live_mask)
        ones = jnp.ones(num_segments, dtype=jnp.bool_)
        return [DevVal(self.dtype, m, ones), DevVal(T.BOOLEAN, any_v, ones)]

    def segment_merge(self, buffers, seg_ids, num_segments, live_mask):
        m, has = buffers
        use = has.data.astype(jnp.bool_) & live_mask
        x = jnp.where(use, m.data, self._ident())
        total = self._seg_reduce(x, seg_ids, num_segments)
        any_v = _seg_any_valid(has.data.astype(jnp.bool_), seg_ids,
                               num_segments, live_mask)
        ones = jnp.ones(num_segments, dtype=jnp.bool_)
        return [DevVal(self.dtype, total, ones), DevVal(T.BOOLEAN, any_v, ones)]

    def finalize(self, buffers):
        m, has = buffers
        return DevVal(self.dtype, m.data, has.data.astype(jnp.bool_))

    def cpu_reduce(self, values, validity):
        if not validity.any():
            return None
        vals = values[validity]
        if self.dtype.is_string:
            vals = [str(v) for v in vals]
        r = min(vals) if self._is_min else max(vals)
        return r


class Min(_MinMax):
    _is_min = True


class Max(_MinMax):
    _is_min = False


class Average(AggregateFunction):
    def _resolve_type(self):
        self.dtype = T.DOUBLE
        self.nullable = True

    def buffers(self):
        return [AggBufferSpec(T.DOUBLE), AggBufferSpec(T.LONG)]

    def segment_update(self, v, seg_ids, num_segments, live_mask):
        use = v.validity & live_mask
        x = v.data.astype(jnp.float64)
        s = jax.ops.segment_sum(jnp.where(use, x, 0.0), seg_ids,
                                num_segments=num_segments, indices_are_sorted=_seg_sorted())
        # count in i32 (native scatter lanes), widened after — see Count
        c = jax.ops.segment_sum(use.astype(jnp.int32), seg_ids,
                                num_segments=num_segments,
                                indices_are_sorted=_seg_sorted()).astype(jnp.int64)
        ones = jnp.ones(num_segments, dtype=jnp.bool_)
        return [DevVal(T.DOUBLE, s, ones), DevVal(T.LONG, c, ones)]

    def segment_merge(self, buffers, seg_ids, num_segments, live_mask):
        s, c = buffers
        st = jax.ops.segment_sum(jnp.where(live_mask, s.data, 0.0), seg_ids,
                                 num_segments=num_segments, indices_are_sorted=_seg_sorted())
        ct = jax.ops.segment_sum(jnp.where(live_mask, c.data, 0), seg_ids,
                                 num_segments=num_segments, indices_are_sorted=_seg_sorted())
        ones = jnp.ones(num_segments, dtype=jnp.bool_)
        return [DevVal(T.DOUBLE, st, ones), DevVal(T.LONG, ct, ones)]

    def finalize(self, buffers):
        s, c = buffers
        nonzero = c.data > 0
        data = s.data / jnp.where(nonzero, c.data, 1).astype(jnp.float64)
        return DevVal(T.DOUBLE, data, nonzero)

    def cpu_reduce(self, values, validity):
        if not validity.any():
            return None
        vals = values[validity].astype(np.float64)
        return float(np.sum(vals) / len(vals))


class _FirstLast(AggregateFunction):
    _is_first = True

    def __init__(self, child: Expression, ignore_nulls: bool = False):
        self.ignore_nulls = ignore_nulls
        super().__init__(child)

    def with_children(self, children):
        return type(self)(children[0], self.ignore_nulls)

    def _resolve_type(self):
        self.dtype = self.child.dtype
        self.nullable = True

    def buffers(self):
        # value + validity + the row index it came from (for merge ordering)
        return [AggBufferSpec(self.dtype), AggBufferSpec(T.BOOLEAN),
                AggBufferSpec(T.LONG)]

    def _pick(self, v_data, v_valid, idx, seg_ids, num_segments, live_mask):
        cap = int(idx.shape[0])
        candidate = live_mask & (v_valid if self.ignore_nulls
                                 else jnp.ones_like(v_valid))
        big = jnp.int64(jnp.iinfo(jnp.int64).max // 2)
        key = jnp.where(candidate, idx, big if self._is_first else -big)
        if self._is_first:
            best = jax.ops.segment_min(key, seg_ids, num_segments=num_segments, indices_are_sorted=_seg_sorted())
        else:
            best = jax.ops.segment_max(key, seg_ids, num_segments=num_segments, indices_are_sorted=_seg_sorted())
        # Scatter values of winners into group slots.
        winner = candidate & (best[seg_ids] == key)
        out_val = jnp.zeros(num_segments, dtype=v_data.dtype)
        out_val = out_val.at[jnp.where(winner, seg_ids, num_segments)].set(
            v_data, mode="drop")
        out_ok = jnp.zeros(num_segments, dtype=jnp.bool_)
        out_ok = out_ok.at[jnp.where(winner, seg_ids, num_segments)].set(
            v_valid, mode="drop")
        has = jax.ops.segment_max(candidate.astype(jnp.int32), seg_ids,
                                  num_segments=num_segments, indices_are_sorted=_seg_sorted()) > 0
        best_idx = jnp.where(has, best, 0)
        return out_val, out_ok & has, best_idx

    def segment_update(self, v, seg_ids, num_segments, live_mask):
        cap = int(v.data.shape[0])
        idx = jnp.arange(cap, dtype=jnp.int64)
        val, ok, bidx = self._pick(v.data.astype(self.dtype.jnp_dtype),
                                   v.validity, idx, seg_ids, num_segments,
                                   live_mask)
        ones = jnp.ones(num_segments, dtype=jnp.bool_)
        return [DevVal(self.dtype, val, ones), DevVal(T.BOOLEAN, ok, ones),
                DevVal(T.LONG, bidx, ones)]

    def segment_merge(self, buffers, seg_ids, num_segments, live_mask):
        val, ok, idx = buffers
        nv, nok, nidx = self._pick(val.data, ok.data.astype(jnp.bool_),
                                   idx.data, seg_ids, num_segments, live_mask)
        ones = jnp.ones(num_segments, dtype=jnp.bool_)
        return [DevVal(self.dtype, nv, ones), DevVal(T.BOOLEAN, nok, ones),
                DevVal(T.LONG, nidx, ones)]

    def finalize(self, buffers):
        val, ok, _ = buffers
        return DevVal(self.dtype, val.data, ok.data.astype(jnp.bool_))

    def cpu_reduce(self, values, validity):
        order = range(len(values)) if self._is_first else \
            range(len(values) - 1, -1, -1)
        for i in order:
            if self.ignore_nulls and not validity[i]:
                continue
            return values[i] if validity[i] else None
        return None


class First(_FirstLast):
    _is_first = True


class Last(_FirstLast):
    _is_first = False


class Percentile(AggregateFunction):
    """percentile(x, p): Spark's exact percentile with linear
    interpolation between closest ranks (used by the reference's mortgage
    AggregatesWithPercentiles benchmark, MortgageSpark.scala:368-390).

    Never executed directly: the dataframe layer rewrites it into a
    rank-and-interpolate pipeline over existing machinery — row_number +
    count windows produce each row's interpolation weight, a plain SUM
    collapses them (see GroupedData._agg_with_percentile).  A buffered
    two-phase implementation would need unbounded per-group state, which
    the fixed-slot aggregate model deliberately excludes."""

    def __init__(self, child: Expression, percentage: float):
        if not (0.0 <= float(percentage) <= 1.0):
            raise ValueError(
                f"percentile percentage must be in [0, 1]: {percentage}")
        self.percentage = float(percentage)
        super().__init__(child)

    def with_children(self, children):
        return Percentile(children[0], self.percentage)

    def _resolve_type(self):
        dt = self.child.dtype
        if dt is not T.NULL and not dt.is_numeric:  # NULL = unresolved yet
            raise TypeError(f"percentile needs a numeric input, got {dt}")
        self.dtype = T.DOUBLE
        self.nullable = True

    def tpu_supported(self, conf):
        return None

    def buffers(self):
        raise AssertionError(
            "Percentile must be rewritten before execution")


class CountDistinct(AggregateFunction):
    """count(DISTINCT x).

    Never executed directly: the dataframe layer rewrites any aggregation
    containing it into two stacked Aggregates (group by keys+value, then by
    keys), the distinct-aggregate rewrite Spark's planner applies
    (cf. RewriteDistinctAggregates; the reference rides the rewritten plan's
    Partial/PartialMerge modes, aggregate.scala).  See
    GroupedData._agg_with_distinct."""

    def _resolve_type(self):
        self.dtype = T.LONG
        self.nullable = False

    def tpu_supported(self, conf):
        return None

    def buffers(self):
        raise AssertionError(
            "CountDistinct must be rewritten before execution")


@dataclasses.dataclass
class AggregateExpression:
    """An aggregate call in an output position: fn + output name."""

    fn: AggregateFunction
    output_name: str

    @property
    def dtype(self):
        return self.fn.dtype


def count_star() -> Count:
    return Count(Literal(1, T.INT))


class GroupingID(AggregateFunction):
    """grouping_id(): the bitmask of masked-out grouping keys under
    ROLLUP/CUBE/GROUPING SETS (Spark GroupingID).  A marker the
    grouping-sets rewrite replaces with min(__grouping_id) — reaching
    execution unreplaced means it was used outside grouping sets."""

    def __init__(self):
        super().__init__(Literal(0, T.INT))

    def with_children(self, children):
        return GroupingID()

    def _resolve_type(self):
        self.dtype = T.INT
        self.nullable = False

    def tpu_supported(self, conf):
        return None

    def buffers(self):
        raise AssertionError(
            "grouping_id() is only valid under rollup/cube/grouping sets")


class _CentralMoment(AggregateFunction):
    """stddev/variance family over (n, n*mean, m2-contribution) buffers.

    Partials merge with Chan's k-way formula expressed as three segment
    sums: S0 = Σnᵢ, S1 = Σnᵢ·meanᵢ, S2 = Σ(m2ᵢ + nᵢ·meanᵢ²); then
    mean = S1/S0 and m2 = S2 − S1²/S0 — numerically safer than raw
    sum-of-squares across shuffled partials.  Spark semantics: NULL for
    zero rows; sample variants give NaN for a single row (0/0)."""

    _sample = True   # ddof=1
    _sqrt = False    # stddev vs variance

    def _resolve_type(self):
        self.dtype = T.DOUBLE
        self.nullable = True

    def buffers(self):
        return [AggBufferSpec(T.DOUBLE), AggBufferSpec(T.DOUBLE),
                AggBufferSpec(T.DOUBLE)]

    def segment_update(self, v, seg_ids, num_segments, live_mask):
        use = v.validity & live_mask
        x = jnp.where(use, v.data.astype(jnp.float64), 0.0)
        n = jax.ops.segment_sum(use.astype(jnp.float64), seg_ids,
                                num_segments=num_segments,
                                indices_are_sorted=_seg_sorted())
        s1 = jax.ops.segment_sum(x, seg_ids, num_segments=num_segments,
                                 indices_are_sorted=_seg_sorted())
        # two-pass m2: deviations from the per-group mean, NOT the
        # cancellation-prone Σx² − (Σx)²/n (large-mean data — e.g. epoch
        # timestamps — loses every significant digit under that form)
        mean = s1 / jnp.maximum(n, 1.0)
        d = jnp.where(use, x - mean[seg_ids], 0.0)
        m2 = jax.ops.segment_sum(d * d, seg_ids,
                                 num_segments=num_segments,
                                 indices_are_sorted=_seg_sorted())
        ones = jnp.ones(num_segments, dtype=jnp.bool_)
        return [DevVal(T.DOUBLE, n, ones),
                DevVal(T.DOUBLE, s1, ones),   # n*mean = Σx
                DevVal(T.DOUBLE, m2, ones)]

    def segment_merge(self, buffers, seg_ids, num_segments, live_mask):
        n_i, nm_i, m2_i = (b.data for b in buffers)
        live = live_mask.astype(jnp.float64)
        s0 = jax.ops.segment_sum(n_i * live, seg_ids,
                                 num_segments=num_segments,
                                 indices_are_sorted=_seg_sorted())
        s1 = jax.ops.segment_sum(nm_i * live, seg_ids,
                                 num_segments=num_segments,
                                 indices_are_sorted=_seg_sorted())
        # deviation form of Chan's combine: m2 = Σm2ᵢ + Σnᵢ·(meanᵢ−mean)²
        # — the Σnᵢ·meanᵢ² − n·mean² form cancels catastrophically for
        # large means (epoch-scale data), this one never does
        mean = s1 / jnp.maximum(s0, 1.0)
        mean_i = nm_i / jnp.maximum(n_i, 1.0)
        dev = mean_i - mean[seg_ids]
        m2 = jax.ops.segment_sum((m2_i + n_i * dev * dev) * live, seg_ids,
                                 num_segments=num_segments,
                                 indices_are_sorted=_seg_sorted())
        ones = jnp.ones(num_segments, dtype=jnp.bool_)
        return [DevVal(T.DOUBLE, s0, ones), DevVal(T.DOUBLE, s1, ones),
                DevVal(T.DOUBLE, m2, ones)]

    def finalize(self, buffers):
        n, _, m2 = (b.data for b in buffers)
        m2 = jnp.maximum(m2, 0.0)  # clamp negative rounding residue
        denom = n - 1.0 if self._sample else n
        out = m2 / denom  # n==1 sample: 0/0 -> NaN (Spark)
        if self._sqrt:
            out = jnp.sqrt(out)
        return DevVal(T.DOUBLE, out, n > 0)

    def cpu_reduce(self, values, validity):
        vals = np.asarray(values[validity], dtype=np.float64)
        if len(vals) == 0:
            return None
        ddof = 1 if self._sample else 0
        if self._sample and len(vals) == 1:
            return float("nan")
        with np.errstate(all="ignore"):
            var = float(np.var(vals, ddof=ddof))
            return float(np.sqrt(var)) if self._sqrt else var


class StddevSamp(_CentralMoment):
    _sample, _sqrt = True, True


class StddevPop(_CentralMoment):
    _sample, _sqrt = False, True


class VarianceSamp(_CentralMoment):
    _sample, _sqrt = True, False


class VariancePop(_CentralMoment):
    _sample, _sqrt = False, False


class _BinaryStatMarker(AggregateFunction):
    """corr/covar family marker: two children, never executed directly —
    the dataframe layer rewrites it onto windows + arithmetic + SUM
    (GroupedData._agg_with_binary_stats), since every aggregation path
    assumes single-child aggregates."""

    def __init__(self, left: Expression, right: Expression):
        self.children = (left, right)
        self._resolve_type()

    @property
    def left(self):
        return self.children[0]

    @property
    def right(self):
        return self.children[1]

    def with_children(self, children):
        return type(self)(children[0], children[1])

    def _resolve_type(self):
        for c in self.children:
            if c.dtype is not T.NULL and not c.dtype.is_numeric:
                raise TypeError(
                    f"{type(self).__name__} needs numeric inputs, "
                    f"got {c.dtype}")
        self.dtype = T.DOUBLE
        self.nullable = True

    def tpu_supported(self, conf):
        return None

    def buffers(self):
        raise AssertionError(
            f"{type(self).__name__} must be rewritten before execution")


class CovarPop(_BinaryStatMarker):
    pass


class CovarSamp(_BinaryStatMarker):
    pass


class Corr(_BinaryStatMarker):
    pass
