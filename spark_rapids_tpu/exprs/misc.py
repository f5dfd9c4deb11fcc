"""Nondeterministic and internal expressions (reference:
GpuRandomExpressions.scala, GpuMonotonicallyIncreasingID.scala,
GpuSparkPartitionID.scala, NormalizeFloatingNumbers.scala)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exprs.base import (
    CpuVal, DevVal, Expression, Literal, UnaryExpression,
)


class MonotonicallyIncreasingID(Expression):
    """(partition_id << 33) + row offset within partition."""

    context_free = False

    def __init__(self):
        self.children = ()
        self.dtype = T.LONG
        self.nullable = False

    def with_children(self, children):
        return self

    def tpu_eval(self, ctx) -> DevVal:
        base = (jnp.int64(ctx.partition_index) << 33) + ctx.base_row_id
        data = base + jnp.arange(ctx.capacity, dtype=jnp.int64)
        return DevVal(T.LONG, data, jnp.ones(ctx.capacity, dtype=jnp.bool_))

    def cpu_eval(self, ctx) -> CpuVal:
        base = (np.int64(ctx.partition_index) << np.int64(33)) + ctx.base_row_id
        data = base + np.arange(ctx.num_rows, dtype=np.int64)
        return CpuVal(T.LONG, data, np.ones(ctx.num_rows, dtype=np.bool_))


class SparkPartitionID(Expression):
    context_free = False

    def __init__(self):
        self.children = ()
        self.dtype = T.INT
        self.nullable = False

    def with_children(self, children):
        return self

    def tpu_eval(self, ctx) -> DevVal:
        data = jnp.full(ctx.capacity, ctx.partition_index, dtype=jnp.int32)
        return DevVal(T.INT, data, jnp.ones(ctx.capacity, dtype=jnp.bool_))

    def cpu_eval(self, ctx) -> CpuVal:
        data = np.full(ctx.num_rows, ctx.partition_index, dtype=np.int32)
        return CpuVal(T.INT, data, np.ones(ctx.num_rows, dtype=np.bool_))


class Rand(Expression):
    """Uniform [0,1) per row.  Nondeterministic: TPU uses jax PRNG keyed by
    (seed, partition, base row id) — results differ from Spark CPU's XORShift
    but are deterministic per plan execution (the reference flags GpuRand as
    'retries are not idempotent')."""

    context_free = False

    def __init__(self, seed: int = 0):
        self.children = ()
        self.seed = int(seed)
        self.dtype = T.DOUBLE
        self.nullable = False

    def with_children(self, children):
        return self

    def tpu_eval(self, ctx) -> DevVal:
        key = jax.random.PRNGKey(self.seed + 1000003 * (ctx.partition_index + 1))
        key = jax.random.fold_in(key, ctx.base_row_id.astype(jnp.uint32))
        data = jax.random.uniform(key, (ctx.capacity,), dtype=jnp.float64)
        return DevVal(T.DOUBLE, data, jnp.ones(ctx.capacity, dtype=jnp.bool_))

    def cpu_eval(self, ctx) -> CpuVal:
        rng = np.random.RandomState(
            (self.seed + 1000003 * (ctx.partition_index + 1)
             + 31 * int(ctx.base_row_id)) % (2 ** 31))
        data = rng.uniform(size=ctx.num_rows)
        return CpuVal(T.DOUBLE, data, np.ones(ctx.num_rows, dtype=np.bool_))


class KnownFloatingPointNormalized(UnaryExpression):
    """Normalize -0.0 -> 0.0 and NaN -> canonical NaN for float grouping keys
    (reference: NormalizeFloatingNumbers.scala)."""

    def tpu_eval(self, ctx) -> DevVal:
        v = self.child.tpu_eval(ctx)
        data = jnp.where(v.data == 0, jnp.zeros_like(v.data), v.data)
        data = jnp.where(jnp.isnan(data), jnp.full_like(data, jnp.nan), data)
        return DevVal(v.dtype, data, v.validity)

    def cpu_eval(self, ctx) -> CpuVal:
        v = self.child.cpu_eval(ctx)
        data = np.where(v.values == 0, np.zeros_like(v.values), v.values)
        data = np.where(np.isnan(data), np.full_like(data, np.nan), data)
        return CpuVal(v.dtype, data, v.validity)


class CreateArray(Expression):
    """array(e1, e2, ...) -> array<common element type>
    (GpuCreateArray, complexTypeCreator analogue).  TPU path requires
    non-nullable inputs (element-level NULLs are host-only in the v1
    nested envelope); nullable inputs fall back to CPU."""

    def __init__(self, *children: Expression):
        assert children, "array() needs at least one element"
        elem = children[0].dtype
        for c in children[1:]:
            elem = T.promote(elem, c.dtype)
        self.children = tuple(children)
        self.dtype = T.ArrayType(elem)
        self.nullable = False

    def with_children(self, children):
        return CreateArray(*children)

    def tpu_supported(self, conf):
        if self.dtype.element.is_string:
            return ("array<string> has variable-length elements "
                    "(host-only in the v1 nested envelope)")
        if any(c.nullable for c in self.children):
            return ("array() with nullable inputs can produce NULL "
                    "elements (host-only in the v1 nested envelope)")
        return None

    def tpu_eval(self, ctx) -> DevVal:
        import jax.numpy as jnp
        elem = self.dtype.element
        vals = [c.tpu_eval(ctx) for c in self.children]
        k = len(vals)
        cap = ctx.capacity
        data = jnp.stack([v.data.astype(elem.jnp_dtype) for v in vals],
                         axis=1).reshape(-1)  # row-major [cap*k]
        offsets = (jnp.arange(cap + 1, dtype=jnp.int32) * k)
        # live rows only: clamp offsets past num_rows to the live total
        total = ctx.num_rows * k
        offsets = jnp.minimum(offsets, total.astype(jnp.int32))
        return DevVal(self.dtype, data,
                      jnp.ones(cap, dtype=jnp.bool_), offsets)

    def cpu_eval(self, ctx) -> CpuVal:
        vals = [c.cpu_eval(ctx) for c in self.children]
        n = ctx.num_rows
        out = np.empty(n, dtype=object)
        elem = self.dtype.element
        for i in range(n):
            out[i] = [
                (None if not v.validity[i] else
                 T.np_scalar(elem, v.values[i]))
                for v in vals]
        return CpuVal(self.dtype, out, np.ones(n, dtype=np.bool_))


class GetArrayItem(Expression):
    """arr[i] with a literal 0-based ordinal (GpuGetArrayItem,
    complexTypeExtractors.scala): NULL when out of range or the array row
    is NULL."""

    def __init__(self, child: Expression, ordinal: int):
        self.children = (child,)
        self.ordinal = int(ordinal)
        # pre-resolution the child is an untyped ColumnRef; the planner
        # rebuilds this node with resolved children (with_children)
        self.dtype = child.dtype.element \
            if isinstance(child.dtype, T.ArrayType) else T.NULL
        self.nullable = True

    def with_children(self, children):
        return GetArrayItem(children[0], self.ordinal)

    def tpu_supported(self, conf):
        if not isinstance(self.children[0].dtype, T.ArrayType):
            return f"getItem needs an array, got {self.children[0].dtype}"
        return None

    def tpu_eval(self, ctx) -> DevVal:
        import jax.numpy as jnp
        v = self.children[0].tpu_eval(ctx)
        if self.ordinal < 0:
            # Spark: negative ordinals are out of range -> NULL
            return DevVal(self.dtype,
                          jnp.zeros(ctx.capacity,
                                    dtype=self.dtype.jnp_dtype),
                          jnp.zeros(ctx.capacity, dtype=jnp.bool_))
        lens = (v.offsets[1:] - v.offsets[:-1]).astype(jnp.int32)
        in_range = self.ordinal < lens
        idx = jnp.clip(v.offsets[:-1] + self.ordinal, 0,
                       int(v.data.shape[0]) - 1)
        data = jnp.where(in_range, v.data[idx], 0)
        return DevVal(self.dtype, data.astype(self.dtype.jnp_dtype),
                      v.validity & in_range & ctx.row_mask)

    def cpu_eval(self, ctx) -> CpuVal:
        # Spark semantics: negative / out-of-range ordinals yield NULL
        # (non-ANSI), never python-style tail indexing.
        v = self.children[0].cpu_eval(ctx)
        n = len(v.values)
        out = np.zeros(n, dtype=self.dtype.np_dtype)
        ok = np.zeros(n, dtype=np.bool_)
        k = self.ordinal
        for i, (arr, valid) in enumerate(zip(v.values, v.validity)):
            if valid and arr is not None and 0 <= k < len(arr) and \
                    arr[k] is not None:
                out[i] = arr[k]
                ok[i] = True
        return CpuVal(self.dtype, out, ok)


class ArraySize(UnaryExpression):
    """size(arr) -> INT element count; size(NULL) -> NULL.

    This matches Spark with ``spark.sql.legacy.sizeOfNull=false`` (the
    ANSI-aligned behavior; Spark's historical default returns -1 for NULL
    input).  Documented divergence from the legacy default."""

    def _resolve_type(self):
        self.dtype = T.INT
        self.nullable = self.child.nullable

    def tpu_supported(self, conf):
        if not isinstance(self.child.dtype, T.ArrayType):
            return f"size needs an array, got {self.child.dtype}"
        return None

    def tpu_eval(self, ctx) -> DevVal:
        import jax.numpy as jnp
        v = self.child.tpu_eval(ctx)
        lens = (v.offsets[1:] - v.offsets[:-1]).astype(jnp.int32)
        return DevVal(T.INT, lens, v.validity)

    def cpu_eval(self, ctx) -> CpuVal:
        v = self.child.cpu_eval(ctx)
        n = len(v.values)
        out = np.zeros(n, dtype=np.int32)
        for i, (arr, ok) in enumerate(zip(v.values, v.validity)):
            out[i] = len(arr) if ok and arr is not None else 0
        return CpuVal(T.INT, out, v.validity)


def _array_rows(v):
    """int32[n_elements]: owning row of each flat element slot (the
    strings module's byte->row mapping, reused for array elements)."""
    from spark_rapids_tpu.exprs.strings import rows_of_positions
    return rows_of_positions(v.offsets, int(v.data.shape[0]))


def _element_slots(v, cap):
    """(rows, in_range) for the flat element buffer: owning row per slot
    (clipped into [0, cap)) and the live-slot mask."""
    nelem = int(v.data.shape[0])
    rows = jnp.clip(_array_rows(v), 0, cap - 1)
    in_range = jnp.arange(nelem, dtype=jnp.int32) < v.offsets[-1]
    return rows, in_range


def _host_isnan(value) -> bool:
    return isinstance(value, float) and value != value


def _needle_eq(e, needle) -> bool:
    """Ordering equivalence for array membership (Spark ArrayContains /
    ArrayPosition): NaN equals NaN, unlike IEEE ==."""
    if _host_isnan(needle):
        return isinstance(e, float) and e != e
    return e == needle


def _check_array_needle(elem_dt, value):
    """Reject needles whose python type does not match the element type
    (a silent narrowing cast would diverge between backends)."""
    if elem_dt.is_string:
        ok = isinstance(value, str)
    elif elem_dt == T.BOOLEAN:
        ok = isinstance(value, bool)
    elif elem_dt.is_integral:
        ok = isinstance(value, int) and not isinstance(value, bool)
    else:
        ok = isinstance(value, (int, float)) and             not isinstance(value, bool)
    if not ok:
        raise TypeError(
            f"needle {value!r} does not match array element type "
            f"{elem_dt} (no implicit narrowing)")


class ArrayContains(Expression):
    """array_contains(arr, literal) -> BOOLEAN (GpuArrayContains role,
    collectionOperations).  NULL array -> NULL; literal must be a
    non-null scalar (Spark requires a foldable non-null value)."""

    def __init__(self, child: Expression, value):
        if isinstance(value, Expression) and not isinstance(value,
                                                            Literal):
            raise NotImplementedError(
                "array_contains needs a literal needle (column-valued "
                "needles are not supported, like the reference's GPU "
                "plugin)")
        if not isinstance(value, Literal):
            value = Literal(value)
        if value.value is None:
            raise ValueError("array_contains value must not be NULL")
        self.children = (child, value)
        self.dtype = T.BOOLEAN
        # NULL when the array row is NULL, or when it has NULL elements
        # and no match (Spark three-valued IN semantics)
        self.nullable = True

    def with_children(self, children):
        return ArrayContains(children[0], children[1])

    def _check_needle(self, elem_dt):
        _check_array_needle(elem_dt, self.children[1].value)

    def tpu_supported(self, conf):
        dt = self.children[0].dtype
        if not isinstance(dt, T.ArrayType):
            return f"array_contains needs an array, got {dt}"
        if dt.element.is_string:
            return "array<string> is host-only"
        self._check_needle(dt.element)
        return None

    def tpu_eval(self, ctx) -> DevVal:
        import jax
        import jax.numpy as jnp
        v = self.children[0].tpu_eval(ctx)
        cap = ctx.capacity
        elem_dt = self.children[0].dtype.element
        self._check_needle(elem_dt)
        needle = jnp.asarray(self.children[1].value,
                             dtype=elem_dt.jnp_dtype)
        rows, in_range = _element_slots(v, cap)
        # Spark's ArrayContains uses ordering equivalence: NaN == NaN
        if elem_dt.is_fractional and _host_isnan(self.children[1].value):
            hit = in_range & jnp.isnan(v.data)
        else:
            hit = in_range & (v.data == needle)
        n_hits = jax.ops.segment_sum(hit.astype(jnp.int32), rows,
                                     num_segments=cap,
                                     indices_are_sorted=True)
        return DevVal(T.BOOLEAN, n_hits > 0, v.validity)

    def cpu_eval(self, ctx) -> CpuVal:
        v = self.children[0].cpu_eval(ctx)
        dt = self.children[0].dtype
        if isinstance(dt, T.ArrayType):
            self._check_needle(dt.element)
        needle = self.children[1].value
        n = len(v.values)
        out = np.zeros(n, dtype=np.bool_)
        valid = np.array(v.validity, dtype=np.bool_).copy()
        for i, (arr, ok) in enumerate(zip(v.values, v.validity)):
            if not (ok and arr is not None):
                continue
            hit = any(e is not None and _needle_eq(e, needle)
                      for e in arr)
            out[i] = hit
            if not hit and any(e is None for e in arr):
                valid[i] = False  # Spark: NULL element + no match -> NULL
        return CpuVal(T.BOOLEAN, out, valid)


class _ArrayMinMax(UnaryExpression):
    """array_min / array_max: reduce each row's elements (NULL for an
    empty or NULL array, Spark semantics)."""

    _is_min = True

    def _resolve_type(self):
        dt = self.child.dtype
        self.dtype = dt.element if isinstance(dt, T.ArrayType) else T.NULL
        self.nullable = True

    def tpu_supported(self, conf):
        dt = self.child.dtype
        if not isinstance(dt, T.ArrayType):
            return f"{self.name} needs an array, got {dt}"
        if dt.element.is_string:
            return "array<string> is host-only"
        return None

    def tpu_eval(self, ctx) -> DevVal:
        import jax
        import jax.numpy as jnp
        v = self.child.tpu_eval(ctx)
        cap = ctx.capacity
        jdt = self.dtype.jnp_dtype
        if self.dtype.is_fractional:
            ident = jnp.asarray(jnp.inf if self._is_min else -jnp.inf,
                                jdt)
        elif self.dtype == T.BOOLEAN:
            ident = jnp.asarray(True if self._is_min else False)
        else:
            info = jnp.iinfo(jdt)
            ident = jnp.asarray(info.max if self._is_min else info.min,
                                jdt)
        rows, in_range = _element_slots(v, cap)
        x = jnp.where(in_range, v.data.astype(jdt), ident)
        if self.dtype.is_fractional:
            # Spark orders NaN as the LARGEST value: min skips NaNs
            # (unless every element is NaN), max is NaN if any present
            is_nan = in_range & jnp.isnan(x)
            x = jnp.where(is_nan, ident, x)
            nan_cnt = jax.ops.segment_sum(
                is_nan.astype(jnp.int32), rows, num_segments=cap,
                indices_are_sorted=True)
            notnan_cnt = jax.ops.segment_sum(
                (in_range & ~is_nan).astype(jnp.int32), rows,
                num_segments=cap, indices_are_sorted=True)
        red = jax.ops.segment_min if self._is_min else \
            jax.ops.segment_max
        out = red(x, rows, num_segments=cap, indices_are_sorted=True)
        if self.dtype.is_fractional:
            nan = jnp.asarray(jnp.nan, jdt)
            if self._is_min:
                out = jnp.where((notnan_cnt == 0) & (nan_cnt > 0), nan,
                                out)
            else:
                out = jnp.where(nan_cnt > 0, nan, out)
        lens = (v.offsets[1:] - v.offsets[:-1]) > 0
        return DevVal(self.dtype, out, v.validity & lens)

    def cpu_eval(self, ctx) -> CpuVal:
        v = self.child.cpu_eval(ctx)
        n = len(v.values)
        out = np.zeros(n, dtype=self.dtype.np_dtype)
        valid = np.zeros(n, dtype=np.bool_)
        frac = self.dtype.is_fractional
        for i, (arr, ok) in enumerate(zip(v.values, v.validity)):
            if not (ok and arr):
                continue
            vals = [e for e in arr if e is not None]
            if not vals:
                continue
            valid[i] = True
            if frac:
                nn = [e for e in vals if e == e]
                if self._is_min:
                    out[i] = min(nn) if nn else float("nan")
                else:
                    out[i] = float("nan") if len(nn) < len(vals) \
                        else max(vals)
            else:
                out[i] = min(vals) if self._is_min else max(vals)
        return CpuVal(self.dtype, out, valid)


class ArrayMin(_ArrayMinMax):
    _is_min = True


class ArrayMax(_ArrayMinMax):
    _is_min = False


class SortArray(UnaryExpression):
    """sort_array(arr[, asc]) — per-row element sort (Spark SortArray).
    Device path: one lexsort over (owning row, element value) reorders
    the flat element buffer; offsets/validity are untouched."""

    def __init__(self, child: Expression, ascending: bool = True):
        self.ascending = bool(ascending)
        super().__init__(child)

    def with_children(self, children):
        return SortArray(children[0], self.ascending)

    def _resolve_type(self):
        self.dtype = self.child.dtype
        self.nullable = self.child.nullable

    def tpu_supported(self, conf):
        dt = self.child.dtype
        if not isinstance(dt, T.ArrayType):
            return f"sort_array needs an array, got {dt}"
        if dt.element.is_string:
            return "array<string> is host-only"
        return None

    def tpu_eval(self, ctx) -> DevVal:
        import jax.numpy as jnp
        v = self.child.tpu_eval(ctx)
        cap = ctx.capacity
        rows, in_range = _element_slots(v, cap)
        elem_dt = self.child.dtype.element
        jdt = elem_dt.jnp_dtype
        x = v.data.astype(jdt)
        if elem_dt.is_fractional:
            is_nan = jnp.isnan(x)
            rk = jnp.where(is_nan, jnp.inf, x.astype(jnp.float64))
            if not self.ascending:
                rk = -rk
            # rank separates NaN from real infinities on key ties, and
            # padding from everything: NaN sorts last ascending / first
            # descending (Spark: NaN is the largest value)
            nan_rank = jnp.where(is_nan,
                                 1 if self.ascending else -1, 0)
        else:
            rk = x.astype(jnp.int64)  # exact for the full int64 range
            if not self.ascending:
                rk = ~rk  # complement: monotone flip, no INT64_MIN wrap
            nan_rank = jnp.zeros_like(rows)
        rk = jnp.where(in_range, rk, 0)
        nan_rank = jnp.where(in_range, nan_rank, 2)  # padding dead last
        order = jnp.lexsort((nan_rank, rk, rows.astype(jnp.int32)))
        data = v.data[order]
        return DevVal(self.dtype, data, v.validity, v.offsets)

    def cpu_eval(self, ctx) -> CpuVal:
        v = self.child.cpu_eval(ctx)
        out = np.empty(len(v.values), dtype=object)
        for i, (arr, ok) in enumerate(zip(v.values, v.validity)):
            if not ok or arr is None:
                out[i] = None
                continue
            nn = [e for e in arr if e is not None]
            nulls = [None] * (len(arr) - len(nn))
            key = (lambda e: (e != e, e)) if any(
                isinstance(e, float) for e in nn) else (lambda e: e)
            s = sorted(nn, key=key, reverse=not self.ascending)
            # Spark: NULL elements first ascending, last descending
            out[i] = nulls + s if self.ascending else s + nulls
        return CpuVal(self.dtype, out, v.validity)


class ArrayPosition(Expression):
    """array_position(arr, literal): 1-based index of the first match,
    0 when absent, NULL for a NULL array (Spark ArrayPosition)."""

    def __init__(self, child: Expression, value):
        if isinstance(value, Expression) and not isinstance(value,
                                                            Literal):
            raise NotImplementedError(
                "array_position needs a literal needle")
        if not isinstance(value, Literal):
            value = Literal(value)
        if value.value is None:
            raise ValueError("array_position value must not be NULL")
        self.children = (child, value)
        self.dtype = T.LONG
        self.nullable = child.nullable

    def with_children(self, children):
        return ArrayPosition(children[0], children[1])

    def tpu_supported(self, conf):
        dt = self.children[0].dtype
        if not isinstance(dt, T.ArrayType):
            return f"array_position needs an array, got {dt}"
        if dt.element.is_string:
            return "array<string> is host-only"
        _check_array_needle(dt.element, self.children[1].value)
        return None

    def tpu_eval(self, ctx) -> DevVal:
        import jax
        import jax.numpy as jnp
        v = self.children[0].tpu_eval(ctx)
        cap = ctx.capacity
        elem_dt = self.children[0].dtype.element
        _check_array_needle(elem_dt, self.children[1].value)
        needle = jnp.asarray(self.children[1].value,
                             dtype=elem_dt.jnp_dtype)
        rows, in_range = _element_slots(v, cap)
        pos = jnp.arange(int(v.data.shape[0]), dtype=jnp.int32)
        if elem_dt.is_fractional and _host_isnan(self.children[1].value):
            hit = in_range & jnp.isnan(v.data)
        else:
            hit = in_range & (v.data == needle)
        big = jnp.int32(1 << 30)
        first = jax.ops.segment_min(jnp.where(hit, pos, big), rows,
                                    num_segments=cap,
                                    indices_are_sorted=True)
        found = first < big
        idx = jnp.where(found,
                        first - v.offsets[:-1].astype(jnp.int32) + 1, 0)
        return DevVal(T.LONG, idx.astype(jnp.int64), v.validity)

    def cpu_eval(self, ctx) -> CpuVal:
        v = self.children[0].cpu_eval(ctx)
        dt = self.children[0].dtype
        if isinstance(dt, T.ArrayType):
            _check_array_needle(dt.element, self.children[1].value)
        needle = self.children[1].value
        n = len(v.values)
        out = np.zeros(n, dtype=np.int64)
        for i, (arr, ok) in enumerate(zip(v.values, v.validity)):
            if ok and arr is not None:
                for j, e in enumerate(arr):
                    if e is not None and _needle_eq(e, needle):
                        out[i] = j + 1
                        break
        return CpuVal(T.LONG, out, v.validity)
