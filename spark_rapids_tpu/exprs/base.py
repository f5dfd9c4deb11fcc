"""Expression base classes and evaluation contexts.

Reference analogue: GpuExpressions.scala (Unary/Binary/Ternary columnarEval
traits) + GpuBoundAttribute.scala.  ``tpu_eval`` runs inside a traced (jit)
stage over a :class:`~spark_rapids_tpu.batch.ColumnBatch`; ``cpu_eval`` is the
numpy oracle with Spark CPU semantics.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.batch import ColumnBatch, DeviceColumn, HostBatch, HostColumn
from spark_rapids_tpu.utils import params as _params


@dataclasses.dataclass
class DevVal:
    """An evaluated expression on device: dense buffers + validity mask.

    For strings ``data`` is the flat uint8 byte buffer and ``offsets`` the
    int32[cap+1] row offsets; otherwise ``data`` is [cap] of the jnp dtype.

    A dictionary-encoded string value (scan v2) additionally carries
    ``codes`` (int32[cap] row -> entry indices; data/offsets then describe
    the dictionary ENTRIES) and the static ``mat_byte_cap`` it would
    materialize into.  Only :func:`eval_maybe_encoded` produces these —
    ``from_column`` always materializes, so no kernel sees an encoded
    value it did not ask for.
    """

    dtype: T.DataType
    data: Any
    validity: Any
    offsets: Any = None
    codes: Any = None
    mat_byte_cap: int = 0

    @property
    def capacity(self) -> int:
        if self.codes is not None:
            return int(self.codes.shape[0])
        if self.offsets is not None:
            return int(self.offsets.shape[0]) - 1
        return int(self.data.shape[0])

    def to_column(self) -> DeviceColumn:
        return DeviceColumn(self.dtype, self.data, self.validity,
                            self.offsets, self.codes, self.mat_byte_cap)

    @staticmethod
    def from_column(col: DeviceColumn) -> "DevVal":
        if col.codes is not None:
            from spark_rapids_tpu.kernels.layout import dict_decode_column
            col = dict_decode_column(col)
        return DevVal(col.dtype, col.data, col.validity, col.offsets)

    @staticmethod
    def from_column_encoded(col: DeviceColumn) -> "DevVal":
        """Wrap a column verbatim, KEEPING dictionary encoding — only for
        callers that handle encoded values (hash/eq/group-key paths)."""
        return DevVal(col.dtype, col.data, col.validity, col.offsets,
                      col.codes, col.mat_byte_cap)

    def tree_flatten(self):
        if self.codes is not None:
            return ((self.data, self.validity, self.offsets, self.codes),
                    (self.dtype, True, True, self.mat_byte_cap))
        if self.offsets is None:
            return (self.data, self.validity), (self.dtype, False, False, 0)
        return ((self.data, self.validity, self.offsets),
                (self.dtype, True, False, 0))

    @classmethod
    def tree_unflatten(cls, aux, children):
        dtype, has_offsets, has_codes, mat_byte_cap = aux
        if has_codes:
            data, validity, offsets, codes = children
            return cls(dtype, data, validity, offsets, codes, mat_byte_cap)
        if has_offsets:
            data, validity, offsets = children
            return cls(dtype, data, validity, offsets)
        data, validity = children
        return cls(dtype, data, validity, None)


jax.tree_util.register_pytree_node(
    DevVal, DevVal.tree_flatten, DevVal.tree_unflatten
)


@dataclasses.dataclass
class CpuVal:
    """Numpy evaluation result (strings: object array of str)."""

    dtype: T.DataType
    values: np.ndarray
    validity: np.ndarray

    def to_column(self) -> HostColumn:
        return HostColumn(self.dtype, self.values, self.validity)

    @staticmethod
    def from_column(col: HostColumn) -> "CpuVal":
        return CpuVal(col.dtype, col.values, col.validity)


class TpuEvalCtx:
    """Evaluation context for one device batch inside a traced stage."""

    def __init__(self, batch: ColumnBatch):
        self.batch = batch
        self.capacity = batch.capacity
        self.row_mask = batch.row_mask
        self.num_rows = batch.num_rows
        # partition_index is used by nondeterministic exprs (SparkPartitionID).
        self.partition_index = 0
        self.base_row_id = jnp.asarray(0, dtype=jnp.int64)


class CpuEvalCtx:
    def __init__(self, batch: HostBatch):
        self.batch = batch
        self.num_rows = batch.num_rows
        self.partition_index = 0
        self.base_row_id = 0


def _fp(v) -> str:
    """Encode a value for Expression.fingerprint (mirrors the
    plan_fingerprint encoder in plan/logical.py)."""
    if isinstance(v, Expression):
        return v.fingerprint()
    if isinstance(v, SortOrder):
        return f"SO({_fp(v.child)},{v.ascending},{v.nulls_first})"
    if isinstance(v, (str, int, float, bool, type(None))):
        return repr(v)
    if isinstance(v, T.DataType):
        return str(v)
    if isinstance(v, T.Schema):
        return str(v)
    if isinstance(v, (list, tuple)):
        return "[" + ",".join(_fp(x) for x in v) + "]"
    if isinstance(v, dict):
        return "{" + ",".join(f"{_fp(k)}:{_fp(x)}" for k, x in sorted(
            v.items(), key=lambda kv: str(kv[0]))) + "}"
    return f"id:{id(v):x}"


def _scoped_eval(tpu_eval, scope: str):
    @functools.wraps(tpu_eval)
    def run(self, ctx):
        with jax.named_scope(scope):
            return tpu_eval(self, ctx)
    return run


class Expression:
    """Declarative expression tree node.

    Subclasses define ``children``, resolve ``dtype``/``nullable`` in
    ``__init__``, and implement ``tpu_eval``/``cpu_eval``.
    """

    children: Tuple["Expression", ...] = ()
    dtype: T.DataType = T.NULL
    nullable: bool = True
    #: False on a class whose value is not a function of its children's
    #: values alone (row position, partition, a seed, opaque python, a
    #: reduction over rows): the planner never folds it to a constant
    context_free: bool = True

    def __init_subclass__(cls, **kwargs):
        # every expression class traces its device evaluation under
        # ``jax.named_scope("e.<Class>")``: a device trace can then say
        # which expression an operator's time went to (it showed q6
        # parsing ``to_date('<literal>')`` per row, 35 % of its device
        # time, until the planner folded constants: PERF.md, PR 27)
        super().__init_subclass__(**kwargs)
        own = cls.__dict__.get("tpu_eval")
        if own is not None:
            cls.tpu_eval = _scoped_eval(own, f"e.{cls.__name__}")

    # -- construction sugar used by the DataFrame frontend ------------------

    def alias(self, name: str) -> "Alias":
        return Alias(self, name)

    @property
    def name(self) -> str:
        return type(self).__name__

    def __repr__(self) -> str:
        args = ", ".join(repr(c) for c in self.children)
        return f"{self.name}({args})"

    def fingerprint(self) -> str:
        """Structural identity INCLUDING non-child attributes (Lag.offset,
        Percentile.percentage, window frames...) — repr() prints only
        class + children, so two semantically different expressions can
        share a repr.  Use this for any dedup/reuse keying."""
        parts = [type(self).__name__]
        for k, a in sorted(vars(self).items()):
            if k == "children":
                continue
            parts.append(f"{k}={_fp(a)}")
        kids = ",".join(_fp(c) for c in self.children)
        return f"{'|'.join(parts)}({kids})"

    # -- resolution ---------------------------------------------------------

    def with_children(self, children: Sequence["Expression"]) -> "Expression":
        """Rebuild this node with new children (default: positional ctor)."""
        return type(self)(*children)

    def transform_up(self, fn) -> "Expression":
        new_children = [c.transform_up(fn) for c in self.children]
        node = self if all(a is b for a, b in zip(new_children, self.children)) \
            else self.with_children(new_children)
        return fn(node)

    def collect(self, pred) -> List["Expression"]:
        out = [self] if pred(self) else []
        for c in self.children:
            out.extend(c.collect(pred))
        return out

    @property
    def references(self) -> List[str]:
        return [e.column for e in self.collect(lambda e: isinstance(e, ColumnRef))]

    @property
    def foldable(self) -> bool:
        """True when the planner may replace this subtree by the
        :class:`Literal` its ``cpu_eval`` gives on any one row
        (Catalyst's ``Expression.foldable``): a context-free class over
        foldable children.  A leaf other than a ``Literal`` takes its
        value from outside the tree (a column, the row's position), and
        a ``Literal`` cannot carry an array."""
        return (self.context_free and bool(self.children)
                and not self.dtype.is_array
                and all(c.foldable for c in self.children))

    # -- evaluation ---------------------------------------------------------

    def tpu_eval(self, ctx: TpuEvalCtx) -> DevVal:
        raise NotImplementedError(f"{self.name}.tpu_eval")

    def cpu_eval(self, ctx: CpuEvalCtx) -> CpuVal:
        raise NotImplementedError(f"{self.name}.cpu_eval")

    # -- planner hooks ------------------------------------------------------

    def tpu_supported(self, conf) -> Optional[str]:
        """Return None if supported on TPU, else a willNotWorkOnTpu reason."""
        if isinstance(self.dtype, T.ArrayType):
            # fixed-width-element arrays ride the varlen (offsets) layout;
            # consumers beyond project/filter/explode are gated at plan level
            return None
        if self.dtype not in T.ALL_TYPES and not isinstance(self.dtype, T.NullType):
            return f"unsupported result type {self.dtype}"
        return None


class ColumnRef(Expression):
    """Unresolved attribute: refers to an input column by name."""

    def __init__(self, column: str, dtype: T.DataType = T.NULL,
                 nullable: bool = True):
        self.column = column
        self.dtype = dtype
        self.nullable = nullable
        self.children = ()

    def with_children(self, children):
        return self

    @property
    def name(self):
        return f"col({self.column})"

    def __repr__(self):
        return f"`{self.column}`"

    def tpu_eval(self, ctx: TpuEvalCtx) -> DevVal:
        return DevVal.from_column(ctx.batch.column(self.column))

    def cpu_eval(self, ctx: CpuEvalCtx) -> CpuVal:
        return CpuVal.from_column(ctx.batch.column(self.column))


class BoundRef(Expression):
    """Reference bound to an input ordinal (GpuBoundAttribute.scala analogue)."""

    def __init__(self, ordinal: int, dtype: T.DataType, nullable: bool = True):
        self.ordinal = ordinal
        self.dtype = dtype
        self.nullable = nullable
        self.children = ()

    def with_children(self, children):
        return self

    def __repr__(self):
        return f"input[{self.ordinal}]"

    def tpu_eval(self, ctx: TpuEvalCtx) -> DevVal:
        return DevVal.from_column(ctx.batch.columns[self.ordinal])

    def cpu_eval(self, ctx: CpuEvalCtx) -> CpuVal:
        return CpuVal.from_column(ctx.batch.columns[self.ordinal])


def _unaliased(expr: "Expression") -> "Expression":
    while isinstance(expr, Alias):
        expr = expr.children[0]
    return expr


def may_stay_encoded(expr: "Expression") -> bool:
    """Static: ``eval_maybe_encoded`` can hand ``expr`` on encoded — it is
    a bare column reference, so it is whatever its column is."""
    return isinstance(_unaliased(expr), (ColumnRef, BoundRef))


def eval_maybe_encoded(expr: "Expression", ctx: TpuEvalCtx) -> DevVal:
    """Evaluate ``expr``, keeping dictionary encoding when it is a bare
    column reference.  Only hash/eq-based consumers (string equality
    predicates, group keys) may call this — every other path goes through
    ``tpu_eval`` → ``from_column`` which materializes."""
    column = column_as_it_is(expr, ctx)
    if column is not None:
        return DevVal.from_column_encoded(column)
    return expr.tpu_eval(ctx)


def column_as_it_is(expr: "Expression", ctx: TpuEvalCtx):
    """The input batch's own :class:`DeviceColumn` where ``expr`` is a
    bare column reference, else None: a projection that only carries a
    column on hands it on as it came — its dictionary codes with it, as a
    filter does — where ``tpu_eval(...).to_column()`` would materialize
    every row of an encoded string column (a million-row decode to hand
    a join a column of which it reads a few thousand rows: PERF.md,
    PR 36)."""
    expr = _unaliased(expr)
    if isinstance(expr, ColumnRef):
        return ctx.batch.column(expr.column)
    if isinstance(expr, BoundRef):
        return ctx.batch.columns[expr.ordinal]
    return None


class Literal(Expression):
    """A constant.  ``slot`` is None for a literal baked into the programs
    that evaluate it; a *lifted* literal (``plan/logical.plan_shape``)
    names the position of its value in the executing query's bound
    parameters (``utils/params``) and ``value`` is only what its shape was
    first planned with: evaluation and ``repr`` read the slot."""

    foldable = True
    slot: Optional[int] = None

    def __init__(self, value: Any, dtype: Optional[T.DataType] = None,
                 slot: Optional[int] = None):
        if dtype is None:
            dtype = infer_literal_type(value)
        self.value = value
        self.dtype = dtype
        self.nullable = value is None
        self.children = ()
        if slot is not None:
            self.slot = slot

    def with_children(self, children):
        return self

    def __repr__(self):
        if self.slot is not None:
            return f"lit({_params.shown(self.slot, self.value)!r})"
        return f"lit({self.value!r})"

    def tpu_eval(self, ctx: TpuEvalCtx) -> DevVal:
        cap = ctx.capacity
        if self.slot is not None:
            val = _params.traced(self.slot)
            return DevVal(self.dtype,
                          jnp.full(cap, val, dtype=self.dtype.jnp_dtype),
                          jnp.ones(cap, dtype=jnp.bool_))
        if self.value is None:
            validity = jnp.zeros(cap, dtype=jnp.bool_)
            if self.dtype.is_string:
                return DevVal(self.dtype, jnp.zeros(16, dtype=jnp.uint8), validity,
                              jnp.zeros(cap + 1, dtype=jnp.int32))
            return DevVal(self.dtype, jnp.zeros(cap, dtype=self.dtype.jnp_dtype),
                          validity)
        validity = jnp.ones(cap, dtype=jnp.bool_)
        if self.dtype.is_string:
            raw = np.frombuffer(str(self.value).encode("utf-8"), dtype=np.uint8)
            nbytes = max(len(raw), 1)
            data = jnp.zeros(cap * nbytes, dtype=jnp.uint8)
            tiled = jnp.tile(jnp.asarray(raw, dtype=jnp.uint8), cap) if len(raw) \
                else jnp.zeros(0, dtype=jnp.uint8)
            data = data.at[: tiled.shape[0]].set(tiled) if len(raw) else data
            offsets = jnp.arange(cap + 1, dtype=jnp.int32) * len(raw)
            return DevVal(self.dtype, data, validity, offsets)
        val = jnp.asarray(self.value, dtype=self.dtype.jnp_dtype)
        return DevVal(self.dtype, jnp.full(cap, val, dtype=self.dtype.jnp_dtype),
                      validity)

    def cpu_eval(self, ctx: CpuEvalCtx) -> CpuVal:
        n = ctx.num_rows
        if self.slot is not None:
            return CpuVal(self.dtype,
                          np.full(n, _params.host(self.slot),
                                  dtype=self.dtype.np_dtype),
                          np.ones(n, dtype=np.bool_))
        if self.value is None:
            validity = np.zeros(n, dtype=np.bool_)
            if self.dtype.is_string:
                return CpuVal(self.dtype, np.array([""] * n, dtype=object), validity)
            return CpuVal(self.dtype, np.zeros(n, dtype=self.dtype.np_dtype), validity)
        validity = np.ones(n, dtype=np.bool_)
        if self.dtype.is_string:
            return CpuVal(self.dtype, np.array([str(self.value)] * n, dtype=object),
                          validity)
        return CpuVal(self.dtype,
                      np.full(n, self.value, dtype=self.dtype.np_dtype), validity)


def infer_literal_type(value: Any) -> T.DataType:
    if value is None:
        return T.NULL
    if isinstance(value, bool):
        return T.BOOLEAN
    if isinstance(value, int):
        return T.INT if -(2 ** 31) <= value < 2 ** 31 else T.LONG
    if isinstance(value, float):
        return T.DOUBLE
    if isinstance(value, (str, bytes)):
        return T.STRING
    raise TypeError(f"cannot infer literal type for {value!r}")


class Alias(Expression):
    # the planner folds beneath an alias: the name stays in the tree
    foldable = False

    def __init__(self, child: Expression, alias_name: str):
        self.children = (child,)
        self.alias_name = alias_name
        self.dtype = child.dtype
        self.nullable = child.nullable

    def with_children(self, children):
        return Alias(children[0], self.alias_name)

    def __repr__(self):
        return f"{self.children[0]!r} AS {self.alias_name}"

    def tpu_eval(self, ctx):
        return self.children[0].tpu_eval(ctx)

    def cpu_eval(self, ctx):
        return self.children[0].cpu_eval(ctx)

    def tpu_supported(self, conf):
        return self.children[0].tpu_supported(conf)


@dataclasses.dataclass
class SortOrder:
    """Sort key spec (GpuSortOrder analogue)."""

    child: Expression
    ascending: bool = True
    nulls_first: Optional[bool] = None  # default: Spark = nulls first iff asc

    def __post_init__(self):
        if self.nulls_first is None:
            self.nulls_first = self.ascending


def output_name(expr: Expression, ordinal: int) -> str:
    if isinstance(expr, Alias):
        return expr.alias_name
    if isinstance(expr, ColumnRef):
        return expr.column
    return f"_c{ordinal}"


def resolve(expr: Expression, schema: T.Schema) -> Expression:
    """Resolve ColumnRefs against a schema, filling in dtype/nullable, and
    re-deriving result types bottom-up."""

    def fix(e: Expression) -> Expression:
        if isinstance(e, ColumnRef):
            f = schema.field(e.column)
            return ColumnRef(e.column, f.dtype, f.nullable)
        return e

    def rebuild(e: Expression) -> Expression:
        new_children = [rebuild(c) for c in e.children]
        e2 = fix(e)
        if new_children and not all(
                a is b for a, b in zip(new_children, e2.children)):
            e2 = e2.with_children(new_children)
        elif e2 is e and not e.children:
            pass
        return e2

    return rebuild(expr)


def bind_references(expr: Expression, schema: T.Schema) -> Expression:
    """Replace resolved ColumnRefs with ordinal BoundRefs."""

    def fn(e: Expression) -> Expression:
        if isinstance(e, ColumnRef):
            f = schema.field(e.column)
            return BoundRef(schema.index_of(e.column), f.dtype, f.nullable)
        return e

    return expr.transform_up(fn)


# ---------------------------------------------------------------------------
# Shared kernel helpers
# ---------------------------------------------------------------------------


def promote_dev(a: DevVal, b: DevVal) -> Tuple[DevVal, DevVal, T.DataType]:
    out = T.promote(a.dtype, b.dtype)
    return cast_dev(a, out), cast_dev(b, out), out


def cast_dev(v: DevVal, to: T.DataType) -> DevVal:
    if v.dtype == to:
        return v
    assert not v.dtype.is_string and not to.is_string
    return DevVal(to, v.data.astype(to.jnp_dtype), v.validity)


def promote_cpu(a: CpuVal, b: CpuVal) -> Tuple[CpuVal, CpuVal, T.DataType]:
    out = T.promote(a.dtype, b.dtype)
    return cast_cpu(a, out), cast_cpu(b, out), out


def cast_cpu(v: CpuVal, to: T.DataType) -> CpuVal:
    if v.dtype == to:
        return v
    return CpuVal(to, v.values.astype(to.np_dtype), v.validity)


class UnaryExpression(Expression):
    def __init__(self, child: Expression):
        self.children = (child,)
        self._resolve_type()

    @property
    def child(self) -> Expression:
        return self.children[0]

    def _resolve_type(self):
        self.dtype = self.child.dtype
        self.nullable = self.child.nullable


class BinaryExpression(Expression):
    def __init__(self, left: Expression, right: Expression):
        self.children = (left, right)
        self._resolve_type()

    @property
    def left(self) -> Expression:
        return self.children[0]

    @property
    def right(self) -> Expression:
        return self.children[1]

    def _resolve_type(self):
        self.dtype = T.promote(self.left.dtype, self.right.dtype)
        self.nullable = self.left.nullable or self.right.nullable
