"""Logical plan nodes built by the DataFrame frontend.

The reference accelerates Spark's physical plans; here the frontend owns the
whole stack, so this logical layer plays Catalyst's role: a typed operator
tree that the physical planner lowers to CPU/TPU execs.  Node set mirrors the
exec inventory of SURVEY.md section 2.5.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exprs.aggregates import AggregateExpression
from spark_rapids_tpu.exprs.base import Expression, SortOrder


class LogicalPlan:
    children: Tuple["LogicalPlan", ...] = ()

    @property
    def schema(self) -> T.Schema:
        raise NotImplementedError

    @property
    def name(self) -> str:
        return type(self).__name__

    def tree_string(self, depth: int = 0) -> str:
        out = "  " * depth + self.describe() + "\n"
        for c in self.children:
            out += c.tree_string(depth + 1)
        return out

    def describe(self) -> str:
        return self.name


class InMemoryScan(LogicalPlan):
    """Scan over host-resident batches (createDataFrame / test input)."""

    def __init__(self, batches: List, schema: T.Schema,
                 num_partitions: int = 1):
        self.batches = batches  # List[HostBatch]
        self._schema = schema
        self.num_partitions = num_partitions
        self.children = ()

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"InMemoryScan({self._schema})"


class FileScan(LogicalPlan):
    """File-source scan (parquet/csv/orc); decode happens host-side, staged
    to HBM by the physical scan exec (GpuParquetScan analogue)."""

    def __init__(self, fmt: str, paths: List[str], schema: T.Schema,
                 options: Optional[Dict[str, Any]] = None,
                 pushed_filters: Optional[List[Expression]] = None,
                 partitions=None):
        self.fmt = fmt
        self.paths = paths
        self._schema = schema
        self.options = options or {}
        self.pushed_filters = pushed_filters or []
        # Hive-layout partition columns: (partition_schema,
        # {file: [values...]}) — appended as constants per file by the scan
        self.partitions = partitions
        self.children = ()

    @property
    def schema(self):
        return self._schema

    def describe(self):
        extra = f", pushed={len(self.pushed_filters)}" \
            if self.pushed_filters else ""
        return f"FileScan({self.fmt}, {len(self.paths)} files{extra})"


class Range(LogicalPlan):
    """spark.range() analogue (GpuRangeExec)."""

    def __init__(self, start: int, end: int, step: int = 1,
                 num_partitions: int = 1, name: str = "id"):
        self.start, self.end, self.step = start, end, step
        self.num_partitions = num_partitions
        self.col_name = name
        self.children = ()

    @property
    def schema(self):
        return T.Schema([(self.col_name, T.LONG)])

    def describe(self):
        return f"Range({self.start}, {self.end}, {self.step})"


class Project(LogicalPlan):
    def __init__(self, exprs: List[Expression], names: List[str],
                 child: LogicalPlan):
        self.exprs = exprs
        self.names = names
        self.children = (child,)

    @property
    def schema(self):
        return T.Schema([
            T.Field(n, e.dtype, e.nullable)
            for n, e in zip(self.names, self.exprs)
        ])

    def describe(self):
        return f"Project({', '.join(self.names)})"


class Filter(LogicalPlan):
    def __init__(self, condition: Expression, child: LogicalPlan):
        self.condition = condition
        self.children = (child,)

    @property
    def schema(self):
        return self.children[0].schema

    def describe(self):
        return f"Filter({self.condition!r})"


class Aggregate(LogicalPlan):
    """Groupby aggregation; empty ``keys`` = global reduction."""

    def __init__(self, keys: List[Expression], key_names: List[str],
                 aggs: List[AggregateExpression], child: LogicalPlan):
        self.keys = keys
        self.key_names = key_names
        self.aggs = aggs
        self.children = (child,)

    @property
    def schema(self):
        fields = [T.Field(n, e.dtype, e.nullable)
                  for n, e in zip(self.key_names, self.keys)]
        fields += [T.Field(a.output_name, a.dtype, True) for a in self.aggs]
        return T.Schema(fields)

    def describe(self):
        return (f"Aggregate(keys=[{', '.join(self.key_names)}], "
                f"aggs=[{', '.join(a.output_name for a in self.aggs)}])")


class Sort(LogicalPlan):
    def __init__(self, orders: List[SortOrder], is_global: bool,
                 child: LogicalPlan):
        self.orders = orders
        self.is_global = is_global
        self.children = (child,)

    @property
    def schema(self):
        return self.children[0].schema

    def describe(self):
        g = "global" if self.is_global else "local"
        return f"Sort({g}, {len(self.orders)} keys)"


class Join(LogicalPlan):
    JOIN_TYPES = ("inner", "left", "right", "full", "left_semi", "left_anti",
                  "cross")

    def __init__(self, left: LogicalPlan, right: LogicalPlan,
                 left_keys: List[Expression], right_keys: List[Expression],
                 how: str, condition: Optional[Expression] = None):
        assert how in self.JOIN_TYPES, how
        self.left_keys = left_keys
        self.right_keys = right_keys
        self.how = how
        self.condition = condition
        self.children = (left, right)

    @property
    def schema(self):
        left, right = self.children
        if self.how in ("left_semi", "left_anti"):
            return left.schema
        lfields = list(left.schema.fields)
        rfields = list(right.schema.fields)
        if self.how in ("left", "full"):
            rfields = [T.Field(f.name, f.dtype, True) for f in rfields]
        if self.how in ("right", "full"):
            lfields = [T.Field(f.name, f.dtype, True) for f in lfields]
        return T.Schema(lfields + rfields)

    def describe(self):
        return f"Join({self.how})"


class Union(LogicalPlan):
    def __init__(self, children: Sequence[LogicalPlan]):
        self.children = tuple(children)
        s0 = self.children[0].schema
        for c in self.children[1:]:
            assert [f.dtype for f in c.schema.fields] == \
                [f.dtype for f in s0.fields], "union schema mismatch"

    @property
    def schema(self):
        return self.children[0].schema


class Limit(LogicalPlan):
    def __init__(self, n: int, child: LogicalPlan):
        self.n = n
        self.children = (child,)

    @property
    def schema(self):
        return self.children[0].schema

    def describe(self):
        return f"Limit({self.n})"


class Distinct(LogicalPlan):
    def __init__(self, child: LogicalPlan):
        self.children = (child,)

    @property
    def schema(self):
        return self.children[0].schema


class Expand(LogicalPlan):
    """Grouping-sets expansion: each projection list emits one output row set
    (GpuExpandExec analogue)."""

    def __init__(self, projections: List[List[Expression]], names: List[str],
                 child: LogicalPlan):
        self.projections = projections
        self.names = names
        self.children = (child,)

    @property
    def schema(self):
        p0 = self.projections[0]
        return T.Schema([
            T.Field(n, e.dtype, True) for n, e in zip(self.names, p0)
        ])


class Generate(LogicalPlan):
    """explode/posexplode over a per-row repetition (GpuGenerateExec
    analogue).  Round 1: explode of a literal-bounded sequence column model;
    array types land with nested-type support."""

    def __init__(self, generator, output_names: List[str], child: LogicalPlan):
        self.generator = generator
        self.output_names = output_names
        self.children = (child,)

    @property
    def schema(self):
        base = list(self.children[0].schema.fields)
        gen = [T.Field(n, t, True)
               for n, t in zip(self.output_names, self.generator.output_types)]
        return T.Schema(base + gen)


class Window(LogicalPlan):
    def __init__(self, window_exprs, output_names: List[str],
                 child: LogicalPlan):
        self.window_exprs = window_exprs
        self.output_names = output_names
        self.children = (child,)

    @property
    def schema(self):
        base = list(self.children[0].schema.fields)
        extra = [T.Field(n, w.dtype, True)
                 for n, w in zip(self.output_names, self.window_exprs)]
        return T.Schema(base + extra)


class Repartition(LogicalPlan):
    """Explicit exchange: mode in {hash, roundrobin, range, single}."""

    def __init__(self, mode: str, num_partitions: int,
                 keys: List[Expression], child: LogicalPlan,
                 orders: Optional[List[SortOrder]] = None):
        self.mode = mode
        self.num_partitions = num_partitions
        self.keys = keys
        self.orders = orders
        self.children = (child,)

    @property
    def schema(self):
        return self.children[0].schema

    def describe(self):
        return f"Repartition({self.mode}, {self.num_partitions})"


class Sample(LogicalPlan):
    def __init__(self, fraction: float, seed: int, child: LogicalPlan):
        self.fraction = fraction
        self.seed = seed
        self.children = (child,)

    @property
    def schema(self):
        return self.children[0].schema


class WriteFile(LogicalPlan):
    """Data-writing command (GpuDataWritingCommandExec analogue)."""

    def __init__(self, fmt: str, path: str, mode: str, options: Dict[str, Any],
                 child: LogicalPlan):
        self.fmt = fmt
        self.path = path
        self.mode = mode
        self.options = options
        self.children = (child,)

    @property
    def schema(self):
        return T.Schema([])


class CacheHolder:
    """Materialized cache state shared by all DataFrames over a cached plan
    (the GPU df.cache() analogue; reference: ParquetCachedBatchSerializer,
    shims/spark310 — here cached batches live as catalog-registered
    spillable device batches, so they flow device->host->disk under
    memory pressure instead of being re-encoded as parquet blobs)."""

    def __init__(self):
        self.partitions = None  # List[List[SpillableBatch]] once filled

    @property
    def is_materialized(self) -> bool:
        return self.partitions is not None

    def unpersist(self):
        if self.partitions:
            for part in self.partitions:
                for h in part:
                    h.close()
        self.partitions = None


class CachedRelation(LogicalPlan):
    def __init__(self, child: LogicalPlan, holder: CacheHolder):
        self.children = (child,)
        self.holder = holder

    @property
    def schema(self):
        return self.children[0].schema

    def describe(self):
        state = "materialized" if self.holder.is_materialized else "lazy"
        return f"CachedRelation({state})"


class BroadcastHint(LogicalPlan):
    """Marks a subtree as broadcast-preferred (functions.broadcast(df))."""

    def __init__(self, child: LogicalPlan):
        self.children = (child,)

    @property
    def schema(self):
        return self.children[0].schema


class MapInPandas(LogicalPlan):
    """mapInPandas(fn, schema): fn(Iterator[pd.DataFrame]) ->
    Iterator[pd.DataFrame] per partition (GpuMapInPandasExec analogue)."""

    def __init__(self, fn, schema: T.Schema, child: LogicalPlan):
        self.fn = fn
        self._schema = schema
        self.children = (child,)

    @property
    def schema(self):
        return self._schema


class FlatMapGroupsInPandas(LogicalPlan):
    """groupBy(...).applyInPandas(fn, schema)
    (GpuFlatMapGroupsInPandasExec analogue)."""

    def __init__(self, keys: List[Expression], key_names: List[str], fn,
                 schema: T.Schema, child: LogicalPlan):
        self.keys = keys
        self.key_names = key_names
        self.fn = fn
        self._schema = schema
        self.children = (child,)

    @property
    def schema(self):
        return self._schema


class FlatMapCoGroupsInPandas(LogicalPlan):
    """a.groupBy(k).cogroup(b.groupBy(k)).applyInPandas(fn, schema)
    (GpuFlatMapCoGroupsInPandasExec analogue)."""

    def __init__(self, left_keys, left_names, right_keys, right_names, fn,
                 schema: T.Schema, left: LogicalPlan, right: LogicalPlan):
        self.left_keys = left_keys
        self.left_names = left_names
        self.right_keys = right_keys
        self.right_names = right_names
        self.fn = fn
        self._schema = schema
        self.children = (left, right)

    @property
    def schema(self):
        return self._schema


class AggregateInPandas(LogicalPlan):
    """groupBy(...).agg_in_pandas({out: (fn, dtype, col)}): one output row
    per group, values computed by python over each group's pandas Series
    (GpuAggregateInPandasExec analogue)."""

    def __init__(self, keys: List[Expression], key_names: List[str],
                 agg_specs, child: LogicalPlan):
        self.keys = keys
        self.key_names = key_names
        self.agg_specs = agg_specs  # list of (out_name, fn, dtype, col)
        self.children = (child,)

    @property
    def schema(self):
        fields = [T.Field(n, e.dtype, e.nullable)
                  for n, e in zip(self.key_names, self.keys)]
        fields += [T.Field(n, dt, True) for n, _fn, dt, _c in self.agg_specs]
        return T.Schema(fields)


def plan_fingerprint(plan: LogicalPlan, pinned: Optional[List] = None,
                     baked: Optional[List] = None) -> str:
    """Canonical identity of a logical plan for physical-plan reuse.

    Built from node types + their scalar/expression attributes; objects
    without stable reprs (user fns, batch lists) key by python identity —
    collisions are impossible (identity reprs are unique), only *misses*
    for structurally equal but distinct-object inputs, which is safe
    while the object lives: a holder of the fingerprint keeps alive what
    ``pinned`` collects (every object keyed by identity).

    A literal is encoded by its value, a *lifted* one (:func:`plan_shape`)
    by slot and type alone; ``baked`` collects the former.
    """
    from spark_rapids_tpu.exprs.base import Expression, Literal, SortOrder

    def enc(v):
        if isinstance(v, AggregateExpression):
            return f"AE({v.output_name},{enc(v.fn)})"
        if isinstance(v, Expression):
            if isinstance(v, Literal):
                if v.slot is not None:
                    return f"Literal?{v.slot}:{v.dtype}"
                if baked is not None:
                    baked.append(v)
            # NOT repr(): Expression.__repr__ prints only class + children,
            # omitting scalar attributes (ConcatWs.sep, Lag.offset,
            # window frames...) — encode every non-child attribute too so
            # structurally different expressions never collide.
            parts = [type(v).__name__]
            for k, a in sorted(vars(v).items()):
                if k == "children":
                    continue
                parts.append(f"{k}={enc(a)}")
            kids = ",".join(enc(c) for c in v.children)
            return f"{'|'.join(parts)}({kids})"
        if isinstance(v, SortOrder):
            return (f"SO({enc(v.child)},{v.ascending},{v.nulls_first})")
        if isinstance(v, (str, int, float, bool, type(None))):
            return repr(v)
        if isinstance(v, T.Schema):
            return str(v)
        if isinstance(v, T.DataType):
            return str(v)
        if isinstance(v, (list, tuple)):
            return "[" + ",".join(enc(x) for x in v) + "]"
        if isinstance(v, dict):
            return "{" + ",".join(
                f"{enc(k)}:{enc(x)}" for k, x in sorted(
                    v.items(), key=lambda kv: str(kv[0]))) + "}"
        if pinned is not None:
            pinned.append(v)
        return f"id:{id(v):x}"  # fns, batch lists, cache holders...

    attrs = []
    for k, v in sorted(vars(plan).items()):
        if k in ("children", "_schema"):
            continue
        attrs.append(f"{k}={enc(v)}")
    kids = ",".join(plan_fingerprint(c, pinned, baked) for c in plan.children)
    return f"{plan.name}({';'.join(attrs)})[{kids}]"


class PlanShape:
    """What :func:`plan_shape` splits a logical plan into: the ``plan``
    with every liftable literal replaced by a slotted one, its
    ``fingerprint`` (the key one physical plan and one set of executables
    are shared under), the lifted ``values`` and their ``dtypes`` slot by
    slot, how many literals stayed ``baked``, and the objects the
    fingerprint names by identity (``pinned``)."""

    __slots__ = ("plan", "fingerprint", "values", "dtypes", "baked",
                 "pinned")

    def __init__(self, plan, fingerprint, values, dtypes, baked, pinned):
        self.plan = plan
        self.fingerprint = fingerprint
        self.values = values
        self.dtypes = dtypes
        self.baked = baked
        self.pinned = pinned


@functools.lru_cache(maxsize=None)
def _lift_through() -> frozenset:
    """Expression classes (exact types) a literal may sit under, at any
    depth, and still be lifted: each evaluates its children through
    ``tpu_eval``/``cpu_eval`` alone — no read of a child's ``value`` when it
    is built, tagged, planned or traced — and rebuilds itself whole from
    ``with_children``.  Everything else (``round`` scales, ``substring``
    positions, LIKE patterns, array needles, CASE WHEN, window frames,
    cast targets as attributes) keeps the literals below it baked."""
    from spark_rapids_tpu.exprs import aggregates as A
    from spark_rapids_tpu.exprs import arithmetic as AR
    from spark_rapids_tpu.exprs import nullexprs as N
    from spark_rapids_tpu.exprs import predicates as P
    from spark_rapids_tpu.exprs.base import Alias
    from spark_rapids_tpu.exprs.cast import Cast
    from spark_rapids_tpu.exprs.conditional import If
    return frozenset({
        P.Equals, P.NotEquals, P.LessThan, P.LessThanOrEqual,
        P.GreaterThan, P.GreaterThanOrEqual, P.EqualNullSafe, P.And, P.Or,
        P.Not, P.In,
        AR.Add, AR.Subtract, AR.Multiply, AR.Divide, AR.IntegralDivide,
        AR.Remainder, AR.Pmod, AR.UnaryMinus, AR.Abs, AR.UnaryPositive,
        N.IsNull, N.IsNotNull, N.IsNan, N.Coalesce, N.NaNvl,
        If, Cast, Alias,
        A.Sum, A.Count, A.Min, A.Max, A.Average,
    })


#: fixed-width types a lifted literal may have (a string's byte length is
#: part of a program's shape; NULL has no value to bind)
_LIFTABLE_TYPES = (T.BOOLEAN, T.BYTE, T.SHORT, T.INT, T.LONG, T.FLOAT,
                   T.DOUBLE, T.DATE, T.TIMESTAMP)


def plan_shape(plan: LogicalPlan, lift: bool = True) -> PlanShape:
    """Split ``plan`` into a shape and the values of its liftable literals
    (the plan cache's key and what an execution binds).

    A literal is lifted when it is non-NULL, of a fixed-width type, held in
    a ``Filter`` condition, a ``Project`` expression or an ``Aggregate``
    argument, below nothing but :func:`_lift_through` classes, and neither
    the whole expression nor the direct argument of an aggregate function
    (``count(1)``).  Everywhere else it reaches, or may reach, planning or
    tracing by its value — LIMIT and sample counts, join and sort
    expressions, grouping keys (they become exchange partitionings),
    window specs, filters pushed into a file scan — and stays baked: part
    of the fingerprint, compiled as a constant.  The type is part of the
    shape: ``24`` and ``24.5`` are two shapes.  One ``Literal`` object in
    two liftable places takes one slot.  ``lift=False`` lifts nothing: the
    shape is the plan's value fingerprint.

    Non-mutating: ``plan`` is untouched and unchanged subtrees are shared.
    """
    import copy

    from spark_rapids_tpu.exprs.aggregates import AggregateFunction
    from spark_rapids_tpu.exprs.base import Literal
    through = _lift_through()
    values: List = []
    dtypes: List = []
    slotted: Dict[int, Literal] = {}

    def slotted_copy(lit: Literal) -> Literal:
        if lit.slot is not None or lit.dtype not in _LIFTABLE_TYPES or \
                type(lit.value) not in (bool, int, float):
            return lit
        new = slotted.get(id(lit))
        if new is None:
            new = slotted[id(lit)] = Literal(lit.value, lit.dtype,
                                             slot=len(values))
            values.append(lit.value)
            dtypes.append(lit.dtype)
        return new

    def expr(e):
        if type(e) not in through:
            return e
        keep_literals = isinstance(e, AggregateFunction)
        kids = [c if type(c) is Literal and keep_literals
                else slotted_copy(c) if type(c) is Literal else expr(c)
                for c in e.children]
        if all(n is o for n, o in zip(kids, e.children)):
            return e
        return e.with_children(kids)

    def exprs(es):
        new = [expr(e) for e in es]
        return es if all(n is o for n, o in zip(new, es)) else new

    def node(n: LogicalPlan) -> LogicalPlan:
        children = tuple(node(c) for c in n.children)
        new = {}
        if type(n) is Filter:
            new["condition"] = expr(n.condition)
        elif type(n) is Project:
            new["exprs"] = exprs(n.exprs)
        elif type(n) is Aggregate:
            fns = exprs([a.fn for a in n.aggs])
            new["aggs"] = n.aggs if all(
                f is a.fn for a, f in zip(n.aggs, fns)) else [
                AggregateExpression(f, a.output_name)
                for a, f in zip(n.aggs, fns)]
        if all(v is getattr(n, k) for k, v in new.items()) and all(
                a is b for a, b in zip(children, n.children)):
            return n
        clone = copy.copy(n)
        vars(clone).update(new)
        clone.children = children
        return clone

    shaped = node(plan) if lift else plan
    pinned: List = []
    baked: List = []
    fingerprint = plan_fingerprint(shaped, pinned, baked)
    return PlanShape(shaped, fingerprint, tuple(values), tuple(dtypes),
                     len(baked), pinned)


class Generate(LogicalPlan):
    """Generator expansion: explode/posexplode of an array column
    (GpuGenerateExec analogue, GpuGenerateExec.scala).  Output = the
    child's other columns repeated per element (+ optional ``pos``) + the
    element column.  ``outer`` keeps empty/NULL-array rows with a NULL
    element (CPU path)."""

    def __init__(self, column: str, alias: str, pos: bool, outer: bool,
                 child: LogicalPlan):
        self.column = column
        self.alias = alias
        self.pos = pos
        self.outer = outer
        self.children = (child,)

    @property
    def schema(self):
        child = self.children[0].schema
        arr = child.field(self.column)
        assert arr.dtype.is_array, f"explode needs an array, got {arr.dtype}"
        fields = [f for f in child.fields if f.name != self.column]
        if self.pos:
            fields.append(T.Field("pos", T.INT, False))
        fields.append(T.Field(self.alias, arr.dtype.element, self.outer))
        return T.Schema(fields)

    def describe(self):
        kind = "posexplode" if self.pos else "explode"
        return f"Generate({kind}({self.column}) as {self.alias})"


class WindowInPandas(LogicalPlan):
    """Whole-partition-frame pandas window: each output row carries
    fn(partition pd.Series) broadcast over its partition
    (GpuWindowInPandasExec analogue — unbounded preceding/following frame,
    the shape pyspark's GROUPED_AGG pandas_udf over a Window takes)."""

    def __init__(self, keys: List[Expression], key_names: List[str],
                 win_specs, child: LogicalPlan):
        self.keys = keys
        self.key_names = key_names
        self.win_specs = win_specs  # list of (out_name, fn, dtype, col)
        self.children = (child,)

    @property
    def schema(self):
        child = self.children[0].schema
        fields = list(child.fields)
        fields += [T.Field(n, dt, True)
                   for n, _fn, dt, _c in self.win_specs]
        return T.Schema(fields)
