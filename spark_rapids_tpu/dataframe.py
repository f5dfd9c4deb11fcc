"""Lazy DataFrame frontend over the logical plan.

The reference accelerates Spark's DataFrame/SQL API transparently; this
engine owns the frontend, exposing a pyspark-flavored API that builds
:mod:`spark_rapids_tpu.plan.logical` trees.  ``collect()`` runs the
TpuOverrides planner and executes.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exprs.aggregates import (
    AggregateExpression, AggregateFunction, Average, Count, First, Last, Max,
    Min, Sum, count_star,
)
from spark_rapids_tpu.exprs.base import (
    Alias, ColumnRef, Expression, Literal, SortOrder, output_name, resolve,
)
from spark_rapids_tpu.plan import logical as L


class Column:
    """Expression wrapper with operator sugar (pyspark Column analogue)."""

    def __init__(self, expr: Expression):
        self.expr = expr

    # comparison / arithmetic build expression trees lazily
    def _bin(self, other, cls):
        from spark_rapids_tpu.exprs import arithmetic as A
        from spark_rapids_tpu.exprs import predicates as P
        o = _to_expr(other)
        return Column(cls(self.expr, o))

    def __add__(self, other):
        from spark_rapids_tpu.exprs.arithmetic import Add
        return self._bin(other, Add)

    def __radd__(self, other):
        return Column(_to_expr(other)) + self

    def __sub__(self, other):
        from spark_rapids_tpu.exprs.arithmetic import Subtract
        return self._bin(other, Subtract)

    def __rsub__(self, other):
        return Column(_to_expr(other)) - self

    def __mul__(self, other):
        from spark_rapids_tpu.exprs.arithmetic import Multiply
        return self._bin(other, Multiply)

    def __rmul__(self, other):
        return Column(_to_expr(other)) * self

    def __truediv__(self, other):
        from spark_rapids_tpu.exprs.arithmetic import Divide
        return self._bin(other, Divide)

    def __mod__(self, other):
        from spark_rapids_tpu.exprs.arithmetic import Remainder
        return self._bin(other, Remainder)

    def __neg__(self):
        from spark_rapids_tpu.exprs.arithmetic import UnaryMinus
        return Column(UnaryMinus(self.expr))

    def __eq__(self, other):  # type: ignore[override]
        from spark_rapids_tpu.exprs.predicates import Equals
        return self._bin(other, Equals)

    def __ne__(self, other):  # type: ignore[override]
        from spark_rapids_tpu.exprs.predicates import NotEquals
        return self._bin(other, NotEquals)

    def __lt__(self, other):
        from spark_rapids_tpu.exprs.predicates import LessThan
        return self._bin(other, LessThan)

    def __le__(self, other):
        from spark_rapids_tpu.exprs.predicates import LessThanOrEqual
        return self._bin(other, LessThanOrEqual)

    def __gt__(self, other):
        from spark_rapids_tpu.exprs.predicates import GreaterThan
        return self._bin(other, GreaterThan)

    def __ge__(self, other):
        from spark_rapids_tpu.exprs.predicates import GreaterThanOrEqual
        return self._bin(other, GreaterThanOrEqual)

    def __and__(self, other):
        from spark_rapids_tpu.exprs.predicates import And
        return self._bin(other, And)

    def __or__(self, other):
        from spark_rapids_tpu.exprs.predicates import Or
        return self._bin(other, Or)

    def __invert__(self):
        from spark_rapids_tpu.exprs.predicates import Not
        return Column(Not(self.expr))

    def is_null(self):
        from spark_rapids_tpu.exprs.nullexprs import IsNull
        return Column(IsNull(self.expr))

    def is_not_null(self):
        from spark_rapids_tpu.exprs.nullexprs import IsNotNull
        return Column(IsNotNull(self.expr))

    def isin(self, *values):
        from spark_rapids_tpu.exprs.predicates import In
        vals = values[0] if len(values) == 1 and \
            isinstance(values[0], (list, tuple, set)) else values
        return Column(In(self.expr, list(vals)))

    def cast(self, dtype: Union[str, T.DataType]):
        from spark_rapids_tpu.exprs.cast import Cast
        dt = T.type_from_name(dtype) if isinstance(dtype, str) else dtype
        return Column(Cast(self.expr, dt))

    def alias(self, name: str) -> "Column":
        return Column(Alias(self.expr, name))

    def asc(self, nulls_first: Optional[bool] = None) -> SortOrder:
        return SortOrder(self.expr, True, nulls_first)

    def desc(self, nulls_first: Optional[bool] = None) -> SortOrder:
        return SortOrder(self.expr, False, nulls_first)

    def getItem(self, ordinal: int) -> "Column":
        from spark_rapids_tpu.exprs.misc import GetArrayItem
        return Column(GetArrayItem(self.expr, ordinal))

    def substr(self, start: int, length: int):
        from spark_rapids_tpu.exprs.strings import Substring
        return Column(Substring(self.expr, start, length))

    def startswith(self, prefix: str):
        from spark_rapids_tpu.exprs.strings import StringStartsWith
        return Column(StringStartsWith(self.expr, Literal(prefix)))

    def endswith(self, suffix: str):
        from spark_rapids_tpu.exprs.strings import StringEndsWith
        return Column(StringEndsWith(self.expr, Literal(suffix)))

    def contains(self, needle: str):
        from spark_rapids_tpu.exprs.strings import StringContains
        return Column(StringContains(self.expr, Literal(needle)))

    def like(self, pattern: str):
        from spark_rapids_tpu.exprs.strings import Like
        return Column(Like(self.expr, pattern))

    def between(self, low, high):
        return (self >= low) & (self <= high)

    def __repr__(self):
        return f"Column({self.expr!r})"

    def __hash__(self):
        return id(self)


def _to_expr(v) -> Expression:
    if isinstance(v, Column):
        return v.expr
    if isinstance(v, Expression):
        return v
    return Literal(v)


def _to_order(v) -> SortOrder:
    if isinstance(v, SortOrder):
        return v
    if isinstance(v, str):
        return SortOrder(ColumnRef(v), True)
    if isinstance(v, Column):
        return SortOrder(v.expr, True)
    raise TypeError(f"cannot order by {v!r}")


class DataFrame:
    def __init__(self, plan: L.LogicalPlan, session):
        self.plan = plan
        self.session = session

    # -- schema -------------------------------------------------------------

    @property
    def schema(self) -> T.Schema:
        return self.plan.schema

    @property
    def columns(self) -> List[str]:
        return self.plan.schema.names

    def __getitem__(self, name: str) -> Column:
        f = self.schema.field(name)
        return Column(ColumnRef(name, f.dtype, f.nullable))

    def col(self, name: str) -> Column:
        return self[name]

    # -- transformations ----------------------------------------------------

    def _resolve(self, e: Expression) -> Expression:
        return resolve(e, self.schema)

    def select(self, *cols) -> "DataFrame":
        exprs, names = [], []
        for i, c in enumerate(cols):
            if isinstance(c, str):
                if c == "*":
                    for f in self.schema.fields:
                        exprs.append(ColumnRef(f.name, f.dtype, f.nullable))
                        names.append(f.name)
                    continue
                c = self[c]
            e = self._resolve(_to_expr(c))
            exprs.append(e)
            names.append(output_name(e, i))
        return DataFrame(self._project_node(exprs, names), self.session)

    def _project_node(self, exprs: List[Expression], names: List[str]):
        """Build a Project, hoisting window expressions ANYWHERE in the
        projection trees into Window nodes below it (Spark's
        ExtractWindowExpressions analogue — round 5 generalized from
        top-level-only so e.g. ``x * 100 / sum(x) OVER (...)`` works)."""
        from spark_rapids_tpu.exprs.windows import WindowExpression

        found: Dict[str, Tuple[str, Any]] = {}  # repr(w) -> (hidden, w)

        def hoist(e):
            if isinstance(e, WindowExpression):
                # fingerprint, NOT repr: repr omits frames/offsets/order
                # flags and would merge semantically different windows
                key = e.fingerprint()
                if key not in found:
                    found[key] = (f"__w{len(found)}", e)
                hn, _ = found[key]
                return ColumnRef(hn, e.dtype, True)
            kids = getattr(e, "children", ())
            if not kids:
                return e
            new_kids = [hoist(c) for c in kids]
            if all(a is b for a, b in zip(new_kids, kids)):
                return e
            return e.with_children(new_kids)

        new_exprs = [hoist(e) for e in exprs]
        if not found:
            return L.Project(exprs, names, self.plan)
        # group by (partition, order) spec; one Window node per group
        groups: Dict[str, List[Tuple[str, Any]]] = {}
        for hn, w in found.values():
            key = f"{[repr(p) for p in w.partition_by]}|" \
                  f"{[(repr(o.child), o.ascending, o.nulls_first) for o in w.order_by]}"
            groups.setdefault(key, []).append((hn, w))
        child = self.plan
        for key, items in groups.items():
            child = L.Window([w for _, w in items], [hn for hn, _ in items],
                             child)
        resolved = [resolve(e, child.schema) for e in new_exprs]
        return L.Project(resolved, names, child)

    def with_column(self, name: str, col) -> "DataFrame":
        exprs, names = [], []
        replaced = False
        for f in self.schema.fields:
            if f.name == name:
                exprs.append(self._resolve(_to_expr(col)))
                replaced = True
            else:
                exprs.append(ColumnRef(f.name, f.dtype, f.nullable))
            names.append(f.name)
        if not replaced:
            exprs.append(self._resolve(_to_expr(col)))
            names.append(name)
        return DataFrame(self._project_node(exprs, names), self.session)

    withColumn = with_column

    def with_column_renamed(self, old: str, new: str) -> "DataFrame":
        exprs = [ColumnRef(f.name, f.dtype, f.nullable)
                 for f in self.schema.fields]
        names = [new if f.name == old else f.name
                 for f in self.schema.fields]
        return DataFrame(L.Project(exprs, names, self.plan), self.session)

    def drop(self, *names: str) -> "DataFrame":
        keep = [f for f in self.schema.fields if f.name not in names]
        exprs = [ColumnRef(f.name, f.dtype, f.nullable) for f in keep]
        return DataFrame(L.Project(exprs, [f.name for f in keep], self.plan),
                         self.session)

    def filter(self, condition) -> "DataFrame":
        if isinstance(condition, str):
            from spark_rapids_tpu.sql.parser import parse_expression
            condition = parse_expression(condition)
        e = self._resolve(_to_expr(condition))
        return DataFrame(L.Filter(e, self.plan), self.session)

    where = filter

    def group_by(self, *cols) -> "GroupedData":
        keys, names = [], []
        for i, c in enumerate(cols):
            if isinstance(c, str):
                c = self[c]
            e = self._resolve(_to_expr(c))
            keys.append(e)
            names.append(output_name(e, i))
        return GroupedData(self, keys, names)

    groupBy = group_by

    def _grouping_sets(self, cols, sets) -> "GroupingSetsData":
        gd = self.group_by(*cols)
        n = len(gd.keys)
        for s in sets:
            bad = [i for i in s if not (0 <= i < n)]
            if bad:
                raise ValueError(
                    f"grouping set {s} references key positions {bad}; "
                    f"only {n} grouping keys exist")
        return GroupingSetsData(self, gd.keys, gd.names,
                                [tuple(s) for s in sets])

    def rollup(self, *cols) -> "GroupingSetsData":
        """GROUP BY ROLLUP: hierarchical subtotal grouping sets
        ((k1..kn), (k1..kn-1), ..., ()) over the Expand exec
        (GpuExpandExec's grouping-sets role)."""
        return self._grouping_sets(cols, rollup_sets(len(cols)))

    def cube(self, *cols) -> "GroupingSetsData":
        """GROUP BY CUBE: every subset of the grouping keys."""
        return self._grouping_sets(cols, cube_sets(len(cols)))

    def grouping_sets(self, cols, sets) -> "GroupingSetsData":
        """Explicit GROUPING SETS: ``sets`` is a list of tuples of key
        positions (indices into ``cols``)."""
        return self._grouping_sets(cols, [tuple(s) for s in sets])

    def agg(self, *aggs) -> "DataFrame":
        return GroupedData(self, [], []).agg(*aggs)

    def explode(self, column: str, alias: Optional[str] = None,
                pos: bool = False, outer: bool = False) -> "DataFrame":
        """Explode an array column: one output row per element, other
        columns repeated (GpuGenerateExec analogue).  ``pos=True`` adds the
        element position column; ``outer=True`` keeps empty/NULL arrays as
        a NULL-element row (CPU path)."""
        node = L.Generate(column, alias or "col", pos, outer, self.plan)
        return DataFrame(node, self.session)

    def window_in_pandas(self, partition_by, specs) -> "DataFrame":
        """Whole-partition pandas window columns: specs is
        {out_name: (fn, dtype, col)} with fn(pd.Series) -> scalar,
        broadcast over each partition (GpuWindowInPandasExec analogue).

        ``partition_by`` must name existing columns (project expression
        keys first); ``out_name``s must not collide with input columns.
        """
        names = []
        for c in partition_by:
            if not isinstance(c, str) or c not in self.schema:
                raise TypeError(
                    f"window_in_pandas partition key must be an existing "
                    f"column name, got {c!r}; project expressions first")
            names.append(c)
        for n in specs:
            if n in self.schema:
                raise ValueError(
                    f"window_in_pandas output {n!r} collides with an "
                    "input column")
        keys = [self._resolve(ColumnRef(n)) for n in names]
        win_specs = [(n, fn, dt, col)
                     for n, (fn, dt, col) in specs.items()]
        return DataFrame(
            L.WindowInPandas(keys, names, win_specs, self.plan),
            self.session)

    def map_in_pandas(self, fn, schema) -> "DataFrame":
        """fn(Iterator[pd.DataFrame]) -> Iterator[pd.DataFrame] per
        partition (GpuMapInPandasExec analogue)."""
        return DataFrame(
            L.MapInPandas(fn, _to_schema(schema), self.plan), self.session)

    mapInPandas = map_in_pandas

    def join(self, other: "DataFrame", on=None, how: str = "inner"
             ) -> "DataFrame":
        how = {"leftouter": "left", "left_outer": "left",
               "rightouter": "right", "right_outer": "right",
               "outer": "full", "fullouter": "full", "full_outer": "full",
               "leftsemi": "left_semi", "semi": "left_semi",
               "leftanti": "left_anti", "anti": "left_anti"}.get(how, how)
        lkeys: List[Expression] = []
        rkeys: List[Expression] = []
        condition = None
        if on is None:
            how = "cross" if how == "inner" else how
        elif isinstance(on, str):
            on = [on]
        if isinstance(on, (list, tuple)):
            return self._join_using(other, list(on), how)
        if isinstance(on, Column):
            # equi-join extraction from a boolean expression
            lkeys, rkeys, condition = _extract_join_keys(
                on.expr, self.schema, other.schema)
        right, mapping = _dedupe_right(
            self, other, how in ("left_semi", "left_anti"))
        if mapping:
            def remap(e: Expression) -> Expression:
                if isinstance(e, ColumnRef) and e.column in mapping:
                    return ColumnRef(mapping[e.column], e.dtype, e.nullable)
                return e
            rkeys = [k.transform_up(remap) for k in rkeys]
            if condition is not None:
                # condition may reference either side; remap only names that
                # exist solely on the right
                lnames = set(self.schema.names)
                def remap_cond(e: Expression) -> Expression:
                    if isinstance(e, ColumnRef) and e.column in mapping and \
                            e.column not in lnames:
                        return ColumnRef(mapping[e.column], e.dtype,
                                         e.nullable)
                    return e
                condition = condition.transform_up(remap_cond)
        node = L.Join(self.plan, right.plan, lkeys, rkeys, how, condition)
        return DataFrame(node, self.session)

    def _join_using(self, other: "DataFrame", names: List[str], how: str
                    ) -> "DataFrame":
        """USING-join semantics: one output column per key name (left value;
        right value for right-outer; coalesce for full-outer), then the
        remaining left columns, then the remaining right columns."""
        lkeys = [self._resolve(ColumnRef(n)) for n in names]
        # rename the right key columns so the raw join output has no dups
        ren = {n: f"__rkey_{i}" for i, n in enumerate(names)}
        rexprs, rnames = [], []
        for f in other.schema.fields:
            rexprs.append(ColumnRef(f.name, f.dtype, f.nullable))
            rnames.append(ren.get(f.name, f.name))
        right = DataFrame(L.Project(rexprs, rnames, other.plan),
                          other.session)
        right, _mapping = _dedupe_right(
            self, right, how in ("left_semi", "left_anti"))
        rkeys = [right._resolve(ColumnRef(ren[n])) for n in names]
        node = L.Join(self.plan, right.plan, lkeys, rkeys, how, None)
        joined = DataFrame(node, self.session)
        if how in ("left_semi", "left_anti"):
            return joined
        # final projection: dedupe key columns
        sch = joined.schema
        exprs, out_names = [], []
        for n in names:
            lref = ColumnRef(n)
            rref = ColumnRef(ren[n])
            if how == "right":
                e = resolve(rref, sch)
            elif how == "full":
                from spark_rapids_tpu.exprs.nullexprs import Coalesce
                e = Coalesce(resolve(lref, sch), resolve(rref, sch))
            else:
                e = resolve(lref, sch)
            exprs.append(e)
            out_names.append(n)
        for f in sch.fields:
            if f.name in names or f.name in ren.values():
                continue
            exprs.append(ColumnRef(f.name, f.dtype, f.nullable))
            out_names.append(f.name)
        return DataFrame(L.Project(exprs, out_names, joined.plan),
                         self.session)

    def cross_join(self, other: "DataFrame") -> "DataFrame":
        node = L.Join(self.plan, other.plan, [], [], "cross", None)
        return DataFrame(node, self.session)

    crossJoin = cross_join

    def union(self, other: "DataFrame") -> "DataFrame":
        left, right = self._union_coerce(other)
        return DataFrame(L.Union([left, right]), self.session)

    def _union_coerce(self, other: "DataFrame"):
        """Widen numeric columns to the common type before UNION (Spark's
        WidenSetOperationTypes): `SELECT 0 AS id` against a LONG column
        must not fail the union schema check.  Output names come from the
        left side, per Spark."""
        lf = list(self.plan.schema.fields)
        rf = list(other.plan.schema.fields)
        if len(lf) != len(rf) or \
                all(a.dtype == b.dtype for a, b in zip(lf, rf)):
            return self.plan, other.plan
        try:
            common = [T.promote(a.dtype, b.dtype)
                      for a, b in zip(lf, rf)]
        except TypeError:
            return self.plan, other.plan  # let L.Union raise its check

        def recast(plan, fields):
            from spark_rapids_tpu.exprs.cast import Cast
            exprs = []
            for f, lt, dt in zip(fields, lf, common):
                ref = ColumnRef(f.name, f.dtype, f.nullable)
                exprs.append(ref if f.dtype == dt else Cast(ref, dt))
            return L.Project(exprs, [f.name for f in lf], plan)

        return recast(self.plan, lf), recast(other.plan, rf)

    unionAll = union

    def _set_op(self, other: "DataFrame", keep) -> "DataFrame":
        """INTERSECT/EXCEPT (distinct set semantics) via union + group-by:
        grouping keys already treat NULLs (and NaNs) as equal, which is
        exactly the SQL set-operation equality — and the plan rides the
        hash-aggregate path instead of a null-safe join (Spark plans
        these as left-semi/anti joins; the aggregate form is the
        TPU-friendly equivalent)."""
        from spark_rapids_tpu import functions as F
        if len(self.columns) != len(other.columns):
            raise ValueError(
                f"set operation needs equal column counts: "
                f"{len(self.columns)} vs {len(other.columns)}")
        side = "__setop_side"
        right = other.select(*[
            other[c2].alias(c1)
            for c1, c2 in zip(self.columns, other.columns)])
        # No per-side distinct: the min/max group-by is insensitive to
        # row multiplicity, so one aggregation collapses everything.
        u = (self.with_column(side, F.lit(0))
             .union(right.with_column(side, F.lit(1))))
        g = (u.group_by(*self.columns)
             .agg(F.min(side).alias("__mn"), F.max(side).alias("__mx")))
        mn, mx = g["__mn"], g["__mx"]
        cond = (mn == 0) & (mx == 1) if keep == "both" else \
            (mn == 0) & (mx == 0)
        return g.filter(cond).select(*self.columns)

    def intersect(self, other: "DataFrame") -> "DataFrame":
        """Distinct rows present in BOTH frames (SQL INTERSECT)."""
        return self._set_op(other, "both")

    def subtract(self, other: "DataFrame") -> "DataFrame":
        """Distinct rows of this frame absent from ``other`` (SQL
        EXCEPT; pyspark subtract/exceptAll's distinct sibling)."""
        return self._set_op(other, "left")

    exceptDistinct = subtract

    def describe(self, *cols) -> "DataFrame":
        """count/mean/stddev/min/max summary of numeric columns (pyspark
        DataFrame.describe): a small string-typed frame with a 'summary'
        column.  Computed eagerly (one aggregation pass)."""
        from spark_rapids_tpu import functions as F
        targets = list(cols) or [f.name for f in self.schema.fields
                                 if f.dtype.is_numeric or
                                 f.dtype.is_string]
        data = {"summary": (T.STRING,
                            ["count", "mean", "stddev", "min", "max"])}
        if targets:
            aggs = []
            for c in targets:
                numeric = self.schema.field(c).dtype.is_numeric
                aggs.append(F.count(c).alias(f"c_{c}"))
                if numeric:
                    aggs += [F.avg(c).alias(f"m_{c}"),
                             F.stddev(c).alias(f"s_{c}")]
                aggs += [F.min(c).alias(f"mn_{c}"),
                         F.max(c).alias(f"mx_{c}")]
            row = list(self.agg(*aggs).collect()[0])

            def s(v):
                return None if v is None else str(v)

            i = 0
            for c in targets:
                numeric = self.schema.field(c).dtype.is_numeric
                cnt = row[i]; i += 1
                mean = std = None
                if numeric:
                    mean, std = row[i], row[i + 1]; i += 2
                mn, mx = row[i], row[i + 1]; i += 2
                data[c] = (T.STRING,
                           [str(cnt), s(mean), s(std), s(mn), s(mx)])
        return self.session.create_dataframe(data, num_partitions=1)

    def fillna(self, value, subset: Optional[List[str]] = None
               ) -> "DataFrame":
        """Replace nulls — and NaNs in float columns — with ``value``
        (pyspark DataFrame.na.fill): scalar applied to type-compatible
        columns, or a {col: value} dict.  Fill values cast to the column
        type (2.5 fills an INT column as 2, like pyspark); incompatible
        columns are left untouched."""
        from spark_rapids_tpu import functions as F

        def check(v):
            if isinstance(v, bool) or                     isinstance(v, (int, float, str)):
                return v
            raise TypeError(
                "value should be a float, int, string, bool or dict, "
                f"got {type(v).__name__}")

        if isinstance(value, dict):
            mapping = {c: check(v) for c, v in value.items()}
            for c in mapping:
                self.schema.field(c)  # raises on unknown columns
        else:
            check(value)
            cols = subset or [f.name for f in self.schema.fields]
            for c in cols:
                self.schema.field(c)
            mapping = {c: value for c in cols}
        sel = []
        for f in self.schema.fields:
            v = mapping.get(f.name)
            if v is not None and _fill_compatible(f.dtype, v):
                if f.dtype.is_integral and isinstance(v, float) \
                        and not isinstance(v, bool):
                    v = int(v)  # pyspark casts the value to the column
                filled = F.coalesce(self[f.name], F.lit(v))
                if f.dtype in (T.FLOAT, T.DOUBLE):
                    # pyspark na.fill replaces NaN too
                    from spark_rapids_tpu.exprs.nullexprs import NaNvl
                    filled = F.coalesce(
                        Column(NaNvl(self[f.name].expr,
                                     Literal(float(v), T.DOUBLE))),
                        F.lit(v))
                sel.append(filled.cast(f.dtype).alias(f.name))
            else:
                sel.append(self[f.name].alias(f.name))
        return self.select(*sel)

    def dropna(self, how: str = "any", thresh: Optional[int] = None,
               subset: Optional[List[str]] = None) -> "DataFrame":
        """Drop rows with null/NaN values (pyspark DataFrame.na.drop;
        Spark plans it as a Filter over AtLeastNNonNulls)."""
        from spark_rapids_tpu.exprs.nullexprs import AtLeastNNonNulls
        cols = subset or [f.name for f in self.schema.fields]
        if thresh is None:
            if how not in ("any", "all"):
                raise ValueError(
                    f"how ({how!r}) should be 'any' or 'all'")
            thresh = len(cols) if how == "any" else 1
        e = AtLeastNNonNulls(thresh, *[
            resolve(ColumnRef(c), self.schema) for c in cols])
        return self.filter(Column(e))

    def distinct(self) -> "DataFrame":
        return DataFrame(L.Distinct(self.plan), self.session)

    def drop_duplicates(self, subset: Optional[List[str]] = None):
        if subset is None:
            return self.distinct()
        keys = [self._resolve(ColumnRef(n)) for n in subset]
        aggs = [AggregateExpression(First(
            self._resolve(ColumnRef(f.name))), f.name)
            for f in self.schema.fields if f.name not in subset]
        node = L.Aggregate(keys, list(subset), aggs, self.plan)
        return DataFrame(node, self.session)

    dropDuplicates = drop_duplicates

    def order_by(self, *cols) -> "DataFrame":
        orders = [self._resolve_order(_to_order(c)) for c in cols]
        return DataFrame(L.Sort(orders, True, self.plan), self.session)

    orderBy = order_by
    sort = order_by

    def sort_within_partitions(self, *cols) -> "DataFrame":
        orders = [self._resolve_order(_to_order(c)) for c in cols]
        return DataFrame(L.Sort(orders, False, self.plan), self.session)

    def _resolve_order(self, o: SortOrder) -> SortOrder:
        """Resolve a sort expression against this DataFrame's schema.  A
        bare column name the select list renamed away falls back to its
        alias's output column (SQL allows ORDER BY on the pre-alias
        input name — Spark resolves sort ordering against both the
        projection's output and its input; sorting by the alias output
        is equivalent because the alias is a pure rename)."""
        try:
            return SortOrder(self._resolve(o.child), o.ascending,
                             o.nulls_first)
        except KeyError:
            alias = self._order_alias_for(o.child)
            if alias is None:
                raise
            return SortOrder(self._resolve(ColumnRef(alias)),
                             o.ascending, o.nulls_first)

    def _order_alias_for(self, e: Expression) -> Optional[str]:
        """Output name of a select-list entry that is a pure rename of
        the input column ``e`` references, when this plan is a
        projection (possibly under distinct/limit); None otherwise."""
        if not isinstance(e, ColumnRef):
            return None
        node = self.plan
        while isinstance(node, (L.Distinct, L.Limit)):
            node = node.children[0]
        if not isinstance(node, L.Project):
            return None
        for name, pe in zip(node.names, node.exprs):
            inner = pe
            while isinstance(inner, Alias):
                inner = inner.children[0]
            if isinstance(inner, ColumnRef) and inner.column == e.column:
                return name
        return None

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(L.Limit(n, self.plan), self.session)

    def repartition(self, n: int, *cols) -> "DataFrame":
        if cols:
            keys = [self._resolve(_to_expr(self[c] if isinstance(c, str)
                                           else c)) for c in cols]
            node = L.Repartition("hash", n, keys, self.plan)
        else:
            node = L.Repartition("roundrobin", n, [], self.plan)
        return DataFrame(node, self.session)

    def coalesce(self, n: int) -> "DataFrame":
        return DataFrame(L.Repartition("roundrobin", n, [], self.plan),
                         self.session)

    def sample(self, fraction: float, seed: int = 42) -> "DataFrame":
        return DataFrame(L.Sample(fraction, seed, self.plan), self.session)

    # -- stat functions (pyspark DataFrameStatFunctions surface) ------------

    def crosstab(self, col1: str, col2: str) -> "DataFrame":
        """Pairwise frequency table (pyspark crosstab): one row per col1
        value, one column per col2 value, cells = pair counts (0 when
        absent).  NULL keys render as the string "null" on both axes and
        MERGE with a literal "null" value (one column/row, summed counts
        — pyspark emits a duplicate column name there)."""
        from spark_rapids_tpu import functions as F
        tmp = "__ct_p"
        normalized = self.with_column(
            tmp, F.coalesce(self[col2].cast(T.STRING), F.lit("null")))
        out = (normalized.group_by(col1)
               .pivot(tmp)
               .agg(F.count("*").alias("n")))
        first = out.columns[0]
        sel = [F.coalesce(out[first].cast(T.STRING), F.lit("null"))
               .alias(f"{col1}_{col2}")]
        for c in out.columns[1:]:
            sel.append(F.coalesce(out[c], F.lit(0)).alias(c))
        return out.select(*sel)

    def approx_quantile(self, col_name: str, probabilities, rel_err=0.0
                        ) -> List[float]:
        """Quantiles of a numeric column (pyspark approxQuantile).  The
        engine computes EXACT percentiles (rel_err accepted for API
        compatibility, ignored — exact satisfies any error bound)."""
        from spark_rapids_tpu import functions as F
        if not probabilities:
            return []
        aggs = [F.percentile(col_name, float(p)).alias(f"q{i}")
                for i, p in enumerate(probabilities)]
        row = self.agg(*aggs).collect()[0]
        if all(v is None for v in row):
            return []  # no non-null values (pyspark returns [])
        return list(row)

    approxQuantile = approx_quantile

    def freq_items(self, cols: List[str], support: float = 0.01
                   ) -> "DataFrame":
        """Values occurring in more than ``support`` of rows, one
        array-typed column per input (pyspark freqItems; this engine
        computes exact heavy hitters, a superset guarantee of pyspark's
        sketch)."""
        from spark_rapids_tpu import functions as F
        out_data = {}
        thresh = support * self.count()
        for c in cols:
            # threshold applied engine-side: the driver only receives
            # frequent values, never the full distinct set
            g = (self.group_by(c).agg(F.count("*").alias("__n")))
            vals = [k for k, _ in
                    g.filter(g["__n"] > float(thresh)).collect()]
            f = self.schema.field(c)
            out_data[f"{c}_freqItems"] = (T.ArrayType(f.dtype), [vals])
        return self.session.create_dataframe(out_data, num_partitions=1)

    freqItems = freq_items

    def sample_by(self, col_name: str, fractions: Dict, seed: int = 42
                  ) -> "DataFrame":
        """Stratified sample without replacement (pyspark sampleBy):
        each row kept with its key's fraction; keys absent from
        ``fractions`` are dropped."""
        from spark_rapids_tpu import functions as F
        for k, f in fractions.items():
            if not (0.0 <= float(f) <= 1.0):
                raise ValueError(f"fraction for {k!r} must be in [0, 1]")
        if not fractions:  # pyspark: empty strata -> empty sample
            return self.filter(F.lit(False))
        key = self[col_name]
        frac = None
        for k, f in fractions.items():
            branch = (key.is_null() if k is None else (key == k))
            frac = F.when(branch, float(f)) if frac is None \
                else frac.when(branch, float(f))
        frac_col = frac.otherwise(0.0)
        return self.filter(F.rand(seed) < frac_col)

    sampleBy = sample_by

    # -- actions ------------------------------------------------------------

    def collect(self) -> List[tuple]:
        """The rows; the query's metrics (``session.last_metrics``) hold
        this call's wall as ``collectWallNs``, the row tuples' making
        included (the ``srt/result/rows`` span)."""
        import time
        from spark_rapids_tpu.utils.tracing import span
        t0 = time.monotonic_ns()
        hb, metrics = self.session.execute_with_metrics(self.plan)
        # after the query's scope closed: a span of the profiler alone
        with span("result", "rows", ring=False) as rows:
            cols = [c.to_list() for c in hb.columns]
            out = [tuple(c[i] for c in cols) for i in range(hb.num_rows)]
        # the call's wall holds execute_with_metrics'; what of the rest
        # the rows' span does not cover is under no span
        wall = time.monotonic_ns() - t0
        metrics["unspannedNs"] += \
            wall - metrics["collectWallNs"] - rows.elapsed_ns
        metrics["collectWallNs"] = wall
        return out

    def to_pydict(self) -> Dict[str, List[Any]]:
        return self.session.execute(self.plan).to_pydict()

    def to_pandas(self):
        import pandas as pd
        return pd.DataFrame(self.to_pydict())

    def count(self) -> int:
        node = L.Aggregate([], [], [AggregateExpression(count_star(),
                                                        "count")], self.plan)
        hb = self.session.execute(node)
        return int(hb.columns[0].values[0])

    def show(self, n: int = 20):
        rows = self.limit(n).collect()
        names = self.columns
        widths = [max(len(str(x)) for x in [nm] + [r[i] for r in rows])
                  for i, nm in enumerate(names)]
        line = "+" + "+".join("-" * (w + 2) for w in widths) + "+"
        print(line)
        print("|" + "|".join(f" {nm:<{w}} "
                             for nm, w in zip(names, widths)) + "|")
        print(line)
        for r in rows:
            print("|" + "|".join(f" {str(x):<{w}} "
                                 for x, w in zip(r, widths)) + "|")
        print(line)

    def cache(self) -> "DataFrame":
        """Mark for caching: first execution materializes device batches
        into the spillable-buffer catalog (df.cache() analogue; spills
        device->host->disk under pressure instead of recompute)."""
        if isinstance(self.plan, L.CachedRelation):
            return self
        return DataFrame(L.CachedRelation(self.plan, L.CacheHolder()),
                         self.session)

    persist = cache

    def unpersist(self) -> "DataFrame":
        if isinstance(self.plan, L.CachedRelation):
            self.plan.holder.unpersist()
        return self

    def create_or_replace_temp_view(self, name: str):
        self.session.register_view(name, self)

    createOrReplaceTempView = create_or_replace_temp_view

    def explain(self) -> str:
        s = self.session.explain_plan(self.plan)
        print(s)
        return s

    def write_parquet(self, path: str, mode: str = "error",
                      partition_by=None):
        from spark_rapids_tpu.io.writer import write_dataframe
        return write_dataframe(self, "parquet", path, mode,
                               partition_by=partition_by)

    def write_csv(self, path: str, mode: str = "error"):
        from spark_rapids_tpu.io.writer import write_dataframe
        return write_dataframe(self, "csv", path, mode)

    def write_orc(self, path: str, mode: str = "error"):
        from spark_rapids_tpu.io.writer import write_dataframe
        return write_dataframe(self, "orc", path, mode)

    # -- conveniences -------------------------------------------------------

    def head(self, n: int = 1):
        rows = self.limit(n).collect()
        return rows[0] if n == 1 and rows else rows

    def first(self):
        return self.head(1)

    def take(self, n: int):
        return self.limit(n).collect()

    def is_empty(self) -> bool:
        return not self.limit(1).collect()

    @property
    def dtypes(self):
        return [(f.name, f.dtype.name) for f in self.schema.fields]

    def print_schema(self):
        print("root")
        for f in self.schema.fields:
            null = "true" if f.nullable else "false"
            print(f" |-- {f.name}: {f.dtype} (nullable = {null})")

    printSchema = print_schema


def _dedupe_right(left: "DataFrame", right: "DataFrame", is_semi: bool):
    """Rename right-side columns that collide with left-side names
    (suffix ``_r``) so the joined schema is unambiguous.  Semi/anti joins
    output only the left side, so no rename is needed.

    Returns (right_df, {old_name: new_name})."""
    if is_semi:
        return right, {}
    lnames = set(left.schema.names)
    if not (lnames & set(right.schema.names)):
        return right, {}
    exprs, names, mapping = [], [], {}
    for f in right.schema.fields:
        exprs.append(ColumnRef(f.name, f.dtype, f.nullable))
        nm = f.name
        while nm in lnames:
            nm = nm + "_r"
        if nm != f.name:
            mapping[f.name] = nm
        names.append(nm)
    return DataFrame(L.Project(exprs, names, right.plan),
                     right.session), mapping


def _extract_join_keys(expr: Expression, lschema: T.Schema,
                       rschema: T.Schema):
    """Split a join condition into equi-key pairs + residual condition."""
    from spark_rapids_tpu.exprs.predicates import And, Equals as EqualTo
    lkeys, rkeys, residual = [], [], []

    def visit(e: Expression):
        if isinstance(e, And):
            visit(e.children[0])
            visit(e.children[1])
            return
        if isinstance(e, EqualTo):
            a, b = e.children
            if isinstance(a, ColumnRef) and isinstance(b, ColumnRef):
                if a.column in lschema and b.column in rschema:
                    lkeys.append(resolve(a, lschema))
                    rkeys.append(resolve(b, rschema))
                    return
                if b.column in lschema and a.column in rschema:
                    lkeys.append(resolve(b, lschema))
                    rkeys.append(resolve(a, rschema))
                    return
        residual.append(e)

    visit(expr)
    cond = None
    if residual:
        from spark_rapids_tpu.exprs.predicates import And as AndE
        cond = residual[0]
        for r in residual[1:]:
            cond = AndE(cond, r)
    return lkeys, rkeys, cond


class GroupedData:
    def __init__(self, df: DataFrame, keys: List[Expression],
                 names: List[str]):
        self.df = df
        self.keys = keys
        self.names = names

    @staticmethod
    def _unwrap_agg(a) -> Tuple[AggregateFunction, Optional[str]]:
        """(aggregate fn, alias-or-None) from an agg() argument."""
        if isinstance(a, AggregateExpression):
            return a.fn, a.output_name
        if isinstance(a, Column):
            e, name = a.expr, None
            if isinstance(e, Alias):
                name, e = e.alias_name, e.children[0]
            if isinstance(e, AggregateFunction):
                return e, name
        raise TypeError(f"not an aggregate: {a!r}")

    def agg(self, *aggs) -> DataFrame:
        out: List[AggregateExpression] = []
        for i, a in enumerate(aggs):
            if isinstance(a, AggregateExpression):
                out.append(a)
                continue
            e, name = self._unwrap_agg(a)
            e = _resolve_agg(e, self.df.schema)
            out.append(AggregateExpression(
                e, name or f"{e.name.lower()}_{i}"))
        from spark_rapids_tpu.exprs import aggregates as A
        if any(isinstance(a.fn, A._BinaryStatMarker) for a in out):
            return self._agg_with_binary_stats(out)
        if any(isinstance(a.fn, A.Percentile) for a in out):
            return self._agg_with_percentile(out)
        if any(isinstance(a.fn, A.CountDistinct) for a in out):
            return self._agg_with_distinct(out)
        node = L.Aggregate(self.keys, self.names, out, self.df.plan)
        return DataFrame(node, self.df.session)

    def _agg_with_binary_stats(self, out: List[AggregateExpression]
                               ) -> DataFrame:
        """corr / covar_pop / covar_samp rewrite: no aggregation path
        takes two inputs, so each marker becomes window means over the
        pair-complete rows + a SUM of centered products, with the ratio
        computed in a post-projection (mean-shifted => no large-mean
        cancellation):

            gx  = x when both non-null; gy likewise
            mx  = avg(gx) OVER (keys); my = avg(gy) OVER (keys)
            sp  = SUM((gx-mx)*(gy-my)); n = COUNT(gx)
            covar_pop  = sp/n;  covar_samp = sp/(n-1) (NaN at n=1)
            corr       = sp / sqrt(SUM((gx-mx)^2) * SUM((gy-my)^2))
                         (NaN when a variance is 0); NULL for n=0.
        """
        from spark_rapids_tpu import functions as F
        from spark_rapids_tpu.exprs import aggregates as A

        df = self.df
        key_cols = [Column(k) for k in self.keys]
        wp = F.Window.partition_by(*key_cols)
        final: List = []
        post = {}  # output name -> builder(frame) -> Column
        for i, a in enumerate(out):
            fn = a.fn
            if not isinstance(fn, A._BinaryStatMarker):
                final.append(a)
                continue
            x, y = Column(fn.left), Column(fn.right)
            both = x.is_not_null() & y.is_not_null()
            gxn, gyn = f"__bs_x{i}", f"__bs_y{i}"
            mxn, myn = f"__bs_mx{i}", f"__bs_my{i}"
            df = (df.with_column(gxn, F.when(both, x.cast(T.DOUBLE))
                                 .otherwise(None))
                  .with_column(gyn, F.when(both, y.cast(T.DOUBLE))
                               .otherwise(None)))
            df = (df.with_column(mxn, F.avg(df[gxn]).over(wp))
                  .with_column(myn, F.avg(df[gyn]).over(wp)))
            dx = df[gxn] - df[mxn]
            dy = df[gyn] - df[myn]
            spn, nn = f"__bs_sp{i}", f"__bs_n{i}"
            final.append(AggregateExpression(
                _resolve_agg(A.Sum((dx * dy).expr), df.schema), spn))
            final.append(AggregateExpression(
                _resolve_agg(A.Count(ColumnRef(gxn)), df.schema), nn))
            if isinstance(fn, A.Corr):
                sxn, syn = f"__bs_sx{i}", f"__bs_sy{i}"
                final.append(AggregateExpression(
                    _resolve_agg(A.Sum((dx * dx).expr), df.schema), sxn))
                final.append(AggregateExpression(
                    _resolve_agg(A.Sum((dy * dy).expr), df.schema), syn))

                def mk_corr(g, spn=spn, nn=nn, sxn=sxn, syn=syn):
                    denom = g[sxn] * g[syn]
                    nan = F.lit(float("nan"))
                    return F.when(
                        (g[nn] >= 1) & (denom > 0),
                        g[spn] / F.sqrt(denom)).when(
                        g[nn] >= 1, nan).otherwise(None)
                post[a.output_name] = mk_corr
            elif isinstance(fn, A.CovarSamp):
                def mk_cs(g, spn=spn, nn=nn):
                    nan = F.lit(float("nan"))
                    samp = g[spn] / (g[nn] - 1).cast(T.DOUBLE)
                    return (F.when(g[nn] > 1, samp)
                            .when(g[nn] == 1, nan).otherwise(None))
                post[a.output_name] = mk_cs
            else:
                def mk_cp(g, spn=spn, nn=nn):
                    return (F.when(g[nn] >= 1,
                                   g[spn] / g[nn].cast(T.DOUBLE))
                            .otherwise(None))
                post[a.output_name] = mk_cp
        gd = GroupedData(df, self.keys, self.names)
        grouped = gd.agg(*final)
        sel = []
        for name in self.names:
            sel.append(grouped[name].alias(name))
        for a in out:
            if a.output_name in post:
                sel.append(post[a.output_name](grouped)
                           .alias(a.output_name))
            else:
                sel.append(grouped[a.output_name].alias(a.output_name))
        return grouped.select(*sel)

    def _agg_with_percentile(self, out: List[AggregateExpression]
                             ) -> DataFrame:
        """Exact-percentile rewrite: no fixed-size aggregation buffer can
        hold a percentile's state, so each percentile becomes a
        rank-and-interpolate pipeline (the positional equivalent of
        Spark's sort-based Percentile ImperativeAggregate):

            rn  = row_number() OVER (keys ORDER BY x nulls-last) - 1
            n   = count(x) OVER (keys)            -- non-null count
            pos = p * (n - 1); lo = floor(pos); frac = pos - lo
            w   = (rn == lo) * (1 - frac) + (rn == lo + 1) * frac
            percentile = SUM(x * w) GROUP BY keys

        Rows with NULL x sort after every valid row (rank >= n) and carry
        a NULL weight, so they vanish in the SUM; an all-NULL group sums
        to NULL, matching Spark.  Regular aggregates in the same list ride
        the final aggregation unchanged."""
        from spark_rapids_tpu import functions as F
        from spark_rapids_tpu.exprs import aggregates as A
        from spark_rapids_tpu.exprs.nullexprs import IsNull

        if any(isinstance(a.fn, A.CountDistinct) for a in out):
            raise NotImplementedError(
                "percentile and count_distinct in one aggregation; "
                "split into separate aggregations")
        df = self.df
        key_cols = [Column(k) for k in self.keys]
        contrib_names: dict = {}
        rank_cols: dict = {}  # repr(child) -> (rn, n): percentiles of the
        #                       same child share one sorted window pass
        for i, a in enumerate(out):
            if not isinstance(a.fn, A.Percentile):
                continue
            x = Column(a.fn.child)
            p = a.fn.percentage
            ckey = repr(a.fn.child)
            wp = F.Window.partition_by(*key_cols)
            if ckey not in rank_cols:
                nf, rn, n = (f"__pct_nf{i}", f"__pct_rn{i}", f"__pct_n{i}")
                df = (df.with_column(nf, Column(IsNull(a.fn.child)))
                      .with_column(rn, F.row_number().over(
                          wp.order_by(F.col(nf), x)))
                      .with_column(n, F.count(x).over(wp)))
                rank_cols[ckey] = (rn, n)
            rn, n = rank_cols[ckey]
            cb = f"__pct_c{i}"
            pos = (df[n] - 1).cast(T.DOUBLE) * F.lit(p)
            lo = F.floor(pos)
            frac = pos - lo
            rn0 = (df[rn] - 1).cast(T.DOUBLE)
            # Gate the VALUE, not a product: rows off the interpolation
            # ranks contribute NULL (which SUM skips), so an inf/NaN
            # elsewhere in the group cannot poison the sum via 0 * inf.
            # The lo+1 branch only exists when frac > 0 — Spark's exact
            # percentile never reads past rank lo on integer positions.
            df = df.with_column(
                cb,
                F.when(rn0 == lo, x.cast(T.DOUBLE) * (F.lit(1.0) - frac))
                .when((rn0 == lo + F.lit(1.0)) & (frac > F.lit(0.0)),
                      x.cast(T.DOUBLE) * frac)
                .otherwise(None))
            contrib_names[i] = cb
        gd = GroupedData(df, self.keys, self.names)
        final: List[AggregateExpression] = []
        for i, a in enumerate(out):
            if i in contrib_names:
                final.append(AggregateExpression(
                    _resolve_agg(A.Sum(ColumnRef(contrib_names[i])),
                                 df.schema), a.output_name))
            else:
                final.append(a)
        return gd.agg(*final)

    def _agg_with_distinct(self, out: List[AggregateExpression]
                           ) -> DataFrame:
        """The distinct-aggregate rewrite (Spark RewriteDistinctAggregates):

            Agg(k, [count(DISTINCT v), regular...])
              -> Agg(k, [count(v'), re-agg(regular)],
                     Agg(k + [v], [regular per (k, v)]))

        The inner aggregate dedups (k, v) pairs while computing the regular
        aggregates once per pair; the outer counts the now-unique non-null
        values and re-aggregates the regulars (sum of sums, sum of counts,
        min of mins, avg from sum/count).  Both levels ride the existing
        partial/merge exchange machinery unchanged."""
        from spark_rapids_tpu.exprs import aggregates as A
        from spark_rapids_tpu.exprs.arithmetic import Divide
        from spark_rapids_tpu.exprs.cast import Cast

        dvals = [a.fn.child for a in out if isinstance(a.fn, A.CountDistinct)]
        if any(repr(e) != repr(dvals[0]) for e in dvals[1:]):
            raise NotImplementedError(
                "count_distinct over different expressions in one "
                "aggregation needs the Expand-based rewrite (not yet "
                "implemented); split into separate aggregations")
        dname = "__cd_val"

        inner_aggs: List[AggregateExpression] = []
        plans = []  # one entry per output: how the outer level produces it
        for i, a in enumerate(out):
            fn = a.fn
            if isinstance(fn, A.CountDistinct):
                plans.append(("count_distinct",))
            elif isinstance(fn, A.Average):
                ns, nc = f"__cd_s{i}", f"__cd_c{i}"
                inner_aggs.append(A.AggregateExpression(A.Sum(fn.child), ns))
                inner_aggs.append(A.AggregateExpression(A.Count(fn.child),
                                                        nc))
                plans.append(("avg", ns, nc))
            elif isinstance(fn, (A.Sum, A.Count, A.Min, A.Max, A.First,
                                 A.Last)):
                nm = f"__cd_a{i}"
                inner_aggs.append(A.AggregateExpression(fn, nm))
                plans.append(("reagg", nm, fn))
            else:
                raise NotImplementedError(
                    f"{type(fn).__name__} cannot be combined with "
                    f"count_distinct (no re-aggregation rule)")

        inner = GroupedData(self.df, self.keys + [dvals[0]],
                            self.names + [dname]).agg(*inner_aggs)

        outer_gd = inner.group_by(*self.names)
        o_aggs: List[AggregateExpression] = []
        avg_slots = {}  # output index -> (sum_name, count_name)
        for i, (a, plan) in enumerate(zip(out, plans)):
            if plan[0] == "count_distinct":
                o_aggs.append(A.AggregateExpression(
                    A.Count(inner._resolve(ColumnRef(dname))),
                    a.output_name))
            elif plan[0] == "avg":
                _, ns, nc = plan
                os_, oc = f"__cd_os{i}", f"__cd_oc{i}"
                o_aggs.append(A.AggregateExpression(
                    A.Sum(inner._resolve(ColumnRef(ns))), os_))
                o_aggs.append(A.AggregateExpression(
                    A.Sum(inner._resolve(ColumnRef(nc))), oc))
                avg_slots[i] = (os_, oc)
            else:
                _, nm, fn = plan
                ref = inner._resolve(ColumnRef(nm))
                if isinstance(fn, A.Count):
                    o_fn = A.Sum(ref)  # sum of per-(k,v) counts
                elif isinstance(fn, (A.First, A.Last)):
                    o_fn = type(fn)(ref, fn.ignore_nulls)
                else:
                    o_fn = type(fn)(ref)
                o_aggs.append(A.AggregateExpression(o_fn, a.output_name))
        outer = outer_gd.agg(*o_aggs)

        if not avg_slots:
            return outer
        # Rebuild avg outputs as sum/count and restore column order/names.
        sel: List[Column] = [Column(ColumnRef(n)) for n in self.names]
        for i, a in enumerate(out):
            if i in avg_slots:
                os_, oc = avg_slots[i]
                e = Divide(Cast(ColumnRef(os_), T.DOUBLE),
                           Cast(ColumnRef(oc), T.DOUBLE))
                sel.append(Column(Alias(e, a.output_name)))
            else:
                sel.append(Column(ColumnRef(a.output_name)))
        return outer.select(*sel)

    def count(self) -> DataFrame:
        return self.agg(Column(Alias(count_star(), "count")))

    def pivot(self, col_name: str, values: Optional[List] = None
              ) -> "PivotedData":
        """Pivot a column's values into output columns (pyspark
        GroupedData.pivot).  Rewritten to per-value conditional
        aggregates — agg_fn(CASE WHEN pivot = v THEN child END) — so the
        plan is one ordinary hash aggregation.  Without an explicit
        ``values`` list the distinct values are computed eagerly (as
        pyspark does), capped at 10000."""
        if values is None:
            vals_df = (self.df.select(self.df[col_name].alias("__pv"))
                       .distinct().limit(10_001))
            raw = [r[0] for r in vals_df.collect()]
            if len(raw) > 10_000:
                raise ValueError(
                    "pivot column has more than 10000 distinct values; "
                    "pass an explicit values list")
            # ascending native sort, NULL first (Spark sort order)
            nonnull = sorted(v for v in raw if v is not None)
            values = ([None] if any(v is None for v in raw) else []) \
                + nonnull
        return PivotedData(self.df, self.keys, self.names, col_name,
                           list(values))

    def _simple(self, cls, cols) -> DataFrame:
        targets = cols or [f.name for f in self.df.schema.fields
                           if f.dtype.is_numeric]
        aggs = [Column(Alias(cls(self.df._resolve(ColumnRef(c))),
                             f"{cls.__name__.lower()}({c})"))
                for c in targets]
        return self.agg(*aggs)

    def sum(self, *cols):
        return self._simple(Sum, cols)

    def avg(self, *cols):
        return self._simple(Average, cols)

    mean = avg

    def min(self, *cols):
        return self._simple(Min, cols)

    def max(self, *cols):
        return self._simple(Max, cols)

    # -- pandas execs (GpuFlatMapGroupsInPandasExec family) -----------------

    def apply_in_pandas(self, fn, schema) -> DataFrame:
        """fn(pd.DataFrame) -> pd.DataFrame per group
        (GpuFlatMapGroupsInPandasExec analogue)."""
        schema = _to_schema(schema)
        node = L.FlatMapGroupsInPandas(self.keys, self.names, fn, schema,
                                       self.df.plan)
        return DataFrame(node, self.df.session)

    applyInPandas = apply_in_pandas

    def agg_in_pandas(self, specs) -> DataFrame:
        """specs: {out_name: (fn, dtype, col)} with fn(pd.Series) -> scalar
        (GpuAggregateInPandasExec / GROUPED_AGG pandas_udf analogue)."""
        agg_specs = [(name, fn, dt, col)
                     for name, (fn, dt, col) in specs.items()]
        node = L.AggregateInPandas(self.keys, self.names, agg_specs,
                                   self.df.plan)
        return DataFrame(node, self.df.session)

    def cogroup(self, other: "GroupedData") -> "CoGroupedData":
        return CoGroupedData(self, other)


class CoGroupedData:
    """a.group_by(k).cogroup(b.group_by(k)).apply_in_pandas(fn, schema)
    (GpuFlatMapCoGroupsInPandasExec analogue)."""

    def __init__(self, left: GroupedData, right: GroupedData):
        self.left = left
        self.right = right

    def apply_in_pandas(self, fn, schema) -> DataFrame:
        schema = _to_schema(schema)
        node = L.FlatMapCoGroupsInPandas(
            self.left.keys, self.left.names, self.right.keys,
            self.right.names, fn, schema, self.left.df.plan,
            self.right.df.plan)
        return DataFrame(node, self.left.df.session)

    applyInPandas = apply_in_pandas


def _to_schema(schema) -> T.Schema:
    if isinstance(schema, T.Schema):
        return schema
    return T.Schema(schema)


def _resolve_agg(fn: AggregateFunction, schema: T.Schema
                 ) -> AggregateFunction:
    if len(fn.children) > 1:  # binary-stat markers (corr/covar)
        return fn.with_children(
            [resolve(c, schema) for c in fn.children])
    child = resolve(fn.fn_child if hasattr(fn, "fn_child") else fn.child,
                    schema)
    return fn.with_children([child])


GROUPING_ID_COL = "__grouping_id"
GROUPING_SET_COL = "__gset_idx"


def rollup_sets(n: int):
    """((0..n-1), (0..n-2), ..., ()) — the ROLLUP ladder.  Ordering is
    bit-layout-sensitive: grouping_id bit (n-1-i) marks key i masked."""
    return [tuple(range(k)) for k in range(n, -1, -1)]


def cube_sets(n: int):
    """Every subset of the n grouping keys (CUBE)."""
    import itertools
    return [s for k in range(n, -1, -1)
            for s in itertools.combinations(range(n), k)]


class GroupingSetsData(GroupedData):
    """GroupedData over ROLLUP / CUBE / GROUPING SETS: plans an Expand
    producing one copy of the input per grouping set — original columns
    passed through for the aggregates, key columns masked to NULL where
    grouped-out, plus a grouping_id — then a single hash aggregation
    over (masked keys, grouping_id).  The reference's GpuExpandExec
    exists for exactly this plan shape (GpuExpandExec.scala)."""

    def __init__(self, df: DataFrame, keys: List[Expression],
                 names: List[str], sets: List[tuple]):
        super().__init__(df, keys, names)
        self.sets = sets

    def apply_in_pandas(self, fn, schema) -> DataFrame:
        raise NotImplementedError(
            "apply_in_pandas under rollup/cube/grouping sets is not "
            "supported (Spark has no pandas path for grouping sets "
            "either); aggregate with agg() instead")

    applyInPandas = apply_in_pandas

    def agg_in_pandas(self, specs) -> DataFrame:
        raise NotImplementedError(
            "agg_in_pandas under rollup/cube/grouping sets is not "
            "supported; aggregate with agg() instead")

    def cogroup(self, other) -> "CoGroupedData":
        raise NotImplementedError(
            "cogroup under rollup/cube/grouping sets is not supported")

    def agg(self, *aggs) -> DataFrame:
        from spark_rapids_tpu import functions as F
        from spark_rapids_tpu.exprs.aggregates import GroupingID
        n = len(self.keys)
        child_fields = self.df.schema.fields
        masked = [f"__gset_k{i}" for i in range(n)]
        projections, names = [], None
        # The set INDEX (not the grouping_id) is the hidden group key:
        # duplicate grouping sets must stay separate groups and emit
        # duplicate result rows (Spark semantics, SPARK-33229) — their
        # gids are equal, their indices are not.
        for si, s in enumerate(self.sets):
            gid = sum(1 << (n - 1 - i) for i in range(n) if i not in s)
            proj = [ColumnRef(f.name, f.dtype, f.nullable)
                    for f in child_fields]
            for i, k in enumerate(self.keys):
                proj.append(k if i in s else Literal(None, k.dtype))
            proj.append(Literal(si, T.INT))
            proj.append(Literal(gid, T.INT))
            projections.append(proj)
        names = [f.name for f in child_fields] + masked \
            + [GROUPING_SET_COL, GROUPING_ID_COL]
        expanded = DataFrame(
            L.Expand(projections, names, self.df.plan), self.df.session)
        inner_keys = [ColumnRef(mn, k.dtype, True)
                      for mn, k in zip(masked, self.keys)]
        inner_keys.append(ColumnRef(GROUPING_SET_COL, T.INT, False))
        gd = GroupedData(expanded, inner_keys,
                         self.names + [GROUPING_SET_COL])
        fixed = []
        for a in aggs:
            e = a.expr if isinstance(a, Column) else None
            name = None
            if isinstance(e, Alias):
                name, e = e.alias_name, e.children[0]
            if isinstance(e, GroupingID):
                fixed.append(F.min(Column(
                    ColumnRef(GROUPING_ID_COL, T.INT, False)))
                    .alias(name or "grouping_id"))
            else:
                fixed.append(a)
        out = gd.agg(*fixed)
        return out.select(*[c for c in out.columns
                            if c not in (GROUPING_ID_COL,
                                         GROUPING_SET_COL)])


def _agg_label(e: Expression) -> str:
    """pyspark-style pivot column label for an unaliased aggregate:
    'sum(x)' — falls back to the expression repr for computed args."""
    child = e.children[0] if e.children else None
    if isinstance(child, ColumnRef):
        arg = child.column
    elif isinstance(child, Literal):
        arg = str(child.value)
    else:
        arg = repr(child) if child is not None else ""
    return f"{e.name.lower()}({arg})"


def _fill_compatible(dtype: T.DataType, value) -> bool:
    """pyspark fill rules: numeric fills numeric, string fills string,
    bool fills bool; mismatches leave the column untouched."""
    if isinstance(value, bool):
        return dtype == T.BOOLEAN
    if isinstance(value, (int, float)):
        return dtype.is_numeric
    if isinstance(value, str):
        return dtype.is_string
    return False


class PivotedData(GroupedData):
    """GroupedData after .pivot(): agg() plans Spark's two-phase pivot —
    an inner aggregation grouped by (keys, pivot column), then an outer
    aggregation picking each pivot value's result with
    first(CASE WHEN pivot = v THEN agg END, ignoreNulls=true) (the
    ResolvePivot/PivotFirst shape).  Group/value combinations with no
    rows come out NULL — including for count(), matching pyspark."""

    def __init__(self, df: DataFrame, keys: List[Expression],
                 names: List[str], pivot_col: str, values: List):
        super().__init__(df, keys, names)
        self.pivot_col = pivot_col
        self.values = values

    def agg(self, *aggs) -> DataFrame:
        from spark_rapids_tpu import functions as F
        from spark_rapids_tpu.exprs.aggregates import First
        from spark_rapids_tpu.exprs.nullexprs import IsNull

        norm = [self._unwrap_agg(a) for a in aggs]

        pv_name = "__pivot_val"
        inner_aggs = [Column(Alias(e, f"__pv_a{j}"))
                      for j, (e, _) in enumerate(norm)]
        inner = GroupedData(
            self.df,
            self.keys + [resolve(ColumnRef(self.pivot_col),
                                 self.df.schema)],
            self.names + [pv_name]).agg(*inner_aggs)

        pcol = inner[pv_name]
        outer = []
        for v in self.values:
            cond = Column(IsNull(pcol.expr)) if v is None else (pcol == v)
            vlabel = "null" if v is None else str(v)
            for j, (e, name) in enumerate(norm):
                picked = First(
                    F.when(cond, inner[f"__pv_a{j}"]).otherwise(None)
                    .expr, ignore_nulls=True)
                # pyspark naming: the bare value for a single aggregate,
                # '{value}_{alias-or-fn(arg)}' otherwise
                if len(norm) == 1:
                    out_name = vlabel
                else:
                    label = name or _agg_label(e)
                    out_name = f"{vlabel}_{label}"
                outer.append(Column(Alias(picked, out_name)))
        return (GroupedData(inner, [
            inner._resolve(ColumnRef(n)) for n in self.names],
            list(self.names)).agg(*outer))
