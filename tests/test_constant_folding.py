"""Constant folding at plan time (plan/overrides._fold_constants, PR 27):
what folds, what never does, that the rule rewrites nothing it was handed,
and that a folded plan answers exactly what the plan as written answers.

Everything here runs on the CPU backend: it pins plans, names, counts and
answers — never a time.
"""

import os
import re

import pytest

from compare import (
    assert_tpu_cpu_equal, cpu_session, lowered_stage_texts, tpu_session,
)
from spark_rapids_tpu import functions as F
from spark_rapids_tpu import types as T
from spark_rapids_tpu.exprs.base import (
    Alias, ColumnRef, CpuVal, Expression, Literal, UnaryExpression,
)
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan import overrides as O
from spark_rapids_tpu.serve.excache import shared_plan_cache

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DATA = {"a": (T.INT, [1, 2, None, 4, 5, 6]),
        "k": (T.INT, [0, 1, 0, 1, 0, 1]),
        "d": (T.DATE, [8000, 8766, 9130, 9131, None, 10000]),
        "x": (T.DOUBLE, [0.5, -1.25, 2.0, None, 0.0, 7.0])}


def _frame(s):
    return s.create_dataframe(DATA, num_partitions=2)


def _as_written(monkeypatch):
    """Plan without the rule (there is no conf key to turn it off)."""
    monkeypatch.setattr(O, "_fold_constants", lambda plan: (plan, []))
    shared_plan_cache().clear()


def _same_answers_folded_and_as_written(monkeypatch, build, n_folded):
    """The CPU oracle's rows of the folded plan and of the plan as written."""
    s = cpu_session()
    folded = build(s).collect()
    assert s.last_metrics["foldedExprs"] == n_folded
    with monkeypatch.context() as m:
        _as_written(m)
        s2 = cpu_session()
        written = build(s2).collect()
        assert s2.last_metrics["foldedExprs"] == 0
    shared_plan_cache().clear()
    assert repr(folded) == repr(written)     # repr: NaN equals NaN, 1 != 1.0
    return folded


# -- what folds ----------------------------------------------------------------

#: id -> (expression over literals only, value, dtype of the literal it becomes)
WHOLE = {
    "to_date": (lambda: F.to_date(F.lit("1994-01-01")), 8766, T.DATE),
    "cast_as_date": (lambda: F.lit("1995-01-01").cast("date"), 9131, T.DATE),
    "arithmetic": (lambda: F.lit(1) + F.lit(2), 3, T.INT),
    "nested": (lambda: (F.lit(1) + F.lit(2)) * F.lit(3) - F.lit(4), 5, T.INT),
    "long": (lambda: F.lit(1).cast("long") + F.lit(2 ** 40),
             2 ** 40 + 1, T.LONG),
    "double": (lambda: F.sqrt(F.lit(4.0)) / F.lit(8.0), 0.25, T.DOUBLE),
    "float": (lambda: F.lit(1.5).cast("float") * F.lit(2).cast("float"),
              3.0, T.FLOAT),
    "string": (lambda: F.upper(F.concat(F.lit("a"), F.lit("b"))),
               "AB", T.STRING),
    "boolean": (lambda: F.lit(1) < F.lit(2), True, T.BOOLEAN),
    "negative": (lambda: -F.lit(5), -5, T.INT),
    "conditional": (lambda: F.when(F.lit(1) > F.lit(2), F.lit(10))
                    .otherwise(F.lit(20)), 20, T.INT),
    "date_part": (lambda: F.year(F.to_date(F.lit("2020-02-29"))),
                  2020, T.INT),
    "null_date": (lambda: F.to_date(F.lit("bogus")), None, T.DATE),
    "null_arithmetic": (lambda: F.lit(None).cast("int") + F.lit(1),
                        None, T.INT),
    "null_string": (lambda: F.upper(F.lit(None).cast("string")),
                    None, T.STRING),
}


@pytest.mark.parametrize("case", sorted(WHOLE))
def test_constant_tree_becomes_one_typed_literal(case, monkeypatch):
    make, value, dtype = WHOLE[case]
    df = _frame(cpu_session()).select(F.col("a"), make().alias("o"))
    written = df.plan.exprs[1].children[0]
    assert written.dtype == dtype            # the RESOLVED type is what stays
    plan, folded = O._fold_constants(df.plan)
    assert len(folded) == 1                  # the maximal subtree, once
    alias = plan.exprs[1]
    assert isinstance(alias, Alias) and alias.alias_name == "o"
    lit = alias.children[0]
    assert isinstance(lit, Literal)
    assert lit.dtype == dtype and lit.value == value
    assert type(lit.value) in (int, float, str, bool, type(None))
    assert folded[0] == f"{written!r} -> lit({value!r}:{dtype})"
    rows = _same_answers_folded_and_as_written(
        monkeypatch,
        lambda s: _frame(s).select(F.col("a"), make().alias("o")), 1)
    assert [r[1] for r in rows] == [value] * 6


#: id -> (column-dependent expression, folds, repr of the tree afterwards)
MIXED = {
    "right_side_only": (lambda: F.col("a") + (F.lit(1) + F.lit(2)), 1,
                        "Add(`a`, lit(3))"),
    "both_bounds": (lambda: (F.col("d") >= F.to_date(F.lit("1994-01-01")))
                    & (F.col("d") < F.lit("1995-01-01").cast("date")), 2,
                    "And(GreaterThanOrEqual(`d`, lit(8766)), "
                    "LessThan(`d`, lit(9131)))"),
    "inside_a_call": (lambda: F.coalesce(F.col("x"), F.sqrt(F.lit(9.0))), 1,
                      "Coalesce(`x`, lit(3.0))"),
    "literal_operand_stays": (lambda: F.col("a") * F.lit(2) + -F.lit(1), 1,
                              "Add(Multiply(`a`, lit(2)), lit(-1))"),
}


@pytest.mark.parametrize("case", sorted(MIXED))
def test_only_the_constant_part_of_a_mixed_tree_folds(case, monkeypatch):
    make, n, after = MIXED[case]
    df = _frame(cpu_session()).select(make().alias("o"))
    plan, folded = O._fold_constants(df.plan)
    assert len(folded) == n
    assert repr(plan.exprs[0].children[0]) == after
    _same_answers_folded_and_as_written(
        monkeypatch, lambda s: _frame(s).select(make().alias("o")), n)


def _joined(s):
    left = _frame(s)
    right = s.create_dataframe({"k2": (T.INT, [0, 1]),
                                "w": (T.INT, [10, 20])})
    return left.join(right, on=(F.col("k") == F.col("k2"))
                     & (F.col("w") > F.lit(5) + F.lit(6)), how="inner")


#: id -> (plan whose node carries a constant subtree, folds)
NODES = {
    "filter": (lambda s: _frame(s).filter(
        F.col("d") >= F.to_date(F.lit("1994-01-01"))), 1),
    "aggregate_argument": (lambda s: _frame(s).group_by("k").agg(
        F.sum(F.col("a") * (F.lit(2) + F.lit(3))).alias("t")), 1),
    "aggregate_key": (lambda s: _frame(s).group_by(
        (F.col("k") + (F.lit(1) + F.lit(1))).alias("k2")).agg(
            F.count(F.col("a")).alias("n")), 1),
    "join_condition": (_joined, 1),
    "sort_order": (lambda s: _frame(s).order_by(
        (F.col("a") * (F.lit(0) - F.lit(1))).asc(), F.col("k").asc()), 1),
    "window_partition": (lambda s: _frame(s).select(
        F.col("a"), F.row_number().over(
            F.Window.partition_by(F.col("k") + (F.lit(1) + F.lit(1)))
            .order_by("a")).alias("rn")), 1),
    "window_default": (lambda s: _frame(s).select(
        F.col("a"), F.lag("a", 1, F.lit(3) * F.lit(3)).over(
            F.Window.partition_by("k").order_by("a")).alias("p")), 1),
    "expand": (lambda s: _frame(s).rollup("k").agg(
        F.sum(F.col("a") + (F.lit(1) + F.lit(1))).alias("t")), 1),
    "repartition_key": (lambda s: _frame(s).repartition(
        3, F.col("k") + (F.lit(1) + F.lit(1))), 1),
    "below_a_union": (lambda s: _frame(s).select(
        (F.col("a") + (F.lit(1) + F.lit(1))).alias("o")).union(
            _frame(s).select(F.col("k").alias("o"))), 1),
}


@pytest.mark.parametrize("case", sorted(NODES))
def test_every_node_that_carries_expressions_is_walked(case, monkeypatch):
    build, n = NODES[case]
    plan = build(cpu_session()).plan
    new, folded = O._fold_constants(plan)
    assert len(folded) == n, folded
    assert new is not plan and type(new) is type(plan)
    _same_answers_folded_and_as_written(monkeypatch, build, n)


@pytest.mark.parametrize("case", ["to_date", "string", "null_date", "double"])
def test_folded_plan_answers_alike_on_the_device(case):
    make = WHOLE[case][0]
    assert_tpu_cpu_equal(
        lambda s: _frame(s).select(F.col("a"), make().alias("o")),
        ignore_order=False, forbid_fallback="Project")


# -- what never folds ----------------------------------------------------------

_py_add = F.udf(lambda x, y: x + y, return_type=T.INT)
_pd_add = F.pandas_udf(lambda x, y: x + y, return_type=T.LONG)

NEVER = {
    "rand": lambda s: _frame(s).select(F.rand(7).alias("o")),
    "monotonically_increasing_id": lambda s: _frame(s).select(
        F.monotonically_increasing_id().alias("o")),
    "spark_partition_id": lambda s: _frame(s).select(
        (F.spark_partition_id() + F.lit(1)).alias("o")),
    "python_udf_over_literals": lambda s: _frame(s).select(
        _py_add(F.lit(1), F.lit(2)).alias("o")),
    "pandas_udf_over_literals": lambda s: _frame(s).select(
        _pd_add(F.lit(1), F.lit(2)).alias("o")),
    "aggregate_over_a_literal": lambda s: _frame(s).group_by("k").agg(
        F.sum(F.lit(1)).alias("n"), F.count(F.lit(1)).alias("c")),
    "global_aggregate_over_a_literal": lambda s: _frame(s).agg(
        F.max(F.lit(3)).alias("m")),
    "ranking_window_function": lambda s: _frame(s).select(
        F.col("a"), F.row_number().over(F.Window.order_by("a")).alias("rn")),
    "offset_window_function_over_a_literal": lambda s: _frame(s).select(
        F.lag(F.lit(1)).over(F.Window.partition_by("k").order_by("a"))
        .alias("p")),
    "window_aggregate_over_a_literal": lambda s: _frame(s).select(
        F.sum(F.lit(1)).over(F.Window.partition_by("k")).alias("n")),
    "array_constructor": lambda s: _frame(s).select(
        F.array(F.lit(1), F.lit(2)).alias("o")),
    "over_an_array_constructor": lambda s: _frame(s).select(
        F.size(F.array(F.lit(1), F.lit(2))).alias("o")),
    "column_reference": lambda s: _frame(s).select(
        (F.col("a") + 1).alias("o")).filter(F.col("o") > 2),
    "bare_literal": lambda s: _frame(s).select(F.lit(1).alias("o")),
}


@pytest.mark.parametrize("case", sorted(NEVER))
def test_not_foldable_and_the_plan_comes_back_itself(case):
    plan = NEVER[case](cpu_session()).plan
    new, folded = O._fold_constants(plan)
    assert folded == []
    assert new is plan                       # the identical object


class _Boom(UnaryExpression):
    def cpu_eval(self, ctx):
        raise ZeroDivisionError("no oracle for this one")


class _WrongType(UnaryExpression):
    def _resolve_type(self):
        self.dtype, self.nullable = T.DATE, True

    def cpu_eval(self, ctx):
        v = self.child.cpu_eval(ctx)
        return CpuVal(T.INT, v.values, v.validity)


@pytest.mark.parametrize("cls", [_Boom, _WrongType])
def test_oracle_that_raises_or_answers_in_another_type_is_left_alone(cls):
    scan = _frame(cpu_session()).plan
    e = cls(Literal(1))
    assert e.foldable
    plan = L.Project([Alias(e, "o")], ["o"], scan)
    new, folded = O._fold_constants(plan)
    assert new is plan and folded == []


def test_leaf_that_is_no_literal_is_not_foldable():
    class Leaf(Expression):
        pass
    assert not Leaf().foldable and not ColumnRef("a", T.INT).foldable
    assert Literal(1).foldable and not Alias(Literal(1), "o").foldable


# -- the rule rewrites nothing it was handed -----------------------------------


def test_input_plan_and_its_fingerprint_do_not_change():
    s = cpu_session()
    df = _frame(s).filter(F.col("d") >= F.to_date(F.lit("1994-01-01"))) \
        .select((F.col("a") + (F.lit(1) + F.lit(2))).alias("o"))
    plan = df.plan
    before = L.plan_fingerprint(plan)
    held = (plan.exprs, plan.exprs[0], plan.children,
            plan.children[0].condition)
    new, folded = O._fold_constants(plan)
    assert len(folded) == 2 and new is not plan
    assert L.plan_fingerprint(plan) == before
    assert L.plan_fingerprint(new) != before
    assert (plan.exprs, plan.exprs[0], plan.children,
            plan.children[0].condition) == held
    assert all(a is b for a, b in zip(
        held, (plan.exprs, plan.exprs[0], plan.children,
               plan.children[0].condition)))
    # the untouched subtree below the rewritten nodes is the original object
    assert new.children[0].children[0] is plan.children[0].children[0]
    assert new.names is plan.names


def test_output_column_names_are_what_they_were(monkeypatch):
    def build(s):
        s.register_view("t", _frame(s))
        return s.sql("SELECT 1 + 1, to_date('2020-02-29') AS d, "
                     "a + (2 * 3) AS b FROM t")

    s = tpu_session()
    df = build(s)
    names = df.columns
    phys = s.plan_physical(df.plan)
    assert phys.folded_exprs == 3
    assert [f.name for f in phys.output_schema.fields] == names
    assert names[1:] == ["d", "b"]
    with monkeypatch.context() as m:
        _as_written(m)
        s2 = tpu_session()
        df2 = build(s2)
        assert df2.columns == names
        assert [f.name for f in
                s2.plan_physical(df2.plan).output_schema.fields] == names
    shared_plan_cache().clear()
    assert df.collect()[0][:2] == (2, 18321)


# -- the counter, the span, the explain line -----------------------------------


def test_counter_on_miss_and_hit_of_the_plan_cache_span_and_explain():
    s = tpu_session()
    df = _frame(s).filter(
        (F.col("d") >= F.to_date(F.lit("1994-01-01")))
        & (F.col("d") < F.to_date(F.lit("1995-01-01"))))
    stats0 = shared_plan_cache().stats()
    rows = df.collect()
    assert sorted(r[2] for r in rows) == [8766, 9130]
    assert s.last_metrics["foldedExprs"] == 2
    fold_spans = [e for e in s.query_history()[-1].events
                  if e.kind == "span" and (e.site, e.name) == ("plan", "fold")]
    assert len(fold_spans) == 1 and fold_spans[0].payload == {"folded": 2}
    assert s.last_explain.splitlines()[-1] == (
        "folded 2: ToDate(lit('1994-01-01')) -> lit(8766:date), "
        "ToDate(lit('1995-01-01')) -> lit(9131:date)")
    assert df.explain().count("folded 2: ") == 1
    df.collect()                                  # the hit: no planning
    stats1 = shared_plan_cache().stats()
    assert stats1["plan_cache_hits"] == stats0["plan_cache_hits"] + 1
    assert stats1["plan_cache_misses"] == stats0["plan_cache_misses"] + 1
    assert s.last_metrics["foldedExprs"] == 2
    assert not [e for e in s.query_history()[-1].events
                if (e.site, e.name) == ("plan", "fold")]
    assert sum(s.last_metrics["critpath"].values()) == \
        s.last_metrics["queryWallNs"]
    # nothing to fold: no line, the counter reads 0
    _frame(s).filter(F.col("a") > 1).collect()
    assert s.last_metrics["foldedExprs"] == 0
    assert "folded" not in s.last_explain


# -- the benchmark's texts, at small scale -------------------------------------

ROWS = 4096


def _lineitem(s):
    """One 4,096-row batch with the columns Q6 and Q1 read."""
    i = list(range(ROWS))
    s.register_view("lineitem", s.create_dataframe({
        "l_shipdate": (T.DATE, [8400 + (7 * k) % 2200 for k in i]),
        "l_discount": (T.DOUBLE, [0.01 * (k % 11) for k in i]),
        "l_quantity": (T.DOUBLE, [float(1 + k % 50) for k in i]),
        "l_extendedprice": (T.DOUBLE, [900.0 + (k % 977) for k in i]),
        "l_tax": (T.DOUBLE, [0.01 * (k % 9) for k in i]),
        "l_returnflag": (T.STRING, ["ANR"[k % 3] for k in i]),
        "l_linestatus": (T.STRING, ["OF"[k % 2] for k in i])}))


def _benchmark_text(q):
    with open(os.path.join(REPO_ROOT, "benchmark", "queries", q,
                           "query.sql")) as f:
        return f.read()


BENCH_CONF = {"spark.rapids.sql.variableFloatAgg.enabled": True,
              "spark.rapids.sql.test.enabled": True}


def _update_stage(texts):
    """The stage program that holds the update aggregate and the WHERE
    clause under it: Q1's own stage, and Q6's one fused program (a keyless
    update is inlined into its consumer's stage)."""
    (update,) = [t for name, t in texts.items() if name.startswith("stage_")
                 and ("e.And" in t or "e._Comparison" in t)]   # the scopes
    return update


@pytest.mark.parametrize("q,n", [("q6", 2), ("q1", 1)])
def test_benchmark_query_plans_without_to_date(q, n, monkeypatch):
    def build(s):
        _lineitem(s)
        return s.sql(_benchmark_text(q))

    s, texts = lowered_stage_texts(monkeypatch, build, **BENCH_CONF)
    assert s.last_metrics["foldedExprs"] == n
    assert [ln for ln in s.last_explain.splitlines()
            if ln.startswith(f"folded {n}: ToDate(")]
    plan, folded = O._fold_constants(build(s).plan)
    assert len(folded) == n
    left = []

    def walk(node):
        for v in vars(node).values():
            for e in (v if isinstance(v, list) else [v]):
                if isinstance(e, Expression):
                    left.extend(e.collect(lambda x: x.name == "ToDate"))
        for c in node.children:
            walk(c)

    walk(plan)
    assert left == []
    update = _update_stage(texts)
    assert "e.ToDate" not in update
    # the date string tiled once per row: u8[rows x 10]
    assert not re.search(rf"tensor<{ROWS * 10}xui8>", update)
    # Q6's filter is applied inside its keyless sum: no row is compacted
    assert ("k.layout.gather_rows" in update) == (q == "q1")
    # the control: as written, the same text does hold both
    with monkeypatch.context() as m:
        _as_written(m)
        _s2, written = lowered_stage_texts(m, build, **BENCH_CONF)
    shared_plan_cache().clear()
    update = _update_stage(written)
    assert "e.ToDate" in update
    assert re.search(rf"tensor<{ROWS * 10}xui8>", update)


def test_cast_literal_as_date_in_a_filter_plans_on_the_device(monkeypatch):
    def build(s):
        s.register_view("t", _frame(s))
        return s.sql("SELECT d FROM t WHERE d >= cast('1994-01-01' as date)")

    s = tpu_session(**{"spark.rapids.sql.test.enabled": True})
    assert sorted(build(s).collect()) == \
        [(8766,), (9130,), (9131,), (10000,)]
    assert s.last_metrics["foldedExprs"] == 1
    assert "cannot run on TPU because expression" not in s.last_explain
    # as written it planned a CPU filter, which the test mode refuses
    with monkeypatch.context() as m:
        _as_written(m)
        s2 = tpu_session(**{"spark.rapids.sql.test.enabled": True})
        with pytest.raises(Exception, match="(?i)tpu|cpu"):
            build(s2).collect()
        assert "!Filter cannot run on TPU" in s2.last_explain
    shared_plan_cache().clear()
