"""End-to-end DataFrame tests: TPU plan vs CPU plan results
(the HashAggregatesSuite / joins / sort / limit suites' pattern)."""

import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.dataframe import Column
from spark_rapids_tpu.exprs.aggregates import (
    Average, Count, Max, Min, Sum, count_star,
)
from spark_rapids_tpu.exprs.base import Alias, ColumnRef

from compare import assert_tpu_cpu_equal, tpu_session

DATA = {
    "a": (T.INT, [1, 2, 2, 3, None, 5, 5, 5, 0, -7]),
    "b": (T.LONG, [10, 20, None, 40, 50, 60, 70, None, 90, 100]),
    "f": (T.DOUBLE, [0.5, None, 2.5, -3.5, 4.5, 5.5, float("nan"), 7.5,
                     8.5, -0.0]),
    "s": (T.STRING, ["apple", "bee", None, "cat", "dog", "bee", "eel",
                     "fox", "", "gnu"]),
}


def make_df(s, data=None, parts=3):
    return s.create_dataframe(data or DATA, num_partitions=parts)


def test_select_project_arith():
    assert_tpu_cpu_equal(
        lambda s: make_df(s).select(
            "a",
            (Column(ColumnRef("a")) + 1).alias("a1"),
            (Column(ColumnRef("b")) * 2).alias("b2"),
            (Column(ColumnRef("f")) / 2.0).alias("fh"),
        ), approx=True)


def test_filter():
    assert_tpu_cpu_equal(
        lambda s: make_df(s).filter(Column(ColumnRef("a")) > 1))


def test_filter_string_and_null():
    def q(s):
        df = make_df(s)
        return df.filter(df["s"].is_not_null() & (df["s"] != "bee"))
    assert_tpu_cpu_equal(q)


def test_groupby_agg():
    def q(s):
        df = make_df(s)
        return df.group_by("a").agg(
            Column(Alias(Sum(ColumnRef("b")), "sum_b")),
            Column(Alias(Count(ColumnRef("b")), "cnt_b")),
            Column(Alias(Min(ColumnRef("b")), "min_b")),
            Column(Alias(Max(ColumnRef("b")), "max_b")),
            Column(Alias(Average(ColumnRef("b")), "avg_b")),
        )
    assert_tpu_cpu_equal(q, approx=True)


def test_groupby_string_key():
    def q(s):
        df = make_df(s)
        return df.group_by("s").agg(
            Column(Alias(Count(ColumnRef("a")), "cnt")),
            Column(Alias(Sum(ColumnRef("a")), "sum_a")),
        )
    assert_tpu_cpu_equal(q)


def test_global_reduction():
    def q(s):
        df = make_df(s)
        return df.agg(Column(Alias(Sum(ColumnRef("b")), "sum_b")),
                      Column(Alias(count_star(), "n")))
    assert_tpu_cpu_equal(q)


def test_global_reduction_empty_input():
    def q(s):
        df = make_df(s)
        return df.filter(Column(ColumnRef("a")) > 1000).agg(
            Column(Alias(Sum(ColumnRef("b")), "sum_b")),
            Column(Alias(count_star(), "n")))
    assert_tpu_cpu_equal(q)


def test_orderby():
    def q(s):
        df = make_df(s)
        return df.order_by(df["a"].desc(), df["s"].asc())
    assert_tpu_cpu_equal(q, ignore_order=False)


def test_orderby_expression_key():
    def q(s):
        df = make_df(s)
        return df.order_by((df["a"] * -1).asc(), "b")
    assert_tpu_cpu_equal(q, ignore_order=False)


def test_limit():
    # limit is non-deterministic across partitions in general; use sorted
    def q(s):
        df = make_df(s)
        return df.order_by("b").limit(4)
    assert_tpu_cpu_equal(q, ignore_order=False)


def test_union():
    def q(s):
        df = make_df(s)
        return df.union(df)
    assert_tpu_cpu_equal(q)


def test_distinct():
    def q(s):
        df = make_df(s).select("a", "s")
        return df.distinct()
    assert_tpu_cpu_equal(q)


def test_join_inner():
    other = {
        "a": (T.INT, [2, 3, 5, 5, 8, None]),
        "v": (T.STRING, ["x", "y", "z", "w", "q", "n"]),
    }

    def q(s):
        df = make_df(s)
        d2 = s.create_dataframe(other, num_partitions=2)
        return df.join(d2, on="a", how="inner")
    assert_tpu_cpu_equal(q)


@pytest.mark.parametrize("bc", ["broadcast", "shuffle"])
@pytest.mark.parametrize("how", ["left", "right", "full", "left_semi",
                                 "left_anti"])
def test_join_types(how, bc):
    other = {
        "a": (T.INT, [2, 3, 5, 5, 8, None]),
        "v": (T.STRING, ["x", "y", "z", "w", "q", "n"]),
    }

    def q(s):
        df = make_df(s)
        d2 = s.create_dataframe(other, num_partitions=2)
        return df.join(d2, on="a", how=how)
    confs = {} if bc == "broadcast" else \
        {"spark.sql.autoBroadcastJoinThreshold": -1}
    assert_tpu_cpu_equal(q, confs=confs)


def test_broadcast_hint_forces_broadcast_plan():
    from spark_rapids_tpu import functions as F
    s = tpu_session()
    df = make_df(s)
    d2 = s.create_dataframe({"a": (T.INT, [1, 2]),
                             "w": (T.INT, [10, 20])})
    out = df.join(F.broadcast(d2), on="a", how="inner")
    out.collect()
    assert "TpuBroadcastHashJoin" in s.last_physical_plan.tree_string()


def test_join_multi_key_expr_cond():
    other = {
        "k": (T.INT, [1, 2, 2, 5]),
        "s2": (T.STRING, ["apple", "bee", "bee", "cat"]),
        "w": (T.LONG, [7, 8, 9, 10]),
    }

    def q(s):
        df = make_df(s)
        d2 = s.create_dataframe(other, num_partitions=2)
        return df.join(d2, on=(df["a"] == d2["k"]) & (df["s"] == d2["s2"]),
                       how="inner")
    assert_tpu_cpu_equal(q)


def test_cross_join():
    small = {"x": (T.INT, [1, 2])}

    def q(s):
        df = make_df(s).select("a")
        d2 = s.create_dataframe(small)
        return df.cross_join(d2)
    assert_tpu_cpu_equal(q)


def test_with_column_cast():
    def q(s):
        df = make_df(s)
        return df.with_column("al", df["a"].cast("bigint")) \
                 .with_column("fs", df["f"].cast("float"))
    assert_tpu_cpu_equal(q, approx=True)


def test_repartition_roundtrip():
    def q(s):
        df = make_df(s)
        return df.repartition(5, "a").select("a", "b")
    assert_tpu_cpu_equal(q)


def test_count_action():
    s = tpu_session()
    df = make_df(s)
    assert df.count() == 10


def test_string_functions():
    def q(s):
        df = make_df(s)
        return df.select(
            df["s"].substr(1, 2).alias("pre"),
            df["s"].contains("e").alias("has_e"),
            df["s"].startswith("b").alias("is_b"),
        )
    assert_tpu_cpu_equal(q)


def test_explain_and_fallback():
    # rand() has no deterministic TPU parity; just check explain shows TPU ops
    s = tpu_session()
    df = make_df(s).filter(Column(ColumnRef("a")) > 1).select("a")
    out = s.explain_plan(df.plan)
    assert "will run on TPU" in out


def test_enforce_tpu_mode():
    s = tpu_session(**{"spark.rapids.sql.test.enabled": True})
    df = make_df(s).filter(Column(ColumnRef("a")) > 1).select("a", "s")
    # should not raise: everything lands on TPU
    df.collect()


def test_large_batch_shrink_path():
    """Exercise shrink_to_fit + the sorted exchange split (big sparse
    batches; regression: shrink_to_fit import bug only hit at scale)."""
    import numpy as np
    from spark_rapids_tpu import functions as F
    n = 40_000
    rng = np.random.RandomState(1)
    data = {
        "k": (T.INT, rng.randint(0, 50, n)),
        "v": (T.LONG, rng.randint(0, 1000, n)),
        "s": (T.STRING, [f"s{int(x)}" for x in rng.randint(0, 50, n)]),
    }

    def q(s):
        df = s.create_dataframe(data, num_partitions=3)
        return df.filter(df["v"] < 40) \
                 .group_by("k", "s").agg(F.sum("v").alias("sv"),
                                         F.count("v").alias("cv"))
    assert_tpu_cpu_equal(q)


@pytest.mark.parametrize("bc", ["broadcast", "shuffle"])
@pytest.mark.parametrize("how", ["inner", "left", "right", "full",
                                 "left_semi", "left_anti"])
def test_join_types_with_residual_condition(how, bc):
    """Residual conditions gate matches INSIDE the join for every type
    (GpuHashJoin.scala:265-271): a row whose matches all fail the
    condition must come out null-padded / kept / dropped per the type."""
    other = {
        "k": (T.INT, [2, 3, 5, 5, 8, None]),
        "w": (T.LONG, [15, 100, 55, 9, 70, 1]),
        "v": (T.STRING, ["x", "y", "z", "w", "q", "n"]),
    }

    def q(s):
        df = make_df(s)
        d2 = s.create_dataframe(other, num_partitions=2)
        return df.join(d2, on=(df["a"] == d2["k"]) & (df["b"] < d2["w"]),
                       how=how)
    confs = {} if bc == "broadcast" else \
        {"spark.sql.autoBroadcastJoinThreshold": -1}
    assert_tpu_cpu_equal(q, confs=confs)


@pytest.mark.parametrize("how", ["inner", "left", "right", "full",
                                 "left_semi", "left_anti"])
def test_nested_loop_join_types(how):
    """Non-equi-only conditions plan as a nested-loop join; all types run
    on TPU (GpuBroadcastNestedLoopJoinExec.scala:305 parity)."""
    other = {
        "k": (T.INT, [1, 3, 6, None]),
        "v": (T.STRING, ["p", "q", "r", "s"]),
    }

    def q(s):
        df = make_df(s).select("a", "s")
        d2 = s.create_dataframe(other)
        return df.join(d2, on=df["a"] < d2["k"], how=how)
    assert_tpu_cpu_equal(q)


def test_nested_loop_join_runs_on_tpu():
    s = tpu_session()
    df = make_df(s).select("a")
    d2 = s.create_dataframe({"k": (T.INT, [1, 3])})
    out = df.join(d2, on=df["a"] < d2["k"], how="left")
    out.collect()
    assert "TpuNestedLoopJoin(left)" in s.last_physical_plan.tree_string()


# ---------------------------------------------------------------------------
# Non-collapsed exchange matrix: collapseLocal=false exercises the device
# partition-split path (exchange.py device split + spillable outputs) that
# the mesh path builds on.
# ---------------------------------------------------------------------------

NO_COLLAPSE = {"spark.rapids.sql.tpu.exchange.collapseLocal": False}


@pytest.mark.parametrize("case", ["groupby", "groupby_str", "sort", "join",
                                  "window_less", "limit", "distinct"])
def test_non_collapsed_exchange_matrix(case):
    def q(s):
        df = make_df(s)
        if case == "groupby":
            return df.group_by("a").agg(
                Column(Alias(Sum(ColumnRef("b")), "sum_b")),
                Column(Alias(Count(ColumnRef("b")), "cnt")))
        if case == "groupby_str":
            return df.group_by("s").agg(
                Column(Alias(Sum(ColumnRef("a")), "sum_a")))
        if case == "sort":
            return df.order_by(df["a"].desc(), df["s"].asc())
        if case == "join":
            d2 = s.create_dataframe({
                "a": (T.INT, [2, 3, 5, None]),
                "w": (T.LONG, [1, 2, 3, 4])}, num_partitions=2)
            return df.join(d2, on="a", how="left")
        if case == "window_less":
            return df.select("a", "b").distinct()
        if case == "limit":
            return df.order_by("b").limit(4)
        return df.select("s").distinct()

    confs = dict(NO_COLLAPSE)
    if case == "join":
        confs["spark.sql.autoBroadcastJoinThreshold"] = -1
    assert_tpu_cpu_equal(q, confs=confs,
                         ignore_order=case not in ("sort", "limit"))


def test_metrics_surfaced():
    """session.last_metrics reports pipeline program counts, op metrics and
    catalog spill counters (GpuExec.scala:27-56 metric surface role)."""
    s = tpu_session()
    df = make_df(s)
    df.group_by("a").agg(Column(Alias(Sum(ColumnRef("b")), "x"))).collect()
    m = s.last_metrics
    assert m.get("pipeline", {}).get("programs", 0) >= 1, m
    assert "memory" in m and "spilled_to_host" in m["memory"], m
    # the iterator path (a nested-loop join at the root inlines nothing)
    # surfaces per-op collect metrics
    df.cross_join(s.create_dataframe({"one": [1]})).collect()
    m2 = s.last_metrics
    assert "pipeline" not in m2, m2
    assert m2.get("collect", {}).get("batches", 0) >= 1, m2


def test_canonical_plan_reuse():
    """Structurally identical plans (rebuilt DataFrames, repeated count())
    share one physical plan and its compiled kernels — the plan
    canonicalization / reuse role."""
    s = tpu_session()
    df = make_df(s)
    g1 = df.group_by("a").sum("b")
    g2 = df.group_by("a").sum("b")
    assert s.plan_physical(g1.plan) is s.plan_physical(g2.plan)
    # different conf state -> different physical plan
    s.conf.set("spark.rapids.sql.exec.Aggregate", False)
    assert s.plan_physical(g1.plan) is not None
    s.conf.set("spark.rapids.sql.exec.Aggregate", True)
    # different plan shape -> miss
    g3 = df.group_by("a").sum("b").filter(Column(ColumnRef("a")) > 1)
    assert s.plan_physical(g3.plan) is not s.plan_physical(g1.plan)


# ---------------------------------------------------------------------------
# count(DISTINCT x): the two-level distinct-aggregate rewrite
# ---------------------------------------------------------------------------


def _cd_df(s, n=200):
    import numpy as np
    rng = np.random.RandomState(7)
    cats = ["a", "b", "c", None, "dd"]
    return s.create_dataframe({
        "k": (T.INT, rng.randint(0, 4, n)),
        "v": (T.STRING, [cats[i] for i in rng.randint(0, len(cats), n)]),
        "w": (T.LONG, [None if i % 11 == 0 else int(x) for i, x in
                       enumerate(rng.randint(0, 100, n))]),
    }, num_partitions=3)


def test_count_distinct_alone():
    from spark_rapids_tpu import functions as F
    assert_tpu_cpu_equal(
        lambda s: _cd_df(s).group_by("k").agg(
            F.count_distinct("v").alias("cd")))


def test_count_distinct_with_other_aggs():
    from spark_rapids_tpu import functions as F
    assert_tpu_cpu_equal(
        lambda s: _cd_df(s).group_by("k").agg(
            F.count_distinct("v").alias("cd"),
            F.sum("w").alias("sw"),
            F.count("w").alias("cw"),
            F.min("w").alias("mn"),
            F.max("w").alias("mx")))


def test_count_distinct_with_avg():
    from spark_rapids_tpu import functions as F
    assert_tpu_cpu_equal(
        lambda s: _cd_df(s).group_by("k").agg(
            F.avg("w").alias("aw"),
            F.count_distinct("v").alias("cd")),
        approx=True)


def test_count_distinct_global():
    from spark_rapids_tpu import functions as F
    assert_tpu_cpu_equal(
        lambda s: _cd_df(s).agg(F.count_distinct("v").alias("cd"),
                                F.sum("w").alias("sw")))


def test_count_distinct_int_col_twice():
    from spark_rapids_tpu import functions as F
    assert_tpu_cpu_equal(
        lambda s: _cd_df(s).group_by("v").agg(
            F.count_distinct("w").alias("cd1"),
            F.count_distinct(F.col("w")).alias("cd2")))


def test_count_distinct_mixed_columns_rejected():
    import pytest
    from spark_rapids_tpu import functions as F
    from tests.compare import tpu_session
    s = tpu_session()
    df = _cd_df(s)
    with pytest.raises(NotImplementedError):
        df.group_by("k").agg(F.count_distinct("v"),
                             F.count_distinct("w"))
