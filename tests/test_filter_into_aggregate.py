"""A filter directly under a keyless aggregate is applied inside the
aggregate's arguments (plan/overrides._filters_into_keyless_aggregates,
PR 27): ``sum(x) … WHERE p`` plans as ``sum(if(p, x, NULL))`` and no row is
compacted.  Pinned here: which plans are rewritten, that the answers are
those of the plan as written, and that the rule changes nothing it was
handed.  CPU backend: plans and answers, never a time.
"""

import pytest

from compare import (
    assert_tpu_cpu_equal, cpu_session, lowered_stage_texts, tpu_session,
)
from spark_rapids_tpu import functions as F
from spark_rapids_tpu import types as T
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan import overrides as O
from spark_rapids_tpu.serve.excache import shared_plan_cache

N = 300
DATA = {
    "i": (T.INT, [None if k % 7 == 0 else k % 41 - 20 for k in range(N)]),
    "x": (T.DOUBLE, [None if k % 11 == 0 else 0.25 * (k % 37) - 3.0
                     for k in range(N)]),
    "d": (T.DATE, [None if k % 13 == 0 else 8000 + k for k in range(N)]),
    "p": (T.INT, [None if k % 5 == 0 else k % 3 for k in range(N)]),
    "s": (T.STRING, [None if k % 9 == 0 else "abc"[k % 3] for k in range(N)]),
    "k": (T.INT, [k % 4 for k in range(N)]),
}


def _frame(s):
    return s.create_dataframe(DATA, num_partitions=3)


def _nodes(plan):
    yield plan
    for c in plan.children:
        yield from _nodes(c)


def _as_written(monkeypatch):
    monkeypatch.setattr(O, "_filters_into_keyless_aggregates",
                        lambda plan: (plan, []))
    shared_plan_cache().clear()


# -- what is rewritten, and that the answers are the same ----------------------

#: a predicate that is NULL on some rows, false on some, true on some
PRED = lambda: (F.col("p") > 0) & (F.col("i") < 15)  # noqa: E731

AGGS = {
    "sum_int": lambda: F.sum("i"),
    "sum_double": lambda: F.sum(F.col("x") * F.col("x")),
    "count_star": lambda: F.count(F.lit(1)),
    "count_column": lambda: F.count("x"),
    "min_date": lambda: F.min("d"),
    "max_double": lambda: F.max("x"),
    "avg_int": lambda: F.avg("i"),
    "avg_double": lambda: F.avg("x"),
}


@pytest.mark.parametrize("case", sorted(AGGS))
def test_keyless_aggregate_over_a_filter_answers_as_written(case, monkeypatch):
    def build(s):
        return _frame(s).filter(PRED()).agg(AGGS[case]().alias("o"))

    plan, absorbed = O._filters_into_keyless_aggregates(
        build(cpu_session()).plan)
    assert len(absorbed) == 1
    assert not [n for n in _nodes(plan) if isinstance(n, L.Filter)]
    agg = next(n for n in _nodes(plan) if isinstance(n, L.Aggregate))
    assert agg.aggs[0].output_name == "o"
    assert agg.aggs[0].fn.child.name == "If"
    rewritten = build(cpu_session()).collect()
    with monkeypatch.context() as m:
        _as_written(m)
        written = build(cpu_session()).collect()
    shared_plan_cache().clear()
    assert len(rewritten) == 1 and repr(rewritten) == repr(written)
    assert_tpu_cpu_equal(build, approx=True, forbid_fallback="Aggregate",
                         confs={
        "spark.rapids.sql.variableFloatAgg.enabled": True})


def test_every_aggregate_at_once_and_the_empty_selection(monkeypatch):
    def build(s, pred):
        return _frame(s).filter(pred).agg(
            *[AGGS[c]().alias(c) for c in sorted(AGGS)])

    for pred in (PRED(), F.col("i") > 1000, F.col("i").is_not_null()):
        rewritten = build(cpu_session(), pred).collect()
        with monkeypatch.context() as m:
            _as_written(m)
            written = build(cpu_session(), pred).collect()
        shared_plan_cache().clear()
        assert repr(rewritten) == repr(written)
    none = build(cpu_session(), F.col("i") > 1000).collect()[0]
    assert none[sorted(AGGS).index("count_star")] == 0
    assert none[sorted(AGGS).index("sum_int")] is None


def test_stacked_filters_are_all_absorbed_and_count_works():
    s = tpu_session()
    df = _frame(s).filter(F.col("p") > 0).filter(F.col("i") < 15)
    plan, absorbed = O._filters_into_keyless_aggregates(
        df.agg(F.sum("i").alias("o")).plan)
    assert len(absorbed) == 2
    assert not [n for n in _nodes(plan) if isinstance(n, L.Filter)]
    want = len([1 for p, i in zip(DATA["p"][1], DATA["i"][1])
                if p is not None and p > 0 and i is not None and i < 15])
    assert df.count() == want
    assert "filter applied inside the keyless aggregate" in s.last_explain
    assert "Filter" not in s.last_explain.split("filter applied")[0]


def test_filter_over_a_file_scan_keeps_its_pushdown(tmp_path):
    s = tpu_session()
    path = str(tmp_path / "t.parquet")
    _frame(s).select("i", "x").write_parquet(path)
    df = s.read.parquet(path).filter(F.col("i") > 3).agg(
        F.sum("x").alias("o"))
    plan = O._pushdown_scan_filters(df.plan)
    plan, absorbed = O._filters_into_keyless_aggregates(plan)
    assert len(absorbed) == 1
    scan = next(n for n in _nodes(plan) if isinstance(n, L.FileScan))
    assert len(scan.pushed_filters) == 1
    want = sum(x for i, x in zip(DATA["i"][1], DATA["x"][1])
               if i is not None and i > 3 and x is not None)
    assert df.collect()[0][0] == pytest.approx(want)


# -- what is left alone --------------------------------------------------------

_flag = F.udf(lambda v: v is not None and v > 0, return_type=T.BOOLEAN)

LEFT_ALONE = {
    "keyed_aggregate": lambda s: _frame(s).filter(PRED()).group_by("k").agg(
        F.sum("i").alias("o")),
    "first": lambda s: _frame(s).filter(PRED()).agg(F.first("i").alias("o")),
    "last_beside_a_sum": lambda s: _frame(s).filter(PRED()).agg(
        F.sum("i").alias("o"), F.last("i").alias("l")),
    "stddev": lambda s: _frame(s).filter(PRED()).agg(
        F.stddev("x").alias("o")),
    "string_argument": lambda s: _frame(s).filter(PRED()).agg(
        F.min("s").alias("o")),
    "rand_in_the_condition": lambda s: _frame(s).filter(
        F.rand(3) < 0.5).agg(F.sum("i").alias("o")),
    "rand_in_the_argument": lambda s: _frame(s).filter(PRED()).agg(
        F.sum(F.rand(3)).alias("o")),
    "udf_in_the_condition": lambda s: _frame(s).filter(
        _flag(F.col("p"))).agg(F.sum("i").alias("o")),
    "project_in_between": lambda s: _frame(s).filter(PRED()).select(
        (F.col("i") + 1).alias("j")).agg(F.sum("j").alias("o")),
    "no_filter": lambda s: _frame(s).agg(F.sum("i").alias("o")),
    "filter_alone": lambda s: _frame(s).filter(PRED()),
}


@pytest.mark.parametrize("case", sorted(LEFT_ALONE))
def test_plan_is_left_alone_and_comes_back_itself(case):
    plan = LEFT_ALONE[case](cpu_session()).plan
    new, absorbed = O._filters_into_keyless_aggregates(plan)
    assert absorbed == [] and new is plan


def test_input_plan_and_its_fingerprint_do_not_change():
    df = _frame(cpu_session()).filter(PRED()).agg(F.sum("i").alias("o")) \
        .select((F.col("o") + 1).alias("o1"))
    plan = df.plan
    before = L.plan_fingerprint(plan)
    agg = plan.children[0]
    held = (agg.aggs, agg.aggs[0], agg.aggs[0].fn, agg.children)
    new, absorbed = O._filters_into_keyless_aggregates(plan)
    assert len(absorbed) == 1 and new is not plan
    assert L.plan_fingerprint(plan) == before != L.plan_fingerprint(new)
    assert plan.children[0] is agg and isinstance(agg.children[0], L.Filter)
    assert all(a is b for a, b in zip(
        held, (agg.aggs, agg.aggs[0], agg.aggs[0].fn, agg.children)))
    # the scan below the absorbed filter is the original object
    assert new.children[0].children[0] is agg.children[0].children[0]


# -- the device program ---------------------------------------------------------


def test_update_program_compacts_no_row(monkeypatch):
    def build(s):
        return _frame(s).filter(PRED()).agg(
            F.sum(F.col("x") * F.col("x")).alias("o"))

    conf = {"spark.rapids.sql.variableFloatAgg.enabled": True}
    _s, texts = lowered_stage_texts(monkeypatch, build, **conf)
    update = texts["stage_TpuHashAggregateExec"]
    assert "e.If" in update
    # (the hash aggregate's slot table keeps its own compaction_indices)
    assert "k.layout.gather_rows" not in update
    with monkeypatch.context() as m:
        _as_written(m)
        _s2, written = lowered_stage_texts(m, build, **conf)
    shared_plan_cache().clear()
    assert "k.layout.gather_rows" in written["stage_TpuHashAggregateExec"]
