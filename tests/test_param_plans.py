"""One physical plan and one set of executables per query SHAPE, literals
bound at execution (plan/logical.plan_shape, serve/excache, utils/params,
PR 28): TPC-H Q6 as QGEN sends it — the same text with other substitution
parameters every time — answers every set right, compiles once, and never
hands one set another's values; what must stay baked makes a new shape.

Everything here runs on the CPU backend at a small size, through
``session.sql`` under ``spark.rapids.sql.test.enabled``: it pins answers,
counts and plans — never a time.  The reference is numpy float64 over
columns generated from a seed, independent of the engine.
"""

import datetime
import threading

import numpy as np
import pytest

from compare import cpu_session, tpu_session
from spark_rapids_tpu import functions as F
from spark_rapids_tpu import types as T
from spark_rapids_tpu.exprs.base import ColumnRef, Literal
from spark_rapids_tpu.exprs.predicates import GreaterThan
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.logical import plan_fingerprint, plan_shape
from spark_rapids_tpu.plan.overrides import TpuOverrides
from spark_rapids_tpu.serve import excache
from spark_rapids_tpu.serve.excache import shared_plan_cache
from spark_rapids_tpu.utils import params

ROWS = 20_000
#: the eight sets of benchmark/configs/tpch_sf1_qgen.json
SETS = [(1994, 0.06, 24), (1993, 0.02, 25), (1995, 0.09, 24),
        (1996, 0.04, 25), (1997, 0.07, 24), (1993, 0.08, 24),
        (1995, 0.03, 25), (1996, 0.05, 24)]
Q6 = """SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= to_date('{y}-01-01')
  AND l_shipdate < to_date('{y1}-01-01')
  AND l_discount BETWEEN {lo} AND {hi}
  AND l_quantity < {q}"""
Q1 = """SELECT l_returnflag, l_linestatus,
       sum(l_quantity) AS sum_qty,
       sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
       avg(l_discount) AS avg_disc,
       count(*) AS count_order
FROM lineitem
WHERE l_shipdate <= to_date('{day}')
GROUP BY l_returnflag, l_linestatus
ORDER BY l_returnflag, l_linestatus"""


def _days(y, m=1, d=1):
    return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days


def _columns(seed=7, rows=ROWS):
    """lineitem's Q6/Q1 columns with dbgen's domains (clause 4.2.3)."""
    r = np.random.RandomState(seed)
    qty = r.randint(1, 51, rows).astype(np.float64)
    return {
        "l_shipdate": r.randint(_days(1992), _days(1998, 12, 1), rows),
        "l_discount": r.randint(0, 11, rows) / 100.0,
        "l_quantity": qty,
        "l_extendedprice": qty * r.randint(90_000, 200_000, rows) / 100.0,
        "l_returnflag": np.array(list("ANR"))[r.randint(0, 3, rows)],
        "l_linestatus": np.array(list("FO"))[r.randint(0, 2, rows)],
    }


COLS = _columns()


def _session(make=tpu_session, cols=COLS, **confs):
    s = make(**{"spark.rapids.sql.test.enabled": make is tpu_session,
                "spark.rapids.sql.variableFloatAgg.enabled": True, **confs})
    df = s.create_dataframe({
        "l_shipdate": (T.DATE, [int(v) for v in cols["l_shipdate"]]),
        "l_discount": (T.DOUBLE, [float(v) for v in cols["l_discount"]]),
        "l_quantity": (T.DOUBLE, [float(v) for v in cols["l_quantity"]]),
        "l_extendedprice": (T.DOUBLE,
                            [float(v) for v in cols["l_extendedprice"]]),
        "l_returnflag": (T.STRING, [str(v) for v in cols["l_returnflag"]]),
        "l_linestatus": (T.STRING, [str(v) for v in cols["l_linestatus"]]),
    }, num_partitions=2)
    s.register_view("lineitem", df)
    return s


def _text(year, discount, quantity):
    return Q6.format(y=year, y1=year + 1, lo=f"{discount - 0.01:.2f}",
                     hi=f"{discount + 0.01:.2f}", q=quantity)


def _reference(year, discount, quantity, cols=COLS):
    lo, hi = float(f"{discount - 0.01:.2f}"), float(f"{discount + 0.01:.2f}")
    m = ((cols["l_shipdate"] >= _days(year))
         & (cols["l_shipdate"] < _days(year + 1))
         & (cols["l_discount"] >= lo) & (cols["l_discount"] <= hi)
         & (cols["l_quantity"] < quantity))
    return float(np.sum(cols["l_extendedprice"][m] * cols["l_discount"][m]))


def _q6(s, params_):
    (got,), = s.sql(_text(*params_)).collect()
    want = _reference(*params_)
    assert got == pytest.approx(want, rel=1e-11), params_
    return got


def _shape(s, plan):
    """The shape ``session.plan_bound`` keys ``plan`` on."""
    return plan_shape(TpuOverrides(s.conf).rewrite_logical(plan)[0])


def _drawn(seed):
    """One of the clause's 80 combinations, by seed."""
    r = np.random.RandomState(seed)
    return (int(r.randint(1993, 1998)), int(r.randint(2, 10)) / 100.0,
            int(r.randint(24, 26)))


@pytest.fixture
def fresh_cache():
    shared_plan_cache().clear()
    yield shared_plan_cache()
    shared_plan_cache().clear()


# -- every set its own answer, one compile a shape ----------------------------


@pytest.fixture(scope="module")
def _stream_session():
    return _session()


@pytest.fixture
def stream(_stream_session):
    """One session that has already seen the template once (again, where
    another test has emptied the process's plan cache since)."""
    _q6(_stream_session, SETS[0])
    return _stream_session


@pytest.mark.parametrize("set_", SETS, ids=[f"q6_s{i}" for i in range(8)])
def test_each_of_the_eight_sets_equals_its_own_reference(stream, set_):
    _q6(stream, set_)
    m = stream.last_metrics
    assert (m["compileCount"], m["planShapeHit"], m["boundParams"]) == \
        (0, 1, 5), m
    assert m["foldedExprs"] == 2 and m["bakedLiterals"] == 0


@pytest.mark.parametrize("seed", range(16))
def test_a_set_drawn_from_the_80_equals_its_own_reference(stream, seed):
    set_ = _drawn(seed)
    _q6(stream, set_)
    _q6(stream, set_)                      # and its repeat
    m = stream.last_metrics
    assert (m["compileCount"], m["planShapeHit"], m["boundParams"]) == \
        (0, 1, 5), m


def test_the_same_text_ten_times_compiles_once(fresh_cache):
    s = _session()
    compiles = []
    for _ in range(10):
        _q6(s, SETS[0])
        compiles.append(s.last_metrics["compileCount"])
    assert compiles[0] > 0 and compiles[1:] == [0] * 9, compiles
    stats = fresh_cache.stats()
    assert stats["plan_cache_misses"] >= 1
    assert stats["plan_cache_entries"] == 1


def test_interleaved_sets_never_return_anothers_answer(stream):
    answers = {set_: _reference(*set_) for set_ in SETS}
    assert len(set(answers.values())) == 8
    r = np.random.RandomState(3)
    for i in r.randint(0, 8, 40):
        assert _q6(stream, SETS[i]) == pytest.approx(answers[SETS[i]],
                                                     rel=1e-11)
        assert stream.last_metrics["compileCount"] == 0


def test_two_threads_and_two_sessions_share_the_cache_not_the_values(stream):
    """Values are bound on the execution, never written into the shared
    plan: two sessions interleaving sets on two threads each get their
    own set's answer, from one set of executables."""
    other = tpu_session(**dict(stream.conf._settings))
    other.register_view("lineitem", stream.table("lineitem"))
    sessions = [stream, other]
    errors, compiled = [], []

    def client(s, offset):
        try:
            for k in range(24):
                set_ = SETS[(k + offset) % 8]
                _out, m = s.execute_with_metrics(s.sql(_text(*set_)).plan)
                got = _out.to_pydict()["revenue"][0]
                if got != pytest.approx(_reference(*set_), rel=1e-11):
                    errors.append((set_, got))
                compiled.append(m["compileCount"])
        except BaseException as e:   # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=client, args=(s, 3 * i))
               for i, s in enumerate(sessions)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors[:3]
    assert sum(compiled) == 0, compiled


def test_the_folded_to_date_is_lifted_and_explain_prints_the_values(stream):
    _q6(stream, (1996, 0.04, 25))
    assert stream.last_metrics["foldedExprs"] == 2
    assert stream.last_metrics["boundParams"] == 5
    explain = stream.last_explain
    assert (f"folded 2: ToDate(lit('1996-01-01')) -> lit({_days(1996)}:date), "
            f"ToDate(lit('1997-01-01')) -> lit({_days(1997)}:date)") in explain
    absorbed = explain.splitlines()[-1]
    for shown in (f"lit({_days(1996)})", f"lit({_days(1997)})", "lit(0.03)",
                  "lit(0.05)", "lit(25)"):
        assert shown in absorbed, absorbed
    # the next query's explain prints ITS values, from the same entry
    _q6(stream, (1993, 0.08, 24))
    assert stream.last_metrics["planShapeHit"] == 1
    absorbed = stream.last_explain.splitlines()[-1]
    assert f"lit({_days(1993)})" in absorbed and "lit(0.09)" in absorbed
    assert "lit(25)" not in absorbed and "lit(24)" in absorbed
    assert f"lit({_days(1993)})" in stream.explain_last(metrics=True)


def test_the_held_statement_and_the_dataframe_api_share_the_texts_shape(
        stream):
    held = stream.sql(_text(1995, 0.03, 25))
    for _ in range(3):
        (got,), = held.collect()
        assert got == pytest.approx(_reference(1995, 0.03, 25), rel=1e-11)
        assert stream.last_metrics["compileCount"] == 0
        assert stream.last_metrics["planShapeHit"] == 1
    # the same tree through the DataFrame API, Python constants for literals
    c = F.col
    api = stream.table("lineitem").filter(
        (((c("l_shipdate") >= F.to_date(F.lit("1997-01-01")))
          & (c("l_shipdate") < F.to_date(F.lit("1998-01-01"))))
         & ((c("l_discount") >= 0.06) & (c("l_discount") <= 0.08)))
        & (c("l_quantity") < 24)).agg(
        F.sum(c("l_extendedprice") * c("l_discount")).alias("revenue")
    ).select(c("revenue").alias("revenue"))
    assert _shape(stream, api.plan).values == \
        (_days(1997), _days(1998), 0.06, 0.08, 24)
    assert _shape(stream, api.plan).fingerprint == \
        _shape(stream, held.plan).fingerprint
    (got,), = api.collect()
    assert got == pytest.approx(_reference(1997, 0.07, 24), rel=1e-11)
    assert stream.last_metrics["compileCount"] == 0
    assert stream.last_metrics["planShapeHit"] == 1


def test_q1_with_delta_60_and_120_shares_one_shape(fresh_cache):
    """Clause 2.4.1.3: DELTA in [60 .. 120] days before 1998-12-01."""
    s = _session()
    compiles = []
    for delta in (60, 120, 60, 90):
        day = datetime.date(1998, 12, 1) - datetime.timedelta(days=delta)
        rows = s.sql(Q1.format(day=day.isoformat())).collect()
        compiles.append(s.last_metrics["compileCount"])
        cut = (day - datetime.date(1970, 1, 1)).days
        m = COLS["l_shipdate"] <= cut
        keys = sorted(set(zip(COLS["l_returnflag"][m],
                              COLS["l_linestatus"][m])))
        assert [r[:2] for r in rows] == [(str(a), str(b)) for a, b in keys]
        for row, (flag, status) in zip(rows, keys):
            g = m & (COLS["l_returnflag"] == flag) \
                & (COLS["l_linestatus"] == status)
            assert row[2] == pytest.approx(COLS["l_quantity"][g].sum(),
                                           rel=1e-11)
            assert row[3] == pytest.approx(
                (COLS["l_extendedprice"][g]
                 * (1 - COLS["l_discount"][g])).sum(), rel=1e-11)
            assert row[4] == pytest.approx(COLS["l_discount"][g].mean(),
                                           rel=1e-11)
            assert row[5] == int(g.sum())
    assert compiles[0] > 0 and compiles[1:] == [0, 0, 0], compiles
    assert s.last_metrics["planShapeHit"] == 1
    assert fresh_cache.stats()["plan_cache_entries"] == 1


# -- what stays baked makes a new shape, and a right answer -------------------


def _where(s, predicate, select="sum(l_extendedprice)"):
    rows = s.sql(f"SELECT {select} FROM lineitem WHERE {predicate}").collect()
    return rows, dict(s.last_metrics)


NEW_SHAPES = {
    # name: (first, second) — same template, something baked differs
    "another_type": ("l_quantity < 24", "l_quantity < 24.5"),
    "null_literal": ("l_quantity < 24", "l_quantity < NULL"),
    "string_literal": ("l_returnflag = 'A'", "l_returnflag = 'N'"),
    "in_list_length": ("l_quantity IN (1, 2)", "l_quantity IN (1, 2, 3)"),
}


@pytest.mark.parametrize("case", list(NEW_SHAPES))
def test_what_stays_baked_is_a_new_shape_and_a_right_answer(case):
    s = _session()
    first, second = NEW_SHAPES[case]
    _where(s, first)
    rows, m = _where(s, second)
    assert m["planShapeHit"] == 0, case
    q, flag, price = (COLS["l_quantity"], COLS["l_returnflag"],
                      COLS["l_extendedprice"])
    want = {"another_type": price[q < 24.5].sum(),
            "null_literal": None,
            "string_literal": price[flag == "N"].sum(),
            "in_list_length": price[np.isin(q, (1, 2, 3))].sum()}[case]
    got = rows[0][0]
    assert got is None if want is None else \
        got == pytest.approx(want, rel=1e-11)
    # and the same thing again IS the same shape
    _rows, m = _where(s, second)
    assert m["planShapeHit"] == 1 and m["compileCount"] == 0


def test_a_limit_and_a_round_scale_are_shapes_a_compared_value_is_not():
    s = _session()
    price, q = COLS["l_extendedprice"], COLS["l_quantity"]
    text = ("SELECT round(l_extendedprice * 0.5, {scale}) AS p FROM lineitem "
            "WHERE l_quantity = {qty} LIMIT {n}")

    def run(qty, scale, n):
        rows = s.sql(text.format(qty=qty, scale=scale, n=n)).collect()
        m = 10.0 ** scale
        assert [r[0] for r in rows] == pytest.approx(
            list(np.floor(price[q == qty][:n] * 0.5 * m + 0.5) / m),
            rel=1e-12)
        return s.last_metrics

    assert run(50, 1, 5)["bakedLiterals"] == 1        # 0.5, below round
    assert run(49, 1, 5)["planShapeHit"] == 1         # the value is bound
    assert s.last_metrics["compileCount"] == 0
    assert run(49, 2, 5)["planShapeHit"] == 0         # the scale is baked
    assert run(49, 2, 7)["planShapeHit"] == 0         # and so is the count
    assert run(50, 2, 7)["planShapeHit"] == 1


def test_what_plan_shape_lifts_and_what_it_leaves():
    from spark_rapids_tpu.batch import HostBatch
    batch = HostBatch.from_pydict({"a": (T.INT, [1]), "b": (T.DOUBLE, [1.0])})
    scan = L.InMemoryScan([batch], batch.schema)
    a = ColumnRef("a", T.INT)
    five = Literal(5)
    plan = L.Project([GreaterThan(a, five), Literal(7)], ["p", "seven"],
                     L.Filter(GreaterThan(a, five), L.Limit(3, scan)))
    shape = plan_shape(plan)
    # one Literal object in two liftable places takes one slot; a literal
    # that is the whole expression stays
    assert shape.values == (5,) and shape.dtypes == (T.INT,)
    assert shape.baked == 1
    assert shape.fingerprint.count("Literal?0:integer") == 2
    assert "n=3" in shape.fingerprint
    assert shape.pinned == [batch]
    # non-mutating, and the value form is what it was
    assert plan.children[0].condition.children[1] is five
    assert five.slot is None and "value=5" in plan_fingerprint(plan)
    assert plan_shape(plan, lift=False).values == ()
    assert plan_shape(plan, lift=False).fingerprint == plan_fingerprint(plan)
    # another value is the same shape, another type is not
    other = L.Project([GreaterThan(a, Literal(9)), Literal(7)],
                      ["p", "seven"],
                      L.Filter(GreaterThan(a, Literal(9)),
                               L.Limit(3, scan)))
    assert plan_shape(other).fingerprint != shape.fingerprint  # two objects
    assert plan_shape(other).values == (9, 9)
    nine = Literal(9)
    same = L.Project([GreaterThan(a, nine), Literal(7)], ["p", "seven"],
                     L.Filter(GreaterThan(a, nine), L.Limit(3, scan)))
    assert plan_shape(same).fingerprint == shape.fingerprint
    wide = Literal(2 ** 40)
    assert plan_shape(L.Project(
        [GreaterThan(a, wide), Literal(7)], ["p", "seven"],
        L.Filter(GreaterThan(a, wide), L.Limit(3, scan)))
    ).fingerprint != shape.fingerprint


def test_a_filter_pushed_into_a_file_scan_keeps_its_values_in_the_shape(
        tmp_path):
    s = _session()
    path = str(tmp_path / "t")
    s.table("lineitem").write_parquet(path)
    s.register_view("files", s.read.parquet(path))
    answers = {}
    for q in (10, 20, 10):
        rows = s.sql("SELECT sum(l_extendedprice) FROM files "
                     f"WHERE l_quantity < {q}").collect()
        answers[q] = rows[0][0]
        assert rows[0][0] == pytest.approx(
            COLS["l_extendedprice"][COLS["l_quantity"] < q].sum(), rel=1e-11)
    shapes = {q: _shape(s, s.sql(
        f"SELECT sum(l_extendedprice) FROM files WHERE l_quantity < {q}"
    ).plan) for q in (10, 20)}
    assert shapes[10].fingerprint != shapes[20].fingerprint
    assert shapes[10].baked >= 1


# -- the cache: LRU lifetime, pins, bounds -------------------------------------


def test_an_entry_outlives_the_plan_object_that_made_it(fresh_cache):
    """The weak reference is gone: nothing held, the text comes back and
    finds its plan and its executables (today: every second time)."""
    import gc
    s = _session()
    _q6(s, SETS[0])
    s.last_exec_ctx = s.last_physical_plan = None
    gc.collect()
    assert fresh_cache.stats()["plan_cache_entries"] == 1
    _q6(s, SETS[1])
    assert s.last_metrics["compileCount"] == 0


def test_lru_by_count_and_by_pinned_bytes(fresh_cache, monkeypatch):
    s = tpu_session()

    def one_shot(n):
        df = s.create_dataframe({"a": list(range(n))})
        assert df.filter(F.col("a") >= 0).count() == n
        return df.plan

    fresh_cache.set_max_plans(4)
    try:
        for n in range(1, 9):
            one_shot(n)
        stats = fresh_cache.stats()
        assert stats["plan_cache_entries"] == 4
        assert stats["plan_cache_evictions"] >= 4
        # an entry pins the batch list its fingerprint names by id(): a
        # recycled id() cannot be taken for it while the entry lives
        from spark_rapids_tpu.batch import HostBatch
        pinned = [e.pinned for e in fresh_cache._plans.values()]
        assert all(p and isinstance(p[0], HostBatch) for p in pinned)
        assert stats["plan_cache_pinned_bytes"] > 0
        # what all entries pin together is bounded: the oldest go
        per_plan = 8 * 4096 + 4096
        monkeypatch.setattr(excache, "MAX_PINNED_BYTES", 2 * per_plan + 100)
        fresh_cache.set_max_plans(256)
        for _ in range(5):
            one_shot(4096)
        stats = fresh_cache.stats()
        assert stats["plan_cache_pinned_bytes"] <= 2 * per_plan + 100
        assert stats["plan_cache_entries"] <= 3
    finally:
        from spark_rapids_tpu.config import SERVE_PLAN_CACHE_MAX
        fresh_cache.set_max_plans(SERVE_PLAN_CACHE_MAX.get(s.conf))


def test_bound_scalars_are_kept_per_value_set_and_spelling(fresh_cache):
    s = _session()
    _q6(s, SETS[0])
    (entry,) = fresh_cache._plans.values()
    a = entry.bind((1, 2.0, 0.0))
    assert entry.bind((1, 2.0, 0.0)) is a
    assert entry.bind((1, 2.0, -0.0)) is not a      # 0.0 == -0.0, hash too
    assert entry.bind((True, 2.0, 0.0)) is not a    # 1 == True
    assert entry.bind(()).device == ()


# -- the binding itself --------------------------------------------------------


def test_a_lifted_literal_with_nothing_bound_raises_and_never_guesses():
    from spark_rapids_tpu.batch import HostBatch
    from spark_rapids_tpu.exprs.base import CpuEvalCtx
    lit = Literal(5, T.INT, slot=0)
    batch = HostBatch.from_pydict({"a": (T.INT, [1, 2])})
    with pytest.raises(RuntimeError, match="no bound parameters"):
        lit.cpu_eval(CpuEvalCtx(batch))
    assert repr(lit) == "lit(5)"
    bound = params.BoundParams((9,), (np.int32(9),))
    with params.executing(bound):
        assert list(lit.cpu_eval(CpuEvalCtx(batch)).values) == [9, 9]
        assert repr(lit) == "lit(9)"
        seen = []
        t = threading.Thread(target=lambda: seen.append(params.current()))
        t.start()
        t.join()
        assert seen == [bound]      # the only one open: a helper finds it
    assert params.current() is None
    with params.showing((4,)):
        assert repr(lit) == "lit(4)"


def test_a_program_that_takes_no_parameters_refuses_to_bake_one():
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.batch import HostBatch, host_to_device
    from spark_rapids_tpu.exprs.base import TpuEvalCtx
    from spark_rapids_tpu.utils.compile_registry import plan_jit
    lit = Literal(5, T.INT, slot=0)
    dev = host_to_device(HostBatch.from_pydict({"a": (T.INT, [1, 2])}))

    def body(b):
        return lit.tpu_eval(TpuEvalCtx(b)).data

    bound = params.BoundParams((9,), (jnp.asarray(9, jnp.int32),))
    with params.executing(bound):
        assert int(body(dev)[0]) == 9                      # eager: bound
        with pytest.raises(RuntimeError, match="no bound parameters"):
            jax.jit(body)(dev)                             # would bake 9
        program = plan_jit(body, label="test_params")
        assert int(program(dev)[0]) == 9
    with params.executing(params.BoundParams(
            (3,), (jnp.asarray(3, jnp.int32),))):
        assert int(program(dev)[0]) == 3                   # same executable
    assert program.jitted._cache_size() == 1


@pytest.mark.parametrize("confs,join_root", [
    ({}, True),
    ({"spark.rapids.sql.enabled": False}, False),
    ({"spark.rapids.sql.exec.Filter": False}, False),
], ids=["iterator_path", "cpu_operators", "cpu_filter_under_tpu_aggregate"])
def test_every_evaluation_path_reads_the_bound_value(confs, join_root,
                                                     fresh_cache):
    """Operator programs outside a stage program, the CPU operators'
    ``cpu_eval``, and a CPU operator feeding the device through the
    read-ahead thread all evaluate the shared plan's lifted literals."""
    s = _session(make=cpu_session if confs.get(
        "spark.rapids.sql.enabled") is False else tpu_session, **confs)
    s.conf.set("spark.rapids.sql.test.enabled", False)
    run = _q6
    if join_root:
        # a nested-loop join at the root inlines nothing, so the plan is
        # not pipeline-viable and Q6 below it runs operator by operator
        one = s.create_dataframe({"one": [1]})

        def run(s, set_):
            (got, _one), = s.sql(_text(*set_)).cross_join(one).collect()
            assert got == pytest.approx(_reference(*set_), rel=1e-11), set_
            assert "pipeline" not in s.last_metrics    # no stage program
    compiles = []
    for set_ in (SETS[0], SETS[1], SETS[2], SETS[1]):
        run(s, set_)
        compiles.append(s.last_metrics["compileCount"])
        assert s.last_metrics["boundParams"] == 5
    assert compiles[1:] == [0, 0, 0], compiles


def test_the_writer_executes_the_shared_plan_under_its_own_values(tmp_path):
    s = _session()
    for q in (5, 9):
        path = str(tmp_path / f"q{q}")
        s.sql("SELECT l_quantity, l_extendedprice FROM lineitem "
              f"WHERE l_quantity < {q}").write_parquet(path)
        back = s.read.parquet(path).collect()
        assert len(back) == int((COLS["l_quantity"] < q).sum())
        assert max(r[0] for r in back) == q - 1


def test_under_a_mesh_nothing_is_lifted():
    s = _session(**{"spark.rapids.shuffle.ici.enabled": True})
    if s._shuffle_mesh() is None:
        pytest.skip("one device: no mesh")
    _q6(s, SETS[0])
    assert s.last_metrics["boundParams"] == 0
    assert s.last_metrics["bakedLiterals"] == 5
    _q6(s, SETS[1])
    assert s.last_metrics["planShapeHit"] == 0


# -- spans and counters --------------------------------------------------------


def test_spans_and_counters_of_a_text_query(fresh_cache):
    s = _session()
    before = fresh_cache.stats()
    for set_ in SETS[:2]:
        _q6(s, set_)
        m = s.last_metrics
        spans = {(e.site, e.name): e for e in s.query_history()[-1].events
                 if e.kind == "span"}
        physical = spans[("plan", "physical")]
        for name, key in (("shape", "planShapeNs"), ("bind", "planBindNs")):
            sp = spans[("plan", name)]
            assert physical.t0 <= sp.t0 and sp.t1 <= physical.t1
            assert m[key] == sp.t1 - sp.t0
        assert ("plan", "fold") in spans          # a new text folds again
        assert m["parseNs"] > 0
        assert sum(m["critpath"].values()) == m["queryWallNs"]
        assert m["critpath"]["plan"] >= m["planShapeNs"] + m["planBindNs"]
    assert m["planShapeHit"] == 1 and m["boundParams"] == 5
    # a held statement neither folds nor fingerprints again, and carries
    # the one parse it was prepared with
    held = s.sql(_text(*SETS[2]))
    held.collect()
    parse_ns = s.last_metrics["parseNs"]
    held.collect()
    assert s.last_metrics["parseNs"] == parse_ns
    assert not [e for e in s.query_history()[-1].events
                if (e.site, e.name) == ("plan", "fold")]
    stats = fresh_cache.stats()
    assert stats["plan_cache_entries"] == 1
    assert stats["plan_cache_misses"] == before["plan_cache_misses"] + 1
    assert stats["plan_cache_hits"] == before["plan_cache_hits"] + 3
